"""core/buckets.py assignment logic — pure units, no devices needed."""
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from repro.compat import PartitionSpec as P
from repro.core import buckets
from repro.core.plan import ParamPlan, Plan


def fake_mesh(**axes):
    return SimpleNamespace(shape=dict(axes), axis_names=tuple(axes))


def fake_rt(mesh, *, bucket_bytes=4 * 2**20, kind="train", batch=("data",),
            replicas=8, experts=0, tied=False, opsw=True,
            param_dtype="float32", wire_dtype="float32"):
    return SimpleNamespace(
        mesh=mesh,
        batch_axes=batch,
        replicas=replicas,
        param_dtype=jnp.dtype(param_dtype),
        wire_dtype=jnp.dtype(wire_dtype),
        model_cfg=SimpleNamespace(n_experts=experts, tie_embeddings=tied),
        shape_cfg=SimpleNamespace(kind=kind),
        run_cfg=SimpleNamespace(bucket_bytes=bucket_bytes, opsw=opsw),
    )


def leaf(name, shape, method="allreduce", sparse=False, pspec=P(None, None),
         dtype_bytes=4):
    n = 1
    for d in shape:
        n *= d
    return ParamPlan(name=name, method=method, pspec=pspec, opt_pspec=pspec,
                     wire_dtype=jnp.float32, sparse=sparse,
                     bytes=n * dtype_bytes)


def fake_plan(leaves, mesh, embed_method="allreduce"):
    return Plan(model_cfg=None, run_cfg=None, shape_cfg=None, mesh=mesh,
                rules=None, params={p.name: p for p in leaves},
                embed_method=embed_method)


MESH = fake_mesh(data=8, model=1)


def test_effective_pspec_drops_size1_axes():
    assert buckets._effective_pspec(P("model", None), MESH) == ()
    assert buckets._effective_pspec(P(None, "model"), MESH) == ()
    assert buckets._effective_pspec(P(("model",), None), MESH) == ()
    big = fake_mesh(data=2, model=4)
    assert buckets._effective_pspec(P("model", None), big) == ("model",)


def test_assign_groups_and_fills_by_bucket_bytes():
    leaves = [leaf(f"w{i}", (64, 64)) for i in range(10)]   # 16 KiB each
    plan = fake_plan(leaves, MESH)
    rt = fake_rt(MESH, bucket_bytes=4 * 16384)              # 4 params/bucket
    bp = buckets.assign_buckets(plan, rt)
    assert bp is not None
    assert [len(b.idx) for b in bp.buckets] == [4, 4, 2]
    assert bp.n_params == 10
    assert bp.wire_bytes == 10 * 16384
    # reverse-topological fill: bucket 0 holds the LAST-forward parameters,
    # whose gradients the backward produces first (overlap issue order)
    assert bp.buckets[0].idx == (9, 8, 7, 6)
    assert bp.buckets[-1].idx == (1, 0)
    # one flat buffer each, element counts preserved
    assert all(b.nbytes == sum(b.sizes) * 4 for b in bp.buckets)


def test_assign_single_bucket_when_under_cap():
    leaves = [leaf(f"w{i}", (8, 8), pspec=P("model", None) if i % 2 else
              P(None, "model")) for i in range(6)]
    plan = fake_plan(leaves, MESH)
    bp = buckets.assign_buckets(plan, fake_rt(MESH))
    # size-1 'model' shardings are physically identical -> one fused buffer,
    # members in reverse flatten order
    assert len(bp.buckets) == 1
    assert bp.buckets[0].idx == tuple(reversed(range(6)))


def test_sparse_methods_keep_their_own_exchange():
    leaves = [leaf("w0", (32, 32)),
              leaf("emb", (128, 32), method="mpi_gatherv", sparse=True)]
    plan = fake_plan(leaves, MESH, embed_method="mpi_gatherv")
    bp = buckets.assign_buckets(plan, fake_rt(MESH))
    assert bp.n_params == 1                      # the gatherv table stays out
    assert plan.embed_method == "mpi_gatherv"


def test_tied_gatherv_table_folds_into_the_bucket():
    leaves = [leaf("w0", (32, 32)),
              leaf("emb", (128, 32), method="mpi_gatherv", sparse=True)]
    plan = fake_plan(leaves, MESH, embed_method="mpi_gatherv")
    bp = buckets.assign_buckets(plan, fake_rt(MESH, tied=True))
    assert plan.embed_method == "allreduce"      # coherence flip
    assert bp.n_params == 2


@pytest.mark.parametrize("veto", [
    dict(bucket_bytes=0),
    dict(kind="decode"),
    dict(batch=(), replicas=1),
    dict(experts=8),                             # MoE opens its own shard_map
])
def test_gate_vetos(veto):
    plan = fake_plan([leaf("w0", (32, 32))], MESH)
    assert buckets.assign_buckets(plan, fake_rt(MESH, **veto)) is None


def test_gate_vetos_live_tp_axis_and_fsdp():
    tp = fake_mesh(data=2, model=4)
    plan = fake_plan([leaf("w0", (32, 32))], tp)
    assert buckets.assign_buckets(plan, fake_rt(tp, batch=("data",),
                                                replicas=2)) is None
    plan2 = fake_plan([leaf("w0", (32, 32), method="fsdp")], MESH)
    assert buckets.assign_buckets(plan2, fake_rt(MESH)) is None


def test_stats_charge_the_latency_model():
    leaves = [leaf(f"w{i}", (64, 64)) for i in range(10)]
    plan = fake_plan(leaves, MESH)
    bp = buckets.assign_buckets(plan, fake_rt(MESH))
    s = bp.stats()
    assert s["n_collectives_dense"] == 1
    assert s["n_collectives_unbucketed"] == 10
    saved = s["est_seconds_unbucketed"] - s["est_seconds"]
    assert saved == pytest.approx(9 * buckets.HW.link_latency)


def test_two_level_schedule_on_multi_host_mesh(tmp_path):
    prof = tmp_path / "hw.json"
    prof.write_text('{"inter_bw": 12.5e9, "inter_latency": 10e-6}')
    mesh = fake_mesh(pod=2, data=4, model=1)
    # 1 MiB bucket: bandwidth-dominated, two-level wins (only b/L crosses
    # the slow tier); 256 B bucket: latency-dominated, the extra 2α₁ of the
    # two-level schedule loses to the flat ring
    leaves = [leaf("big", (512, 512)), leaf("small", (8, 8))]
    plan = fake_plan(leaves, mesh)
    rt = fake_rt(mesh, batch=("pod", "data"), replicas=8,
                 bucket_bytes=1 << 20)
    rt.run_cfg.hw_profile = str(prof)
    bp = buckets.assign_buckets(plan, rt)
    assert bp.hosts == 2
    by_name = {b.idx: b.schedule for b in bp.buckets}
    assert by_name[(0,)] == "two_level"      # the 1 MiB buffer
    assert by_name[(1,)] == "ring"           # the 256 B buffer
    s = bp.stats()
    assert s["n_two_level"] == 1 and s["hosts"] == 2 and s["overlap"]


def test_single_host_mesh_keeps_flat_ring_even_with_profile(tmp_path):
    prof = tmp_path / "hw.json"
    prof.write_text('{"inter_bw": 12.5e9, "inter_latency": 10e-6}')
    leaves = [leaf("w0", (512, 512))]
    plan = fake_plan(leaves, MESH)
    rt = fake_rt(MESH)
    rt.run_cfg.hw_profile = str(prof)
    bp = buckets.assign_buckets(plan, rt)
    assert bp.hosts == 1
    assert all(b.schedule == "ring" for b in bp.buckets)


def test_stats_count_exactly_the_one_member_ring_buckets(tmp_path):
    """``n_shaped_buckets`` counts the buckets that keep their member's
    shape: one member on the ring schedule. A leaf over ``bucket_bytes``
    sits alone; small leaves share; a one-member two-level bucket stays
    flat and is not counted."""
    leaves = [leaf("big0", (1024, 1024)), leaf("big1", (1024, 1024))] + \
        [leaf(f"w{i}", (64, 64)) for i in range(8)]
    bp = buckets.assign_buckets(fake_plan(leaves, MESH), fake_rt(MESH))
    shaped = [b for b in bp.buckets if b.shaped]
    assert sorted(b.idx for b in shaped) == [(0,), (1,)]
    assert all(len(b.idx) == 8 for b in bp.buckets if not b.shaped)
    assert bp.stats()["n_shaped_buckets"] == 2

    prof = tmp_path / "hw.json"
    prof.write_text('{"inter_bw": 12.5e9, "inter_latency": 10e-6}')
    mesh = fake_mesh(pod=2, data=4, model=1)
    plan = fake_plan([leaf("big", (512, 512)), leaf("small", (8, 8))], mesh)
    rt = fake_rt(mesh, batch=("pod", "data"), replicas=8,
                 bucket_bytes=1 << 20)
    rt.run_cfg.hw_profile = str(prof)
    bp = buckets.assign_buckets(plan, rt)
    assert [(b.idx, b.schedule, b.shaped) for b in bp.buckets] == \
        [((1,), "ring", True), ((0,), "two_level", False)]
    s = bp.stats()
    assert s["n_shaped_buckets"] == 1 and s["n_two_level"] == 1


def test_monitor_surfaces_shaped_bucket_count():
    from repro.runtime.monitor import StepMonitor
    bp = buckets.assign_buckets(
        fake_plan([leaf("w0", (1024, 1024))], MESH), fake_rt(MESH))
    mon = StepMonitor()
    mon.note_exchange(bp.stats())
    mon.start()
    assert mon.stop(tokens=8)["n_shaped_buckets"] == 1
