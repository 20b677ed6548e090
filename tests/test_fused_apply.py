"""Fused bucket-apply integration: the bucket-native optimizer update is
bit-identical to the per-param path at f32 end to end, fused optimizer
memory migrates through a bucket-regrouping replan, and checkpoints hold
the canonical per-param layout (save/restore round-trips through a fused
trainer exactly). Unit-level layout/bitwise tests live in test_optim.py;
the sparse-push overlap HLO regression lives in test_perf_paths.py."""
import pytest

from conftest import distributed_run

REGROUP_CODE = """
import dataclasses
from repro.configs import get_config, reduced, RunConfig, ShapeConfig
from repro.core.transform import estimate_census, get_runner
from repro.data import SyntheticLM
from repro.optim.optimizer import is_fused

cfg = reduced(get_config("seamless-m4t-medium"))
shape = ShapeConfig("tiny", seq_len=32, global_batch=8, kind="train")
# mpi: the decoder vocab table keeps its gatherv row-buffer exchange, so
# the fused apply coexists with an unbucketed sparse leaf in the same step
kw = dict(attention_impl="naive", remat="none", param_dtype="float32",
          compute_dtype="float32", wire_dtype="float32", comm_mode="mpi",
          bucket_bytes=256 * 1024)
ds = SyntheticLM(cfg.vocab_size, 32, 8, is_encdec=True,
                 frames_dim=cfg.d_model, frames_len=8)

def sig(plan):
    return [[list(b.idx), b.key[1]] for b in plan.bucket_plan.buckets]

mesh = make_mesh((8, 1), ("data", "model"))
with use_mesh(mesh):
    out = {}
    for fused in (True, False):
        run = get_runner(cfg, shape, RunConfig(**kw, fused_apply=fused),
                         mesh=mesh)
        losses = [float(run.run(ds.batch(i))["loss"]) for i in range(2)]
        rec = {"pre_sig": sig(run.plan),
               "pre_fused": bool(is_fused(run.state)),
               "pre_flag": bool(run.plan.fused_apply)}
        # regroup the buckets: a quarter of the budget makes more, smaller
        # buckets; force the hot-swap so the optimizer memory must migrate
        run.rt.run_cfg = dataclasses.replace(run.rt.run_cfg,
                                             bucket_bytes=64 * 1024)
        diff = run.replan(estimate_census(run.model, run.rt), force=True)
        losses += [float(run.run(ds.batch(i))["loss"]) for i in range(2, 4)]
        rec.update(losses=losses, post_sig=sig(run.plan),
                   post_fused=bool(is_fused(run.state)),
                   post_flag=bool(run.plan.fused_apply),
                   rebuilt=bool(diff.get("rebuilt")))
        out[str(fused)] = rec
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.distributed
def test_fused_apply_bit_exact_across_regrouping_replan():
    """The fused-apply tentpole regression: fused vs per-param trajectories
    are bitwise equal at f32 — including across a forced replan that
    regroups the bucket layout, which must migrate the fused m/v/EMA
    buffers through the canonical per-param layout (old layout unfuses,
    new layout re-fuses)."""
    res = distributed_run(REGROUP_CODE, devices=8, timeout=900)
    f, p = res["True"], res["False"]
    assert f["pre_flag"] and f["pre_fused"], res
    assert not p["pre_flag"] and not p["pre_fused"], res
    # the replan genuinely regrouped the layout (same on both runners)
    assert f["pre_sig"] != f["post_sig"], res
    assert f["post_sig"] == p["post_sig"], res
    # ...and the fused state survived the migration
    assert f["rebuilt"] and f["post_fused"] and f["post_flag"], res
    # trajectory continuity: bitwise equal before AND after the regroup
    assert f["losses"] == p["losses"], res


CKPT_CODE = """
import tempfile
from repro.configs import get_config, reduced, RunConfig, ShapeConfig
from repro.data import SyntheticLM
from repro.optim.optimizer import is_fused
from repro.runtime.trainer import Trainer, TrainerConfig

cfg = reduced(get_config("phi3-medium-14b"), vocab=256)
shape = ShapeConfig("tiny", seq_len=32, global_batch=8, kind="train")
rc = RunConfig(attention_impl="naive", remat="none", param_dtype="float32",
               compute_dtype="float32", wire_dtype="float32")
mesh = make_mesh((8, 1), ("data", "model"))

def drive(total, ckpt_dir, resume=False):
    ds = SyntheticLM(cfg.vocab_size, 32, 8)
    tcfg = TrainerConfig(total_steps=total, ckpt_dir=ckpt_dir, ckpt_every=4)
    with use_mesh(mesh):
        t = Trainer(cfg, shape, rc, tcfg, ds, mesh=mesh)
        if resume:
            t.maybe_restore()
        stats = []
        t.run(on_metrics=lambda s, m: stats.append((s, m)))
    return t, stats

t_ref, ref = drive(8, None)
d = tempfile.mkdtemp()
t_a, first = drive(4, d)
t_b, second = drive(8, d, resume=True)
res = {
    "fused": [bool(is_fused(t.state)) for t in (t_ref, t_a, t_b)],
    "resumed_from": second[0][0],
    "ref_losses": [float(m["loss"]) for _, m in ref],
    "split_losses": [float(m["loss"]) for _, m in first + second],
    "exchange": "exchange" in ref[-1][1],
}
print("RESULT:" + json.dumps(res))
"""


@pytest.mark.distributed
def test_fused_trainer_checkpoint_trajectory_continuity():
    """Checkpoints written by a fused trainer hold the canonical per-param
    layout: a run interrupted at step 4 and resumed by a fresh trainer
    reproduces the uninterrupted 8-step f32 trajectory exactly (restore
    lands in a canonical template, then re-fuses onto the live plan)."""
    res = distributed_run(CKPT_CODE, devices=8, timeout=900)
    assert all(res["fused"]), res                # fused layout was live
    assert res["resumed_from"] == 5, res         # restore picked up step 4
    assert res["split_losses"] == res["ref_losses"], res
    assert res["exchange"], res


SHAPED_CODE = """
import re
from repro.configs import get_config, reduced, RunConfig, ShapeConfig
from repro.core.transform import get_runner
from repro.data import SyntheticLM

cfg = reduced(get_config("parallax-lm"), vocab=256, d_model=32, d_ff=64,
              layers=1)
shape = ShapeConfig("tiny", seq_len=8, global_batch=8, kind="train")
kw = dict(attention_impl="naive", remat="none", param_dtype="float32",
          compute_dtype="float32", wire_dtype="float32")
ds = SyntheticLM(cfg.vocab_size, 8, 8)

def all_reduce_operands(run):
    # every operand shape of every all-reduce: XLA's combiner may merge
    # bucket psums into one variadic all-reduce, each operand keeps its own
    txt = run.train_step.lower(run.state, ds.batch(0)).compile().as_text()
    out = []
    for line in txt.splitlines():
        m = re.search(r"= (.*?) all-reduce(?:-start)?\\(", line)
        if m:
            out += [[int(d) for d in dims.split(",") if d]
                    for dims in re.findall(r"[a-z0-9]+\\[([0-9,]*)\\]",
                                           m.group(1))]
    return out

mesh = make_mesh((4, 1), ("data", "model"))
with use_mesh(mesh):
    # 4 KiB: every 2-D leaf of this model sits alone in its bucket;
    # 1 MiB: they all share one flat bucket
    runs = {"shaped": RunConfig(**kw, bucket_bytes=4096),
            "unfused": RunConfig(**kw, bucket_bytes=4096, fused_apply=False),
            "shared": RunConfig(**kw, bucket_bytes=1 << 20)}
    out = {}
    for name, rc in runs.items():
        run = get_runner(cfg, shape, rc, mesh=mesh)
        bp = run.plan.bucket_plan
        shapes = [list(x.shape) for x in jax.tree.leaves(run.state.params)]
        rec = {"members": [[shapes[i] for i in b.idx] for b in bp.buckets],
               "shaped": [b.shaped for b in bp.buckets],
               "n_shaped": bp.stats()["n_shaped_buckets"],
               "fused": bool(run.plan.fused_apply),
               "operands": all_reduce_operands(run)}
        if run.plan.fused_apply:
            rec["m_bucket"] = [list(x.shape) for x in run.state.m["bucket"]]
        rec["losses"] = [float(run.run(ds.batch(i))["loss"])
                         for i in range(2)]
        out[name] = rec
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.distributed
def test_one_member_bucket_keeps_leaf_shape_through_psum_and_apply():
    """A one-member bucket never flattens: its all-reduce operand keeps the
    2-D leaf's shape (no rank-1 all-reduce of that element count), its
    fused m/v/EMA buffers keep it too, and the 2-step f32 losses equal the
    per-param apply and a plan where the same leaves share a flat bucket."""
    res = distributed_run(SHAPED_CODE, devices=4, timeout=900)
    sh, shared = res["shaped"], res["shared"]
    two_d = [m[0] for m, s in zip(sh["members"], sh["shaped"])
             if s and len(m[0]) == 2]
    assert sh["fused"] and two_d, res
    assert sh["n_shaped"] == sum(sh["shaped"]) == len(sh["members"]), res
    for leaf_shape in two_d:
        n = leaf_shape[0] * leaf_shape[1]
        assert leaf_shape in sh["operands"], res
        assert [n] not in sh["operands"], res
        assert leaf_shape in sh["m_bucket"], res
    # the control: sharing a bucket puts those leaves on a flat buffer
    assert shared["n_shaped"] == 0 and len(shared["members"]) == 1, res
    assert not any(s in shared["operands"] for s in two_d), res
    assert sh["losses"] == res["unfused"]["losses"] == shared["losses"], res
