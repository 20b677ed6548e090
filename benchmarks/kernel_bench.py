"""Kernel micro-benchmarks: interpret-mode correctness timing is meaningless
for perf, so we report the kernel's analytic VMEM working set + MXU-aligned
tile shapes and the wall time of the *reference* path on CPU (the quantity
that is measurable here), per shape.

Plus the PR-level numbers, written to ``BENCH_kernels.json``:

  * fused bucket-apply vs per-param optimizer apply (optim/optimizer.py
    ``update_fused``): wall time over the same flat post-psum buffers — the
    per-param path pays the exchange boundary's per-leaf materialisation
    before the update (modelled as a separate jitted unflatten stage);
    bit-equality of the resulting states is asserted;
  * the measured autotune sweep (kernels/autotune.py) on a small shape: the
    argmin is taken over a candidate set that always contains the fixed
    block 0, so tuned can never lose to fixed — asserted — plus the
    roofline ranking at TPU constants for a production-sized table (what
    the sweep targets on real hardware);
  * distributed switch contrasts on 8 fake devices: fused_apply on/off and
    kernel_autotune on/off (Pallas path, cache pre-seeded with a 128-lane
    feature tile) must both hold a 0.0 f32 loss divergence — neither switch
    may change the math, only the schedule/layout.

    PYTHONPATH=src python -m benchmarks.run kernels
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, run_with_devices, time_fn
from repro.kernels import ref

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")


def _ref_paths() -> None:
    for (b, s, h, d) in [(1, 512, 8, 64), (1, 1024, 8, 128)]:
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, s, h, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)
        fn = jax.jit(lambda a, b2, c: ref.flash_attention_ref(a, b2, c))
        sec = time_fn(fn, q, k, v)
        vmem_kb = (128 * d * 2 * 3 + 128 * d * 4 + 128 * 8) / 1024
        emit(f"kernels/flash_ref/b{b}s{s}h{h}d{d}", sec * 1e6,
             f"kernel_vmem_kb={vmem_kb:.0f};blocks=128x128")
    # sparse hot path: PS pull (embed_gather) / push (embed_scatter_add).
    # Interpret-mode wall time is meaningless, so we time the jnp reference
    # (what a TPU-less run executes) and report the kernel's analytic DMA
    # working set: ids live in SMEM, one tile-aligned (8, E) f32 row group
    # moves per grid step — n_ids·8·E·itemsize streamed, never the (Vs, E)
    # table.
    for (vs, e, n) in [(4096, 512, 1024), (32768, 1024, 4096)]:
        ks = jax.random.split(jax.random.key(2), 3)
        table = jax.random.normal(ks[0], (vs, e), jnp.float32)
        ids = jax.random.randint(ks[1], (n,), -vs // 2, 2 * vs)
        rows = jax.random.normal(ks[2], (n, e), jnp.float32)
        uids = jnp.sort(jnp.unique(ids, size=n, fill_value=2 * vs))
        gfn = jax.jit(lambda t, i: ref.embed_gather_ref(t, i, 0))
        sec = time_fn(gfn, table, ids)
        emit(f"kernels/embed_gather_ref/v{vs}e{e}n{n}", sec * 1e6,
             f"dma_kb={n * 8 * e * 4 / 1024:.0f};"
             f"ids_smem_kb={n * 4 / 1024:.0f}")
        sfn = jax.jit(lambda i, r: ref.embed_scatter_add_ref(i, r, vs))
        sec = time_fn(sfn, uids, rows)
        emit(f"kernels/embed_scatter_ref/v{vs}e{e}n{n}", sec * 1e6,
             f"dma_kb={n * e * 4 / 1024:.0f};blocks=8x{e}")
    for (b, s, h, e) in [(2, 512, 4, 64)]:
        ks = jax.random.split(jax.random.key(1), 5)
        r = jax.random.normal(ks[0], (b, s, h, e), jnp.float32)
        kk = jax.random.normal(ks[1], (b, s, h, e), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, h, e), jnp.float32)
        lw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, e)) * 0.3 - 1)
        u = jax.random.normal(ks[4], (h, e)) * 0.1
        st = jnp.zeros((b, h, e, e), jnp.float32)
        fn = jax.jit(lambda *a: ref.wkv_ref(*a)[0])
        sec = time_fn(fn, r, kk, v, lw, u, st)
        emit(f"kernels/wkv_ref/b{b}s{s}h{h}e{e}", sec * 1e6,
             f"state_vmem_kb={e*e*4/1024:.0f};chunk=32")


def _fused_apply_bench() -> dict:
    """Optimizer apply over the same post-psum flat buffers. The non-fused
    step materialises per-leaf gradient arrays at the manual exchange
    region's boundary (one out_spec per leaf) before the optimizer walks
    them — modelled here as two jitted stages (unflatten, then update), so
    the leaf arrays hit memory exactly as the shard_map boundary forces
    them to. The fused path reads the flat buffers directly against the
    bucket-fused m/v layout in a single stage — the unflatten/reflatten
    write+read of the full param footprint never happens. Same math —
    states must be bitwise equal."""
    import numpy as np
    from repro.core.buckets import Bucket, BucketPlan
    from repro.optim.optimizer import adamw, fuse_state, unfuse_state

    shape, n_leaves, per_bucket = (512, 512), 16, 4
    sz = shape[0] * shape[1]
    ks = jax.random.split(jax.random.key(3), 2 * n_leaves)
    params = {f"w{i:02d}": jax.random.normal(ks[i], shape, jnp.float32)
              for i in range(n_leaves)}
    bufs = [jnp.concatenate(
        [jax.random.normal(ks[n_leaves + i], (sz,), jnp.float32)
         for i in range(k * per_bucket, (k + 1) * per_bucket)])
        for k in range(n_leaves // per_bucket)]
    buckets = [Bucket(key=("allreduce", "float32", ()),
                      idx=tuple(range(k * per_bucket, (k + 1) * per_bucket)),
                      sizes=(sz,) * per_bucket, nbytes=per_bucket * sz * 4)
               for k in range(n_leaves // per_bucket)]
    bp = BucketPlan(buckets=buckets, batch_axes=("data",), replicas=1,
                    n_params=n_leaves, wire_bytes=n_leaves * sz * 4,
                    bucket_bytes=per_bucket * sz * 4)
    opt = adamw(1e-2, weight_decay=0.1, clip_norm=1.0)
    _, tdef = jax.tree_util.tree_flatten(params)

    def unflatten(bufs):
        g = []
        for k, b in enumerate(bp.buckets):
            off = 0
            for _, s in zip(b.idx, b.sizes):
                g.append(bufs[k][off:off + s].reshape(shape))
                off += s
        return jax.tree_util.tree_unflatten(tdef, g)

    unflat = jax.jit(unflatten)
    apply_pp = jax.jit(opt.update)

    def pp(s, bufs):
        # two stages: the leaf grads materialise in between, as they do at
        # the exchange region's per-leaf output boundary in the real step
        return apply_pp(s, unflat(bufs))

    fu = jax.jit(lambda s, bufs: opt.update_fused(s, s.params, bufs, bp))
    state_pp = opt.init(params)
    state_fu = fuse_state(opt.init(params), bp)
    got_pp, _ = pp(state_pp, bufs)
    got_fu, _ = fu(state_fu, bufs)
    bit_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(got_pp),
                        jax.tree.leaves(unfuse_state(got_fu, bp))))
    pp_s = time_fn(pp, state_pp, bufs)
    fu_s = time_fn(fu, state_fu, bufs)
    return {"per_param_us": pp_s * 1e6, "fused_us": fu_s * 1e6,
            "speedup": pp_s / fu_s, "bit_equal": bool(bit_equal),
            "n_leaves": n_leaves, "n_buckets": len(buckets),
            "param_bytes": n_leaves * sz * 4}


def _autotune_sweep() -> dict:
    """The measured sweep on a shape small enough for interpret mode, plus
    the roofline ranking at TPU constants for a production-sized table
    (the measured argmin decides on real hardware; here it demonstrates
    tuned-never-loses: block 0 is always a candidate)."""
    from repro.kernels import autotune
    from repro.utils import roofline

    vs, e, n = 4096, 256, 128
    out = {"sweep_shape": [vs, e, n], "kernels": {}}
    for kernel in ("gather", "scatter"):
        best, us = autotune.tune(kernel, vs, e, n, jnp.float32, cache={})
        fixed_us, tuned_us = us[0], us[best]
        out["kernels"][kernel] = {
            "best_block": best, "fixed_us": fixed_us, "tuned_us": tuned_us,
            "tok_s_tuned": n / (tuned_us * 1e-6),
            "tok_s_fixed": n / (fixed_us * 1e-6),
            "sweep_us": {str(k): v for k, v in us.items()},
        }
    # production shape, priced by the roofline the sweep prunes with
    pvs, pe, pn = 262144, 1024, 4096
    cands = roofline.kernel_tile_candidates(pe, 4)
    est = {be: roofline.embed_tile_seconds(pn, pe, be or pe, 4)
           for be in cands}
    best = min(est, key=est.get)
    out["roofline"] = {"shape": [pvs, pe, pn],
                       "candidates": cands,
                       "est_us": {str(k): v * 1e6 for k, v in est.items()},
                       "best_block": best,
                       "tuned_over_fixed": est[best] / est[0]}
    return out


_SWITCH_CODE = """
import os
import tempfile
import time
from repro.configs import RunConfig, ShapeConfig, get_config, reduced
from repro.core.transform import get_runner
from repro.data import SyntheticLM

# d_model 256: wide enough for a 128-lane feature tile on the Pallas path
cfg = reduced(get_config("seamless-m4t-medium"), d_model=256, d_ff=512)
shape = ShapeConfig("bench", seq_len=32, global_batch=8, kind="train")
kw = dict(attention_impl="naive", remat="none", param_dtype="float32",
          compute_dtype="float32", wire_dtype="float32", comm_mode="mpi",
          bucket_bytes=256 * 1024)
ds = SyntheticLM(cfg.vocab_size, 32, 8, is_encdec=True,
                 frames_dim=cfg.d_model, frames_len=8)
mesh = make_mesh((8, 1), ("data", "model"))

def drive(**over):
    with use_mesh(mesh):
        run = get_runner(cfg, shape, RunConfig(**{**kw, **over}), mesh=mesh)
        losses, times = [], []
        for i in range(5):
            t0 = time.perf_counter()
            m = run.run(ds.batch(i))
            losses.append(float(m["loss"]))
            times.append(time.perf_counter() - t0)
        return run, losses, sorted(times[2:])[1]

# fused bucket-apply on vs off: same exchange, same grads, only the
# optimizer-apply layout moves — a 0.0 f32 loss divergence is the contract
run_f, loss_f, t_f = drive(fused_apply=True)
run_p, loss_p, t_p = drive(fused_apply=False)
stats = run_f.plan.bucket_plan.stats()

# autotuned vs fixed tiles on the Pallas path: pre-seed the cache with a
# 128-lane feature tile (a measured sweep on this CPU backend would just
# re-pick 0 — interpret mode taxes every extra grid step), so the tuned
# run genuinely executes tiled kernels against the fixed-block baseline
cache = tempfile.mktemp(suffix=".json")
os.environ["REPRO_AUTOTUNE_CACHE"] = cache
run0, loss_fix, _ = drive(embed_impl="pallas")
from repro.kernels.autotune import _key
vs, e = run0.rt.padded_vocab, cfg.d_model
n = run0.plan.table_capacity["embed"]
seed = {_key(k, vs, e, n, "float32"):
        {"best": 128, "us": {"0": 2.0, "128": 1.0}}
        for k in ("gather", "scatter")}
with open(cache, "w") as f:
    json.dump(seed, f)
run1, loss_tuned, _ = drive(embed_impl="pallas", kernel_autotune=True)

print("RESULT:" + json.dumps({
    "fused": {
        "on_losses": loss_f[:3], "off_losses": loss_p[:3],
        "loss_divergence": max(abs(a - b)
                               for a, b in zip(loss_f, loss_p)),
        "step_us_on": t_f * 1e6, "step_us_off": t_p * 1e6,
        "fused_flag": bool(run_f.plan.fused_apply),
        "n_overlapped_sparse": stats["n_overlapped_sparse"],
    },
    "autotune": {
        "fixed_losses": loss_fix[:3], "tuned_losses": loss_tuned[:3],
        "loss_divergence": max(abs(a - b)
                               for a, b in zip(loss_fix, loss_tuned)),
        "tiles": list(run1.plan.table_tiles.get("embed", (0, 0))),
        "table": {"vs": vs, "e": e, "n": n},
    },
}))
"""


def main():
    _ref_paths()
    res = {"fused_apply": _fused_apply_bench(),
           "autotune": _autotune_sweep()}
    res["switches"] = run_with_devices(_SWITCH_CODE, devices=8)

    fa = res["fused_apply"]
    emit("kernels/apply_fused_us", fa["fused_us"],
         f"per_param_us={fa['per_param_us']:.1f};"
         f"speedup={fa['speedup']:.2f};bit_equal={fa['bit_equal']}")
    for kernel, r in res["autotune"]["kernels"].items():
        emit(f"kernels/autotune_{kernel}_us", r["tuned_us"],
             f"fixed_us={r['fixed_us']:.1f};block={r['best_block']};"
             f"tok_s={r['tok_s_tuned']:.0f}")
    ro = res["autotune"]["roofline"]
    emit("kernels/roofline_tile_us", ro["est_us"][str(ro["best_block"])],
         f"fixed_us={ro['est_us']['0']:.1f};block={ro['best_block']};"
         f"shape={'x'.join(str(x) for x in ro['shape'])}")
    sw = res["switches"]
    emit("kernels/fused_switch_divergence", sw["fused"]["loss_divergence"],
         f"steps=3;dtype=f32;"
         f"n_overlapped_sparse={sw['fused']['n_overlapped_sparse']}")
    emit("kernels/autotune_switch_divergence",
         sw["autotune"]["loss_divergence"],
         f"steps=3;dtype=f32;tiles={sw['autotune']['tiles']}")

    # the PR contracts: fused beats the per-param apply bitwise-identically,
    # the sweep's argmin can never lose to the fixed block, and neither
    # switch moves the f32 trajectory by a single ULP
    assert fa["bit_equal"], fa
    assert fa["fused_us"] < fa["per_param_us"], fa
    for r in res["autotune"]["kernels"].values():
        assert r["tuned_us"] <= r["fixed_us"], r
    assert ro["tuned_over_fixed"] <= 1.0, ro
    assert sw["fused"]["fused_flag"] is True
    assert sw["fused"]["n_overlapped_sparse"] >= 1, sw["fused"]
    assert sw["fused"]["loss_divergence"] == 0.0, sw["fused"]
    assert sw["autotune"]["tiles"] == [128, 128], sw["autotune"]
    assert sw["autotune"]["loss_divergence"] == 0.0, sw["autotune"]
    with open(OUT, "w") as f:
        json.dump(res, f, indent=2, sort_keys=True)
    print(f"wrote {os.path.normpath(OUT)}")


if __name__ == "__main__":
    main()
