"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch phi3-medium-14b \
      --steps 100 --seq 512 --batch 8 [--devices 8 --mesh 2x4] \
      [--ckpt-dir /tmp/ckpt] [--comm-mode hybrid]

``main(argv)`` parses its own arguments, so the launcher can also be driven
in-process (``chip_smoke.py`` does). ``--devices N`` runs on N fake CPU
devices; it has to be given before the process has touched a JAX backend.
"""
import argparse
import logging
import time

import jax

from repro.configs import RunConfig, ShapeConfig, get_config, reduced
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.runtime.trainer import Trainer, TrainerConfig


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-medium-14b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config of the arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--devices", type=int, default=0,
                    help="run on this many fake CPU devices (no chip)")
    ap.add_argument("--mesh", default="", help="e.g. 2x4 => data=2,model=4")
    ap.add_argument("--comm-mode", default="hybrid")
    ap.add_argument("--no-local-agg", action="store_true")
    ap.add_argument("--no-opau", action="store_true")
    ap.add_argument("--no-opsw", action="store_true")
    ap.add_argument("--capacity-mode", default="exact",
                    choices=("exact", "capped"))
    ap.add_argument("--capacity-factor", type=float, default=1.0)
    ap.add_argument("--bucket-bytes", type=int, default=4 * 1024 * 1024,
                    help="fused dense-gradient bucket size; 0 = per-tensor")
    ap.add_argument("--embed-impl", default="jnp",
                    choices=("jnp", "pallas"),
                    help="embedding gather/scatter kernels (pallas = TPU "
                    "Pallas, interpret mode on the CPU)")
    ap.add_argument("--zipf-a", type=float, default=1.3,
                    help="skew of the synthetic token distribution")
    ap.add_argument("--plan-zipf", action="store_true",
                    help="let the planner assume the declared --zipf-a skew "
                         "(default: conservative uniform-draw bound)")
    ap.add_argument("--table-zipf", default="",
                    help="per-table declared skew for the planner, e.g. "
                         "'embed=1.3,enc_embed=1.05' (overrides --plan-zipf "
                         "for the named tables)")
    ap.add_argument("--capacity-growth", type=float, default=1.5,
                    help="capacity headroom multiplier applied when a "
                         "table's overflow EMA triggers a growth replan")
    ap.add_argument("--overflow-tolerance", type=float, default=0.5,
                    help="dropped-rows EMA (per table, per step) above "
                         "which the replan loop grows that table's capacity")
    ap.add_argument("--wire-auto", action="store_true",
                    help="profiled per-parameter wire-dtype selection: "
                         "outlier-prone gradient buckets keep f32 on the "
                         "wire, the rest ride the wire dtype")
    ap.add_argument("--wire-outlier-ratio", type=float, default=64.0,
                    help="per-bucket |g|inf/rms ratio above which --wire-"
                         "auto pins the bucket's parameters to f32")
    ap.add_argument("--hw-profile", default=None,
                    help="fitted hardware profile JSON (tools/"
                         "profile_collectives.py fit): measured intra/inter "
                         "α+β constants for the planner's argmin and the "
                         "two-level schedule choice")
    ap.add_argument("--no-fused-apply", action="store_true",
                    help="keep the per-param optimizer apply even when the "
                         "plan is eligible for the bucket-native fused "
                         "update (the fused-apply regression baseline)")
    ap.add_argument("--kernel-autotune", action="store_true",
                    help="measured block_e sweep for the Pallas embedding "
                         "kernels, cached on disk (REPRO_AUTOTUNE_CACHE); "
                         "no effect off --embed-impl pallas")
    ap.add_argument("--no-overlap", action="store_true",
                    help="pin bucket collectives after the full backward "
                         "instead of issuing each at gradient readiness "
                         "(the overlap regression baseline)")
    ap.add_argument("--replan-every", type=int, default=0,
                    help="profile->replan period in steps (0 = static plan)")
    ap.add_argument("--replan-warmup", type=int, default=2)
    ap.add_argument("--replan-drift", type=float, default=1.5,
                    help="capacity drift factor that triggers a replan")
    ap.add_argument("--profile-decay", type=float, default=0.9)
    ap.add_argument("--remesh-on-straggle", action="store_true",
                    help="elastic straggler response: on a sustained step-"
                         "time regression, checkpoint, drop the slow data "
                         "slice, re-price the plan for the smaller world, "
                         "and resume on the live state")
    ap.add_argument("--remesh-cooldown", type=int, default=50,
                    help="steps after an auto-remesh before the monitor "
                         "may escalate again (anti-thrash)")
    ap.add_argument("--min-data-parallel", type=int, default=1,
                    help="never shrink the data axis below this many slices")
    ap.add_argument("--heartbeat", action="store_true",
                    help="per-host straggler attribution: each data slice's "
                         "step-time scalar rides the fused metrics psum so "
                         "the auto-remesh evicts the *named* slow slice "
                         "instead of the last by convention")
    ap.add_argument("--no-attribution", action="store_true",
                    help="keep the by-convention last-slice eviction even "
                         "when heartbeats are on")
    ap.add_argument("--probation-steps", type=int, default=100,
                    help="probation window (steps) after readmit(): the "
                         "re-admitted slice re-straggling inside it is "
                         "re-evicted without a second full escalation")
    ap.add_argument("--probation-sustained", type=int, default=2,
                    help="outlier heartbeats on probation that re-evict")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="bound (steps) on the age of gradients the "
                         "bounded-staleness sparse fallback may apply; "
                         "0 disables the staleness machinery entirely")
    ap.add_argument("--stale-on-jitter", action="store_true",
                    help="under sustained step-time jitter below the "
                         "eviction threshold, flip sparse tables to stale "
                         "pushes (and back once the jitter drains); needs "
                         "--max-staleness > 0")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--remat", default="block")
    ap.add_argument("--attention", default="naive")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None):
    """Parse ``argv`` (default ``sys.argv[1:]``), train, and return
    ``(trainer, history)``: one dict per step with its ``loss`` and
    ``wall_s``, the host wall time from the previous step's end to this
    step's state being ready (the first step's includes compilation)."""
    args = _parse(argv)
    if args.devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.devices)
    enable_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    dev = jax.devices()[0]
    print(f"jax {jax.__version__}  platform={dev.platform}  "
          f"kind={dev.device_kind}  devices={jax.device_count()}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    table_zipf = tuple(
        (k, float(v)) for k, v in
        (kv.split("=", 1) for kv in args.table_zipf.split(",") if kv))
    run_cfg = RunConfig(
        comm_mode=args.comm_mode, local_agg=not args.no_local_agg,
        opau=not args.no_opau, opsw=not args.no_opsw,
        capacity_mode=args.capacity_mode,
        capacity_factor=args.capacity_factor,
        capacity_growth=args.capacity_growth,
        overflow_tolerance=args.overflow_tolerance,
        zipf_a=args.zipf_a if args.plan_zipf else None,
        table_zipf=table_zipf,
        wire_dtype_auto=args.wire_auto,
        wire_outlier_ratio=args.wire_outlier_ratio,
        hw_profile=args.hw_profile, overlap=not args.no_overlap,
        fused_apply=not args.no_fused_apply,
        kernel_autotune=args.kernel_autotune,
        bucket_bytes=args.bucket_bytes, embed_impl=args.embed_impl,
        learning_rate=args.lr, remat=args.remat,
        attention_impl=args.attention, seed=args.seed,
        heartbeat=args.heartbeat, max_staleness=args.max_staleness)
    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model") if len(dims) == 2 else \
            ("pod", "data", "model")
        mesh = make_mesh(dims, axes)
    ds = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=args.seed,
                     zipf_a=args.zipf_a, is_encdec=cfg.is_encdec,
                     frames_dim=cfg.d_model if cfg.family == "audio" else 0,
                     frames_len=max(args.seq // 4, 1))
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         log_every=args.log_every,
                         replan_every=args.replan_every,
                         replan_warmup=args.replan_warmup,
                         replan_drift=args.replan_drift,
                         profile_decay=args.profile_decay,
                         remesh_on_straggle=args.remesh_on_straggle,
                         remesh_cooldown=args.remesh_cooldown,
                         min_data_parallel=args.min_data_parallel,
                         attribution=not args.no_attribution,
                         probation_steps=args.probation_steps,
                         probation_sustained=args.probation_sustained,
                         stale_on_jitter=args.stale_on_jitter)
    trainer = Trainer(cfg, shape, run_cfg, tcfg, ds, mesh=mesh)
    trainer.maybe_restore()

    history = []
    first_compiles = []
    t0 = last = time.perf_counter()

    def on_metrics(step, m):
        nonlocal last
        jax.block_until_ready(trainer.state)
        now = time.perf_counter()
        history.append({"step": step, "loss": m.get("loss", float("nan")),
                        "wall_s": now - last})
        last = now
        if not first_compiles:
            first_compiles.append(m["compiles"])
        if step % args.log_every == 0:
            extra = ""
            if "observed_alpha" in m:
                extra = (f"  alpha {m['observed_alpha']:.4f}"
                         f"  replans {int(m.get('replans', 0))}")
            over = {t: v for t, v in m.get("overflow", {}).items() if v > 0}
            if over:
                extra += "  dropped " + ",".join(
                    f"{t}:{v:.1f}" for t, v in sorted(over.items()))
            if m.get("remeshes"):
                extra += f"  remeshes {int(m['remeshes'])}"
            if m.get("regrows"):
                extra += f"  regrows {int(m['regrows'])}"
            if "stale_mode" in m:
                extra += f"  stale {'on' if m['stale_mode'] else 'off'}"
            if m.get("ckpt_retries"):
                extra += f"  ckpt-retries {int(m['ckpt_retries'])}"
            if m["compiles"] > first_compiles[0]:
                extra += f"  recompiles {m['compiles'] - first_compiles[0]}"
            if m.get("n_overlapped_sparse"):
                extra += f"  ovl-sparse {int(m['n_overlapped_sparse'])}"
            if "ckpt_error" in m:
                extra += f"  CKPT-ERROR {m['ckpt_error']}"
            print(f"step {step:5d}  loss {m.get('loss', float('nan')):.4f}  "
                  f"{m.get('tokens_per_s', 0):.0f} tok/s  "
                  f"gnorm {m.get('grad_norm', float('nan')):.3f}{extra}")

    trainer.run(on_metrics=on_metrics)
    dt = time.perf_counter() - t0
    print(f"done: {len(history)} steps in {dt:.1f}s "
          f"({len(history) * shape.tokens / dt:.0f} tok/s avg)")
    return trainer, history


if __name__ == "__main__":
    main()
