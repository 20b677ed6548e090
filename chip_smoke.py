"""Bring-up check: parallax-lm training on a TPU through the training launcher.

    python chip_smoke.py             # one chip: the jnp and the Pallas phase
    python chip_smoke.py --chips 4   # four chips: 4x1 data mesh vs one device

Drives ``repro.launch.train.main`` in this process (a chip belongs to one
process, so nothing here starts another) with ``parallax-lm`` at its
published widths (800k vocab, 512 embedding, 2048-unit LSTM), seq 20,
global batch 32, the default hybrid plan, random weights from seed 0.

One chip:
  train   embed_impl=jnp; every loss finite and the last below the first.
  pallas  the same run with embed_impl=pallas after the first run's state
          is freed; the compiled step must hold a ``tpu_custom_call``, its
          first loss must equal the train phase's, and the rest match it
          within ``PALLAS_LOSS_TOL``.
Four chips (``--chips 4``): the same training on a 4x1 data x model mesh
against ``mesh=None`` on one device, both at global batch 32 (the
distributed == single-device claim); each step's loss must agree within
``MESH_LOSS_TOL``.

The lines before the last are bring-up observations (compile seconds, step
wall times after ``block_until_ready``, peak HBM), not benchmark numbers.
The last line is one JSON object, printed only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or without the rest of the repository, the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import math
import os
import sys

STEPS = 6
ARCH_ARGS = ["--arch", "parallax-lm", "--seq", "20", "--batch", "32",
             "--steps", str(STEPS), "--log-every", "1", "--seed", "0"]
# Both embedding paths copy rows and scatter unique rows, so the first
# step's loss (the forward pass) must be bit-equal. The pushed gradient
# rows are rounded to the bf16 wire dtype; around the kernel that rounding
# happens, while in the jnp path XLA may keep the f32 sums (excess
# precision). Rows hit by several tokens then differ by up to 2^-9
# relatively, which Adam carries into later losses: 8e-4 by step 5 on a
# v5e, against a bound of 5e-3.
PALLAS_LOSS_TOL = 5e-3
# bf16 parameters and a bf16 gradient wire: the 4-way partial gradient sums
# round differently from the single-device sum, and Adam's normalised step
# carries that into the next losses. 0.05 is under one bf16 ulp of a loss
# near ln(800000) = 13.6 (ulp 0.0625).
MESH_LOSS_TOL = 0.05

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class PhaseFailed(Exception):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def _run(train, name: str, argv: list, compile_s: collections.Counter,
         hlo: bool = False) -> dict:
    """One launcher run; returns losses, step walls, compile seconds and
    (with ``hlo``) the compiled step's text and methods. The trainer is
    dropped before returning, so its state can be freed."""
    import jax
    from repro import compat
    print(f"[{name}] launcher argv: {' '.join(argv)}", flush=True)
    compile_s.clear()
    trainer, history = train.main(argv)
    out = {"losses": [h["loss"] for h in history],
           "walls": [h["wall_s"] for h in history],
           "compile": dict(compile_s),
           "methods": dict(trainer.plan.table_methods)}
    if hlo:
        batch = trainer.dataset.batch(0)
        if trainer.mesh is not None:
            with compat.use_mesh(trainer.mesh):
                lowered = trainer.train_step.lower(trainer.state, batch)
        else:
            lowered = trainer.train_step.lower(trainer.state, batch)
        out["hlo"] = lowered.compile().as_text()
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes"] = stats.get("peak_bytes_in_use")
    del trainer, history
    gc.collect()
    c = out["compile"]
    print(f"[{name}] compile_s total {sum(c.values()):.2f} "
          f"(trace {c.get(COMPILE_EVENTS[0], 0.0):.2f}, "
          f"lower {c.get(COMPILE_EVENTS[1], 0.0):.2f}, "
          f"backend {c.get(COMPILE_EVENTS[2], 0.0):.2f})", flush=True)
    for i, (loss, wall) in enumerate(zip(out["losses"], out["walls"]), 1):
        print(f"[{name}] step {i} loss {loss:.6f} wall_s {wall:.4f}"
              + ("  (includes compile)" if i == 1 else ""), flush=True)
    print(f"[{name}] table methods {out['methods']}", flush=True)
    print(f"[{name}] peak_bytes_in_use {out['peak_bytes']}", flush=True)
    return out


def _losses_fall(name: str, losses: list) -> None:
    _check(all(math.isfinite(x) for x in losses),
           f"{name}: non-finite loss {losses}")
    _check(losses[-1] < losses[0],
           f"{name}: loss did not fall ({losses[0]} -> {losses[-1]})")


def one_chip(train, compile_s) -> None:
    ref = _run(train, "train", ARCH_ARGS + ["--embed-impl", "jnp"],
               compile_s)
    _losses_fall("train", ref["losses"])
    pal = _run(train, "pallas", ARCH_ARGS + ["--embed-impl", "pallas"],
               compile_s, hlo=True)
    _losses_fall("pallas", pal["losses"])
    n_kernels = pal.pop("hlo").count("tpu_custom_call")
    print(f"[pallas] tpu_custom_call in compiled step: {n_kernels}",
          flush=True)
    _check(n_kernels > 0, "pallas: no tpu_custom_call in the compiled step")
    deltas = [abs(a - b) for a, b in zip(ref["losses"], pal["losses"])]
    print(f"[pallas] |loss - train loss| per step {deltas} "
          f"(step 1 exact, then tolerance {PALLAS_LOSS_TOL})", flush=True)
    _check(deltas[0] == 0.0,
           f"pallas: first-step loss differs from the jnp path by {deltas[0]}")
    _check(max(deltas) <= PALLAS_LOSS_TOL,
           f"pallas: losses differ from the jnp path by {max(deltas)}")


def four_chips(train, compile_s) -> None:
    from repro.utils.hlo import parse_collectives
    ref = _run(train, "single", ARCH_ARGS, compile_s)
    _losses_fall("single", ref["losses"])
    dp = _run(train, "mesh4x1", ARCH_ARGS + ["--mesh", "4x1"], compile_s,
              hlo=True)
    _losses_fall("mesh4x1", dp["losses"])
    counts = parse_collectives(dp.pop("hlo")).collective_count
    print(f"[mesh4x1] collectives in compiled step: "
          f"{int(sum(counts.values()))} {counts}", flush=True)
    deltas = [abs(a - b) for a, b in zip(ref["losses"], dp["losses"])]
    print(f"[mesh4x1] |loss - single-device loss| per step {deltas} "
          f"(bf16 tolerance {MESH_LOSS_TOL})", flush=True)
    _check(max(deltas) <= MESH_LOSS_TOL,
           f"mesh4x1: losses differ from one device by {max(deltas)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4x1 mesh comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch import train
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"jax {jax.__version__}  platform {dev.platform}  "
          f"device_kind {dev.device_kind}  device_count {len(devices)}  "
          f"compile cache {cache}", flush=True)
    compile_s: collections.Counter = collections.Counter()

    def on_duration(event, secs, *args, **kwargs):
        if event in COMPILE_EVENTS:
            compile_s[event] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        if args.chips == 4:
            four_chips(train, compile_s)
        else:
            one_chip(train, compile_s)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
