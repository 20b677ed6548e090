"""Device time of a benchmark cell's training step by phase and by the
model's scopes, from a profiler trace, with the step's MoE counters.

    python tools/trace_scopes.py --workload moonlight-1chip-8k --seed 7 \
        [--steps 5] [--out scopes.json]

Builds the cell's trainer as ``bench/run.py`` does, runs 3 warm-up steps,
profiles ``--steps`` more, and joins each device op of the trace to the
compiled step's text by instruction name (``utils.hlo.split_device_time``):
``forward``, ``backward``, ``optimizer``, ``exchange``, each split further
by ``attention``, ``moe_route``, ``moe_experts`` and ``moe_shared`` where
the op lies in one. Prints one JSON object: milliseconds per step by key,
busy milliseconds per step, and per step ``moe_held_rows``,
``moe_load_max`` and ``moe_dropped`` where the model has them. Needs the
cell's chips (the profiler's device planes exist only there): without them
it exits 2.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(0, os.path.join(ROOT, "src"))

COUNTERS = ("moe_held_rows", "moe_load_max", "moe_dropped")
CONTAINERS = ("while", "call", "conditional")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import run as bench_run
    from lib import trace as tracelib
    from lib.registry import Registry, load_module

    reg = Registry(ROOT)
    cell = reg.cell(args.workload)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    cmod = reg.config_module(cell["config"])
    try:
        bench_run._devices(cell["chips"], require_tpu=True)
    except bench_run.NoChip as e:
        print(f"trace_scopes: {e}", file=sys.stderr)
        return 2

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.utils import hlo
    # a cached executable keeps the op_names of the revision that filled
    # the cache unless the metadata is part of the key
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    enable_compile_cache()
    gen = load_module(os.path.join(reg.dir, "traffic", "generator.py"),
                      "traffic")
    traffic = gen.Traffic(mix, cfg["model"]["vocab_size"], args.seed,
                          cfg["model"].get("is_encdec", False))
    trainer, _ = bench_run.build(cell, cfg, mix, args.seed, cmod, traffic)
    counters = []

    def keep(step, m):
        counters.append({k: m[k] for k in COUNTERS if k in m})

    trainer.tcfg.total_steps = 3
    trainer.run(on_metrics=keep)
    jax.block_until_ready(trainer.state)
    tdir = os.path.join(reg.dir, ".trace", "scopes")
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    trainer.tcfg.total_steps = 3 + args.steps
    trainer.run(on_metrics=keep)
    jax.block_until_ready(trainer.state)
    jax.profiler.stop_trace()
    devices, _ = tracelib.read_xplane(tdir)
    shutil.rmtree(tdir, ignore_errors=True)

    n = len(devices) * args.steps
    op_s, busy = {}, 0.0
    for evs in devices.values():
        busy += tracelib.length(tracelib.union((s, e) for s, e, _ in evs))
        for s, e, name in evs:
            # a loop or call is drawn around the ops it runs: count those
            if tracelib.op_kind(name).split()[-1] in CONTAINERS:
                continue
            key = name.partition(" = ")[0].strip().lstrip("%")
            op_s[key] = op_s.get(key, 0.0) + (e - s) / n
    text = trainer.train_step.lower(trainer.state,
                                    traffic.draw(0)).compile().as_text()
    split = hlo.split_device_time(op_s, text)
    out = {"workload": args.workload, "seed": args.seed,
           "steps": args.steps, "busy_ms_per_step": 1e3 * busy / n,
           "ms_per_step": {k: 1e3 * v for k, v in sorted(split.items())},
           "counters": [{k: float(v) for k, v in c.items()}
                        for c in counters]}
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
