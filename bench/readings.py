"""The readings that a cell's correctness limits are set from, at the
cell's own size, on the chip. Not part of a benchmark run.

    python bench/readings.py --workload <cell> --seeds 11,12,13 \
        [--program] [--variants control,half_batch,no_exchange]

For each seed, in one process:

  program      the harness's own set-up (the program's first steps through
               ``Trainer.run``) against the plain reference: the lower
               readings;
  control      the reference put in the program's place, computed and held
               at the next precision below the configuration's parameters'
               (float8 e4m3 for bfloat16, bfloat16 for float32), against
               the float32 reference;
  half_batch   the reference in the program's place with the loss and the
               gradient taken over the first half of each batch's rows;
  no_exchange  the same over the first chip's rows alone (a data-parallel
               step without its gradient exchange).

Each line printed is one JSON object: the seed, the variant, the numbers
of lib/check.py and ``correct``, the harness's own judgement of those
numbers against the cell's limits. The last line gives, per variant, the
largest and the smallest reading of each number and how many seeds came
out correct.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from lib import check  # noqa: E402
from lib.lstm_ref import seed32  # noqa: E402
from lib.registry import Registry, load_module  # noqa: E402

LOWER = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--variants", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    reg = Registry(os.path.dirname(BENCH_DIR))
    cell = reg.cell(args.workload)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    cmod = reg.config_module(cell["config"])
    # the reference variants run on one chip; the program takes the cell's
    devs = run._devices(cell["chips"] if args.program else 1,
                        require_tpu=True)
    sys.path.insert(0, os.path.join(reg.root, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    gen = load_module(os.path.join(reg.dir, "traffic", "generator.py"),
                      "traffic")
    enc = cfg["model"].get("is_encdec", False)
    optim = cfg["run_config"]
    pdt = optim["param_dtype"]
    rows = {"half_batch": slice(0, mix["global_batch"] // 2),
            "no_exchange": slice(0, mix["global_batch"] // cell["chips"])}
    steps = cell.get("loss_steps", 0)
    summary = {}

    def report(seed, variant, numbers):
        correct = check.judge(numbers, cell["limits"])[0]
        print(json.dumps({"seed": seed, "variant": variant, **numbers,
                          "correct": correct}), flush=True)
        s = summary.setdefault(variant, {"correct": 0, "seeds": 0})
        s["correct"] += correct
        s["seeds"] += 1
        for k, v in numbers.items():
            lo, hi = s.get(k, (v, v))
            s[k] = (min(lo, v), max(hi, v))

    for seed in [int(s) for s in args.seeds.split(",")]:
        traffic = gen.Traffic(mix, cfg["model"]["vocab_size"], seed, enc)
        batches = [traffic.draw(s) for s in range(run.CHECKED_STEPS)]
        key_seed = seed32(seed)
        prog = None
        if args.program:
            trainer, _ = run.build(cell, cfg, mix, seed, cmod, traffic)
            prog = run.setup_steps(trainer, cmod, cfg, key_seed)
            trainer.train_step = None
            del trainer
            gc.collect()
        with jax.default_device(devs[0]):
            ref = cmod.reference(cfg["model"], optim, key_seed, batches)
            gc.collect()
            if prog is not None:
                report(seed, "program",
                       check.compare(prog, ref, steps))
                print(json.dumps({"seed": seed, "detail": check.detail(
                    prog, ref, ref["names"])}), flush=True)
            for v in [v for v in args.variants.split(",") if v]:
                if v == "control":
                    out = cmod.reference(cfg["model"], optim, key_seed,
                                         batches, dtype=jnp.dtype(LOWER[pdt]))
                else:
                    out = cmod.reference(cfg["model"], optim, key_seed,
                                         batches, rows=rows[v])
                gc.collect()
                report(seed, v, check.compare(out, ref, steps))
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
