"""Serving launcher: batched requests against a small model.

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-medium-14b \
      --reduced --requests 16 --max-new 8

``--engine paged`` (default) runs the rebuilt engine: one jitted prefill
per admission, slot-paged decode, device-side sampling. ``--engine toy``
runs the teacher-forced baseline loop (also the fallback for recurrent
families, whose carry cannot be bucket-prefilled under padding).
``main(argv)`` parses its own arguments; ``--devices N`` runs on N fake CPU
devices and has to be given before the process has touched a JAX backend.
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import RunConfig, get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.runtime.server import Request, Server, ServerConfig, ToyServer


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-medium-14b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--engine", choices=("paged", "toy"), default="paged")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--sample", action="store_true",
                    help="temperature sampling instead of greedy argmax")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--devices", type=int, default=0,
                    help="run on this many fake CPU devices (no chip)")
    ap.add_argument("--mesh", default="")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse(argv)
    if args.devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.devices)
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"jax {jax.__version__}  platform={dev.platform}  "
          f"kind={dev.device_kind}  devices={jax.device_count()}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model") if len(dims) == 2 else \
            ("pod", "data", "model")
        mesh = make_mesh(dims, axes)
    rng = np.random.default_rng(args.seed)
    cls = Server if args.engine == "paged" else ToyServer
    server = cls(cfg, RunConfig(attention_impl="naive"),
                 ServerConfig(max_batch=args.max_batch,
                              max_seq=args.max_seq,
                              greedy=not args.sample,
                              temperature=args.temperature), mesh=mesh)
    for i in range(args.requests):
        plen = int(rng.integers(2, 9))
        server.submit(Request(
            uid=i, prompt=rng.integers(0, cfg.vocab_size, plen,
                                       dtype=np.int32),
            max_new_tokens=args.max_new))
    t0 = time.time()
    done = server.run_until_drained()
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    ttft = sorted(r.ttft for r in done)
    print(f"[{args.engine}] served {len(done)} requests, {toks} tokens in "
          f"{dt:.1f}s ({toks/dt:.1f} tok/s, TTFT p50 "
          f"{ttft[len(ttft)//2]*1e3:.1f} ms)")
    if args.engine == "paged":
        print(f"  {server.stats['prefill_calls']} prefill dispatches / "
              f"{server.stats['prefill_traces']} traces over buckets "
              f"{sorted(server.stats['buckets'])}, "
              f"{server.stats['decode_steps']} decode steps, "
              f"{server.stats['cross_slot_mismatches']} cross-slot "
              f"mismatches")
        server.close()
    for r in done[:4]:
        print(f"  req {r.uid}: prompt {r.prompt.tolist()} -> {r.out_tokens}")
    assert len(done) == args.requests


if __name__ == "__main__":
    main()
