"""Benchmark helpers: subprocess lowering (512 fake devices) + wall-clock
micro-timing (single device — the bench process itself never forces devices).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")


def run_with_devices(code: str, devices: int = 512, timeout: int = 900) -> dict:
    """Run code in a subprocess with N fake devices; expects RESULT: json."""
    prelude = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import json
        import jax
        from repro.compat import (AxisType, NamedSharding, PartitionSpec,
                                  make_mesh, use_mesh)
        P = PartitionSpec
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # fake devices are CPU devices: the child never reaches for a chip the
    # parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise RuntimeError(f"no RESULT line:\n{proc.stdout[-2000:]}")


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall seconds per call (after jit warmup)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
