"""Measured autotune of the embedding-kernel feature tiles (block_e).

The Pallas embed_gather / embed_scatter_add kernels take a ``block_e``
feature tile: 0 keeps the fixed full-row block, a lane-multiple divisor of E
pipelines each row through VMEM in slabs. Which wins is a scheduling
question the roofline model can rank but not decide — so this module runs a
small *measured* sweep per (kernel, table shape, buffer rows, dtype,
backend), guided by utils/roofline.py:

  * candidates come from ``roofline.kernel_tile_candidates`` (lane-aligned
    divisors of E that double-buffer within VMEM, plus 0 — the fixed block
    is always in the running, so tuned can never lose to untuned),
  * ``roofline.embed_tile_seconds`` ranks them and the sweep keeps only the
    few cheapest predictions (plus 0) to measure,
  * the measured argmin is cached on disk (JSON, atomic write) keyed by
    shape/dtype/backend, so a given config pays the sweep once per machine.

``ensure_for_plan`` stamps the winners into ``Plan.table_tiles`` (read by
``Runtime.embed_ctx``); with a cold cache and measurement disabled — or no
Pallas path at all (``embed_impl != "pallas"``) — tables fall back to the
fixed full-row block (0, 0). Tile choice never changes the math, only the
schedule, so the fallback is always safe.

Cache location: ``~/.cache/repro/kernel_autotune.json``, overridable via
``REPRO_AUTOTUNE_CACHE``. Delete the file (or change it per-machine) to
invalidate; entries self-invalidate on any key change (shape, dtype,
backend). ``REPRO_AUTOTUNE_NO_MEASURE=1`` forbids new measurements (cache
hits still apply — the CI/offline mode).
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.utils import roofline

DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                             "kernel_autotune.json")
# tables can be huge (256k x 1k); per-step work is one row, independent of
# Vs, so the sweep measures against a row-capped proxy table
_VS_PROXY = 4096
_MEASURE_CANDS = 4          # 0 + the (this - 1) cheapest roofline predictions


def cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE", DEFAULT_CACHE)


def _load() -> dict:
    try:
        with open(cache_path()) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _save(cache: dict) -> None:
    path = cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _key(kernel: str, vs: int, e: int, n: int, dtype) -> str:
    return (f"{kernel}:{vs}x{e}:n{n}:{jnp.dtype(dtype).name}"
            f":{jax.default_backend()}")


def measurement_allowed() -> bool:
    return os.environ.get("REPRO_AUTOTUNE_NO_MEASURE", "0") in ("0", "")


def _time_us(fn: Callable[[], jax.Array], repeats: int = 3) -> float:
    fn().block_until_ready()              # compile + warm
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn().block_until_ready()
        ts.append((time.perf_counter() - t0) * 1e6)
    return sorted(ts)[len(ts) // 2]


def _sweep_candidates(e: int, n: int, itemsize: int) -> list[int]:
    cands = roofline.kernel_tile_candidates(e, itemsize)
    if len(cands) <= _MEASURE_CANDS:
        return cands
    tiled = sorted(
        (be for be in cands if be),
        key=lambda be: roofline.embed_tile_seconds(n, e, be, itemsize))
    return [0] + tiled[:_MEASURE_CANDS - 1]


def tune(kernel: str, vs: int, e: int, n: int, dtype,
         cache: Optional[dict] = None) -> tuple[int, dict]:
    """Measured best block_e for one kernel/shape. Returns
    (best_block, {block: median_us}); (0, {}) when the sweep cannot run
    (degenerate shape, or measurement forbidden on a cold cache).
    Mutates/persists the disk cache unless ``cache`` is passed in (the
    caller then owns persistence).
    """
    own_cache = cache is None
    cache = _load() if own_cache else cache
    key = _key(kernel, vs, e, n, dtype)
    hit = cache.get(key)
    if hit is not None:
        return int(hit["best"]), {int(k): v for k, v in hit["us"].items()}
    cands = _sweep_candidates(e, n, jnp.dtype(dtype).itemsize)
    if len(cands) <= 1 or n <= 0 or not measurement_allowed():
        return 0, {}
    # a kernel the backend refuses raises here: a tile is never picked by
    # hiding the refusal behind the fixed block
    from repro.kernels import ops
    vs_m = min(vs, _VS_PROXY)
    us = {}
    if kernel == "gather":
        ids = (jnp.arange(n, dtype=jnp.int32) * 7919) % vs_m
        table = jnp.ones((vs_m, e), dtype)
        for be in cands:
            us[be] = _time_us(
                lambda be=be: ops.embed_gather(table, ids, block_e=be))
    else:
        # the scatter's contract: sorted ids, unique among owned rows
        ids = jnp.sort((jnp.arange(n, dtype=jnp.int32) * 7919) % vs_m)
        rows = jnp.ones((n, e), jnp.dtype(dtype))
        for be in cands:
            us[be] = _time_us(
                lambda be=be: ops.embed_scatter_add(ids, rows, vs_m,
                                                    block_e=be))
    best = min(us, key=us.get)
    cache[key] = {"best": int(best),
                  "us": {str(k): float(v) for k, v in us.items()}}
    if own_cache:
        _save(cache)
    return int(best), us


def ensure_for_plan(plan, rt, specs=None) -> dict:
    """Stamp measured (gather_block, scatter_block) tiles for every sparse
    table into ``plan.table_tiles``. ``specs`` is the model's ParamSpec tree
    (for table shapes); without it — or off the Pallas path — tables keep
    the fixed blocks. Returns the stamped dict."""
    if rt.run_cfg.embed_impl != "pallas" or specs is None:
        return {}
    from repro.models.layers import ParamSpec
    from repro.utils.tree import path_name
    shapes = {}
    jax.tree_util.tree_map_with_path(
        lambda path, s: shapes.__setitem__(path_name(path), s.shape)
        if s.sparse else None,
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    cache = _load()
    before = json.dumps(cache, sort_keys=True)
    for name, shape in shapes.items():
        if len(shape) != 2:
            continue
        vs, e = int(shape[0]), int(shape[1])
        n = int(plan.table_capacity.get(name, 0)) or \
            rt.embed_capacity_for(name)
        gb, _ = tune("gather", vs, e, n, rt.param_dtype, cache=cache)
        wire = plan.table_wire.get(name, rt.wire_dtype)
        sb, _ = tune("scatter", vs, e, n, wire, cache=cache)
        plan.table_tiles[name] = (int(gb), int(sb))
    if json.dumps(cache, sort_keys=True) != before:
        _save(cache)
    return dict(plan.table_tiles)


def cache_status() -> dict:
    """Autotune cache report for tools/check_env.py."""
    path = cache_path()
    cache = _load()
    return {
        "path": path,
        "exists": os.path.exists(path),
        "entries": len(cache),
        "state": "warm" if cache else "cold",
        "backend_entries": sum(
            1 for k in cache if k.endswith(f":{jax.default_backend()}")),
    }
