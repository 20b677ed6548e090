"""Explicit sequence-parallel block collectives (§Perf iteration A).

GSPMD-auto emits all-reduce + all-gather pairs of *full* activations at the
TP block boundaries (and the CPU backend widens them to f32). This module
makes the Megatron-SP schedule explicit and wire-dtype-controlled:

  proj_in   all-gather the seq-sharded residual ONCE per block half (bf16),
            then local matmuls against every column-sharded weight;
            backward reduce-scatters d_x.
  proj_out  local matmul -> psum-scatter the partial outputs back to the
            seq-sharded residual; backward all-gathers d_out.

Per layer the wire carries exactly 4 (fwd) + 4 (bwd) + 4 (remat recompute)
seq-scattered bf16 activation units instead of ~10 full-size f32 units —
napkin: ≥3x on the dominant collective term. Weight grads ride one psum over
the replica axes at the wire dtype (OPSW), subsuming the XLA-inserted AR.

Implemented like core/embedding.py: one custom_vjp whose fwd/bwd are
non-differentiated shard_maps (exact manual transpose: AG^T = RS, RS^T = AG).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import Mesh, P, shard_map


@dataclass(frozen=True)
class SpCtx:
    mesh: Mesh
    batch_axes: tuple
    model_axis: str
    wire_dtype: Any
    n_out_sharded: tuple        # per-weight: True if out dim is model-sharded

    @property
    def m(self) -> int:
        return self.mesh.shape[self.model_axis]


def _wspec(ctx, sharded):
    return P(None, ctx.model_axis) if sharded else P(None, None)


# ---------------------------------------------------------------------------
# proj_in: AG(x over seq) once, then k local matmuls
# ---------------------------------------------------------------------------

def _in_fwd_local(x_loc, ws, ctx: SpCtx):
    xf = jax.lax.all_gather(x_loc.astype(ctx.wire_dtype), ctx.model_axis,
                            axis=1, tiled=True).astype(x_loc.dtype)
    ys = tuple(xf @ w for w in ws)
    return ys, xf


def _in_bwd_local(xf, ws, d_ys, ctx: SpCtx):
    # d_x: sum of partial products, reduce-scattered back to seq shards.
    # Outputs whose weight is NOT model-sharded are replicated: every shard
    # holds the full logical cotangent, so their d_x contribution must be
    # counted once (scaled by 1/m) across the psum_scatter.
    d_xf = None
    d_ws = []
    for w, d_y, sharded in zip(ws, d_ys, ctx.n_out_sharded):
        contrib = d_y @ w.T
        if not sharded and ctx.m > 1:
            contrib = contrib / ctx.m
        d_xf = contrib if d_xf is None else d_xf + contrib
        d_w = jnp.einsum("bsd,bsf->df", xf, d_y).astype(ctx.wire_dtype)
        if ctx.batch_axes:
            d_w = jax.lax.psum(d_w, ctx.batch_axes)   # dense grad exchange
        d_ws.append(d_w)
    d_x = jax.lax.psum_scatter(d_xf.astype(ctx.wire_dtype), ctx.model_axis,
                               scatter_dimension=1, tiled=True)
    return (d_x,) + tuple(d_ws)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _proj_in(ctx: SpCtx, x, *ws):
    return _proj_in_fwd(ctx, x, *ws)[0]


def _proj_in_fwd(ctx: SpCtx, x, *ws):
    ba = ctx.batch_axes or None
    in_specs = (P(ba, ctx.model_axis, None),) + tuple(
        _wspec(ctx, s) for s in ctx.n_out_sharded)
    out_specs = tuple(
        P(ba, None, ctx.model_axis if s else None)
        for s in ctx.n_out_sharded)
    fn = shard_map(
        lambda x_loc, *w: _in_fwd_local(x_loc, w, ctx)[0],
        mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)
    return fn(x, *ws), (x, ws)


def _proj_in_bwd(ctx: SpCtx, res, d_ys):
    x, ws = res
    ba = ctx.batch_axes or None
    in_specs = (P(ba, ctx.model_axis, None),) + tuple(
        _wspec(ctx, s) for s in ctx.n_out_sharded) + tuple(
        P(ba, None, ctx.model_axis if s else None)
        for s in ctx.n_out_sharded)
    out_specs = (P(ba, ctx.model_axis, None),) + tuple(
        _wspec(ctx, s) for s in ctx.n_out_sharded)

    def body(x_loc, *rest):
        k = len(ws)
        w_loc, d_y_loc = rest[:k], rest[k:]
        xf = jax.lax.all_gather(x_loc.astype(ctx.wire_dtype), ctx.model_axis,
                                axis=1, tiled=True).astype(x_loc.dtype)
        outs = _in_bwd_local(xf, w_loc, d_y_loc, ctx)
        return tuple(o.astype(a.dtype) for o, a in
                     zip(outs, (x_loc,) + tuple(w_loc)))

    fn = shard_map(body, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(x, *ws, *d_ys)


_proj_in.defvjp(_proj_in_fwd, _proj_in_bwd)


# ---------------------------------------------------------------------------
# proj_out: local matmul then psum-scatter to seq shards
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _proj_out(ctx: SpCtx, h, w):
    return _proj_out_fwd(ctx, h, w)[0]


def _proj_out_fwd(ctx: SpCtx, h, w):
    ba = ctx.batch_axes or None

    def body(h_loc, w_loc):
        partial_out = (h_loc @ w_loc).astype(ctx.wire_dtype)
        out = jax.lax.psum_scatter(partial_out, ctx.model_axis,
                                   scatter_dimension=1, tiled=True)
        return out.astype(h_loc.dtype)

    fn = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ba, None, ctx.model_axis), P(ctx.model_axis, None)),
        out_specs=P(ba, ctx.model_axis, None), check_vma=False)
    return fn(h, w), (h, w)


def _proj_out_bwd(ctx: SpCtx, res, d_out):
    h, w = res
    ba = ctx.batch_axes or None

    def body(h_loc, w_loc, d_loc):
        d_full = jax.lax.all_gather(d_loc.astype(ctx.wire_dtype),
                                    ctx.model_axis, axis=1,
                                    tiled=True).astype(h_loc.dtype)
        d_h = d_full @ w_loc.T
        d_w = jnp.einsum("bsf,bsd->fd", h_loc, d_full).astype(ctx.wire_dtype)
        if ctx.batch_axes:
            d_w = jax.lax.psum(d_w, ctx.batch_axes)
        return d_h, d_w.astype(w_loc.dtype)

    fn = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ba, None, ctx.model_axis), P(ctx.model_axis, None),
                  P(ba, ctx.model_axis, None)),
        out_specs=(P(ba, None, ctx.model_axis), P(ctx.model_axis, None)),
        check_vma=False)
    return fn(h, w, d_out)


_proj_out.defvjp(_proj_out_fwd, _proj_out_bwd)


# ---------------------------------------------------------------------------
# public API (global semantics)
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# local_proj: seq-local matmul + AG of the (small) output — for projections
# whose weights are replicated over the model axis (GQA KV). Trades a small
# output all-gather for the m-fold redundant full-sequence matmul.
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _local_proj(ctx: SpCtx, x, *ws):
    return _local_proj_fwd(ctx, x, *ws)[0]


def _local_proj_fwd(ctx: SpCtx, x, *ws):
    ba = ctx.batch_axes or None

    def body(x_loc, *w):
        ys = tuple(
            jax.lax.all_gather((x_loc @ wi).astype(ctx.wire_dtype),
                               ctx.model_axis, axis=1,
                               tiled=True).astype(x_loc.dtype)
            for wi in w)
        return ys

    fn = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ba, ctx.model_axis, None),) + (P(None, None),) * len(ws),
        out_specs=tuple(P(ba, None, None) for _ in ws), check_vma=False)
    return fn(x, *ws), (x, ws)


def _local_proj_bwd(ctx: SpCtx, res, d_ys):
    x, ws = res
    ba = ctx.batch_axes or None

    def body(x_loc, *rest):
        k = len(ws)
        w_loc, d_y = rest[:k], rest[k:]
        d_x = None
        d_ws = []
        for wi, d_yi in zip(w_loc, d_y):
            d_yloc = jax.lax.psum_scatter(
                d_yi.astype(ctx.wire_dtype), ctx.model_axis,
                scatter_dimension=1, tiled=True).astype(x_loc.dtype)
            contrib = d_yloc @ wi.T
            d_x = contrib if d_x is None else d_x + contrib
            d_w = jnp.einsum("bsd,bsf->df", x_loc, d_yloc)
            d_w = jax.lax.psum(d_w.astype(ctx.wire_dtype),
                               (ctx.model_axis,) + tuple(ctx.batch_axes))
            d_ws.append(d_w.astype(wi.dtype))
        return (d_x,) + tuple(d_ws)

    fn = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ba, ctx.model_axis, None),) + (P(None, None),) * len(ws)
        + tuple(P(ba, None, None) for _ in ws),
        out_specs=(P(ba, ctx.model_axis, None),) + (P(None, None),) * len(ws),
        check_vma=False)
    return fn(x, *ws, *d_ys)


_local_proj.defvjp(_local_proj_fwd, _local_proj_bwd)


def local_proj(rt, x, ws: list) -> tuple:
    """Seq-local projection + output AG (replicated weights only)."""
    ctx = SpCtx(mesh=rt.mesh, batch_axes=rt.batch_axes, model_axis="model",
                wire_dtype=rt.wire_dtype,
                n_out_sharded=tuple(False for _ in ws))
    return _local_proj(ctx, x, *ws)


def kv_local_favorable(rt, cfg) -> bool:
    """Cost model: seq-local KV (+output AG) vs KV-on-gathered-x.

    saved compute/chip ≈ 4 passes · 2·T·D·KVdim·(m-1)/m / peak
    added wire/chip    ≈ 3 units · 2·T·KVdim·wire_bytes·(m-1)/m / link_bw
    """
    from repro.utils.roofline import device_hw
    hw = device_hw()
    m = rt.mesh.shape["model"]
    d, kvdim = cfg.d_model, cfg.kv_dim
    saved = 4 * 2 * d * kvdim * (m - 1) / m / hw.peak_flops
    added = 3 * 2 * kvdim * (m - 1) / m / hw.link_bw
    # SP-TP training lives near the collective roof: wire seconds are worth
    # ~2x compute seconds unless compute clearly dominates (hypothesis log,
    # §Perf iteration A2: confirmed on mistral/command-r, refuted on phi3
    # without the penalty).
    return saved > 2.0 * added


def sp_active(rt, x) -> bool:
    rc = rt.run_cfg
    if not getattr(rc, "explicit_sp", False) or rt.mesh is None:
        return False
    if "model" not in rt.mesh.axis_names:
        return False
    if "model" in (rt.batch_axes or ()):
        return False    # dp strategy: the model axis carries batch, no TP
    m = rt.mesh.shape["model"]
    return (m > 1 and x.ndim == 3 and x.shape[1] % m == 0
            and rt.shape_cfg.kind != "decode")


def proj_in(rt, x, ws: list, out_sharded: list) -> tuple:
    """x: (B,S,D) seq-sharded residual; ws: weights (D, F_i). One AG."""
    ctx = SpCtx(mesh=rt.mesh, batch_axes=rt.batch_axes, model_axis="model",
                wire_dtype=rt.wire_dtype, n_out_sharded=tuple(out_sharded))
    return _proj_in(ctx, x, *ws)


def proj_out(rt, h, w) -> jax.Array:
    """h: (B,S,F) col-sharded; w: (F, D) row-sharded. Matmul + RS."""
    ctx = SpCtx(mesh=rt.mesh, batch_axes=rt.batch_axes, model_axis="model",
                wire_dtype=rt.wire_dtype, n_out_sharded=(True,))
    return _proj_out(ctx, h, w)
