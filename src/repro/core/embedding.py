"""PS-style sharded embedding — the paper's sparse path, TPU-native.

The embedding table is row-sharded over the ``model`` mesh axis: each shard
is a "parameter server" for its vocab rows (DESIGN.md §2). One custom_vjp
wraps the whole lookup; its forward and backward are each *non-differentiated*
shard_maps, so every byte on the wire is written explicitly (no autodiff
transpose of collectives — shard_map transposition of replicated operands is
subtle, and the paper's contribution is exactly this exchange schedule):

  local aggregation (C2): each replica dedupes its local ids (sort/unique)
      before any wire traffic; backward segment-sums cotangent rows into the
      same deduped buffer.
  pull (forward): fetch owned rows shard-locally, psum the deduped row
      buffer over ``model`` → per-replica wire bytes ≈ 2αb (Table 3, PS).
  push (backward): either
      ``ps``        owner-local scatter-add into the dense shard + psum over
                    ``data``/``pod`` (2·b/M per chip), or
      ``ps_gather`` all-gather the sparse (ids, rows) buffers over the
                    replica axes + owner-local scatter-add (D·αb),
      picked per workload by core/cost_model.py.
  mpi_gatherv: the paper's MPI baseline — table replicated; push =
      all-gather of sparse buffers over every replica (2(N-1)αb).

Static-shape adaptation: the dedupe buffer has ``capacity`` rows per replica
(DESIGN.md "Static shapes caveat"); ``exact`` capacity == local token count
never drops; overflow is counted in the metrics.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass, replace as _dc_replace
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import Mesh, P, shard_map
from repro.utils.hlo import EXCHANGE


@dataclass(frozen=True)
class EmbedCtx:
    """Static context for the sharded lookup (hashable for custom_vjp)."""
    mesh: Optional[Mesh]
    method: str                 # ps | ps_gather | mpi_gatherv | dense
    batch_axes: tuple           # mesh axes the batch is sharded over
    model_axis: str             # mesh axis of the row shards
    vocab_padded: int
    wire_dtype: Any             # dtype on the wire (OPSW)
    local_agg: bool             # C2: dedupe before exchange
    exact: bool = True          # exact capacity: size buffer per call-site
    manual: bool = False        # already inside a manual (shard_map) region:
                                # run the per-device bodies directly — the
                                # batch axes are live named axes (the
                                # bucketed-exchange path, core/buckets.py)
    impl: str = "jnp"           # gather/scatter impl: jnp | pallas kernels
    defer_push: bool = False    # overlap=False bucketed baseline: the VJP
                                # returns the locally-densified gradient and
                                # core/buckets.py reruns the gatherv push
                                # post-backward (deferred_push)
    gather_block: int = 0       # Pallas embed_gather lane tile (autotuned;
                                # 0 = the fixed full-row block)
    scatter_block: int = 0      # Pallas embed_scatter_add lane tile
    stale: bool = False         # bounded-staleness push mode: the exchange
                                # still runs every step (replica
                                # consistency), but the train step applies
                                # the *previous* step's exchanged gradient
                                # through the staleness buffer
                                # (core/transform.py); marker only here —
                                # surfaced as the {name}_stale_mode metric
    census: bool = True         # cross-replica observed-census reduction:
                                # off on the serve path (decode-kind
                                # Runtime), where nothing consumes the
                                # profile and the scalar psum would ride
                                # every decode step's critical path

    @property
    def model_shards(self) -> int:
        if self.mesh is None or not self.model_axis or \
                self.method in ("dense", "allreduce", "mpi_gatherv"):
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def replicas(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.batch_axes:
            n *= self.mesh.shape[a]
        return n


def _dedupe(ids_flat: jax.Array, capacity: int, vocab_padded: int,
            local_agg: bool
            ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """dedupe + observed census: also returns the true unique count
    (pre-capacity) — the in-graph sparsity measurement the runtime profiler
    consumes (core/sparsity.py::SparsityProfile).

    One argsort produces everything: the sorted order gives first-occurrence
    flags, their cumsum is each id's unique rank ("slot"), and scattering
    first occurrences by slot rebuilds the ascending unique buffer —
    byte-compatible with ``jnp.unique(size=capacity, fill_value=...)`` +
    a separate census sort, at half the sorts.
    """
    t = ids_flat.shape[0]
    if not local_agg:
        # no dedupe: the activated row-buffer is the raw token count. The
        # census reports the buffer actually exchanged — and the LA-off
        # ablation path stays sort-free.
        return (ids_flat.astype(jnp.int32),
                jnp.arange(t, dtype=jnp.int32),
                jnp.zeros((), jnp.int32),
                jnp.asarray(t, jnp.int32))
    capacity = min(capacity, t)
    order = jnp.argsort(ids_flat)                       # the one sort
    sorted_ids = ids_flat[order].astype(jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             sorted_ids[1:] != sorted_ids[:-1]])
    n_unique = jnp.sum(first).astype(jnp.int32)
    slot = (jnp.cumsum(first) - 1).astype(jnp.int32)    # unique rank, sorted
    dropped = jnp.maximum(n_unique - capacity, 0)
    # ascending unique ids; slots past capacity overflow into a discard row
    uids = jnp.full((capacity + 1,), vocab_padded, jnp.int32)
    uids = uids.at[jnp.where(first & (slot < capacity), slot, capacity)
                   ].set(sorted_ids)[:capacity]
    # inverse: original position -> slot (capacity == overflowed sentinel)
    inv = jnp.zeros((t,), jnp.int32).at[order].set(
        jnp.minimum(slot, capacity))
    return uids, inv, dropped, n_unique


def dedupe(ids_flat: jax.Array, capacity: int, vocab_padded: int,
           local_agg: bool) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(unique_ids[capacity], inverse[T], n_dropped). Sentinel = vocab_padded.

    inverse entries that overflowed capacity point one-past-end (= capacity),
    which readers treat as a zero row.
    """
    uids, inv, dropped, _ = _dedupe(ids_flat, capacity, vocab_padded,
                                    local_agg)
    return uids, inv, dropped


# ---------------------------------------------------------------------------
# per-device bodies (never auto-differentiated)
# ---------------------------------------------------------------------------

def _gather_rows(table_shard, local_ids, ctx: EmbedCtx):
    """Owned-row pull: rows for local-space ids in [0, Vs), zeros elsewhere.

    The per-shard half of the PS pull — either the Pallas embed_gather
    kernel (ids in SMEM drive the table DMA; interpret mode on the CPU) or its
    jnp oracle (kernels/ref.py — one source of truth for the take+mask
    semantics), per ``RunConfig.embed_impl``.
    """
    if ctx.impl == "pallas":
        from repro.kernels import ops
        return ops.embed_gather(table_shard, local_ids,
                                block_e=ctx.gather_block)
    from repro.kernels import ref
    return ref.embed_gather_ref(table_shard, local_ids, 0)


def _scatter_rows(local_ids, rows, vs: int, ctx: EmbedCtx):
    """Owner-local push: scatter deduped cotangent rows into the (Vs, E)
    f32 gradient shard, dropping unowned ids.

    The Pallas embed_scatter_add kernel requires unique ids (the dedupe
    buffer is sorted-unique), so it only serves the local_agg path; gathered
    cross-replica buffers (ps_gather / mpi_gatherv) take the jnp oracle
    (kernels/ref.py), whose scatter-add accumulates duplicates.
    """
    if ctx.impl == "pallas" and ctx.local_agg:
        from repro.kernels import ops
        return ops.embed_scatter_add(local_ids, rows, vs,
                                     block_e=ctx.scatter_block)
    from repro.kernels import ref
    return ref.embed_scatter_add_ref(local_ids, rows, vs)


def _fwd_local(table_shard, ids_loc, ctx: EmbedCtx, capacity: int):
    """-> out (B_loc,S,E), uids (1,cap), inv (B_loc,S), dropped, uniq."""
    b_loc, s = ids_loc.shape
    flat = ids_loc.reshape(-1).astype(jnp.int32)
    uids, inv, dropped, n_unique = _dedupe(flat, capacity, ctx.vocab_padded,
                                           ctx.local_agg)
    # observed census: mean unique ids per replica-step (scalar; cheap).
    # Inside shard_map the count varies over the batch axes — average them
    # (a scalar psum, OPAU-style); over the model axis ids are replicated.
    # In a manual (bucketed) region the average instead rides the fused
    # scalar-metrics psum in core/buckets.py — no collective here.
    uniq = n_unique.astype(jnp.float32)
    in_shard_map = ctx.mesh is not None and not ctx.manual and \
        ctx.method not in ("dense", "allreduce")
    if in_shard_map and ctx.batch_axes:
        if ctx.census:
            with jax.named_scope(EXCHANGE):
                uniq = jax.lax.psum(uniq, ctx.batch_axes) / ctx.replicas
        else:
            # census off (serve path): drop the measurement rather than
            # declare a device-varying scalar replicated (out_specs P())
            uniq = jnp.zeros_like(uniq)
    vs = table_shard.shape[0]
    if ctx.model_shards > 1:
        m = jax.lax.axis_index(ctx.model_axis)
        rows = _gather_rows(table_shard, uids - m * vs, ctx)
        with jax.named_scope(EXCHANGE):
            rows = rows.astype(ctx.wire_dtype)
            rows = jax.lax.psum(rows, ctx.model_axis)  # pull: ~2αb over model
            rows = rows.astype(table_shard.dtype)
    else:
        rows = _gather_rows(table_shard, uids, ctx)
    rows_pad = jnp.concatenate([rows, jnp.zeros_like(rows[:1])], axis=0)
    out = jnp.take(rows_pad, inv, axis=0).reshape(b_loc, s, -1)
    return out, uids[None], inv.reshape(b_loc, s), dropped, uniq


def _bwd_local(uids_row, inv_loc, d_out_loc, vs_shard, ctx: EmbedCtx):
    """-> d_table shard (vs_shard, E). Runs the push exchange."""
    uids = uids_row[0]
    cap = uids.shape[0]
    d_flat = d_out_loc.reshape(-1, d_out_loc.shape[-1])
    # C2 local aggregation: segment-sum cotangents into the deduped buffer
    d_rows = jnp.zeros((cap + 1, d_flat.shape[-1]), jnp.float32)
    d_rows = d_rows.at[inv_loc.reshape(-1)].add(d_flat.astype(jnp.float32))
    d_rows = d_rows[:cap].astype(ctx.wire_dtype)

    if ctx.method == "mpi_gatherv":
        if ctx.defer_push:
            # overlap=False bucketed baseline: no collectives here — return
            # the locally-densified gradient; core/buckets.py re-extracts
            # the deduped rows (deferred_push) and runs the identical
            # all-gather exchange after the full backward, pinned.
            return _scatter_rows(uids, d_rows, vs_shard,
                                 _dc_replace(ctx, local_agg=False))
        # paper's MPI baseline: all-gather (ids, rows) over every replica.
        # Gathered ids duplicate across replicas -> jnp scatter-add (the
        # overwrite-style Pallas kernel needs unique ids), via local_agg=False
        with _exchange_scope(ctx):
            uids_all, rows_all = _gather_pushed(uids, d_rows, ctx)
            return _scatter_rows(uids_all, rows_all, vs_shard,
                                 _dc_replace(ctx, local_agg=False))

    m = jax.lax.axis_index(ctx.model_axis) if ctx.model_shards > 1 else 0
    if ctx.method == "ps_gather":
        # sparse all-gather over replicas, owner-local scatter (D·αb)
        with _exchange_scope(ctx):
            uids_all, rows_all = _gather_pushed(uids, d_rows, ctx)
            return _scatter_rows(uids_all - m * vs_shard, rows_all, vs_shard,
                                 _dc_replace(ctx, local_agg=False))

    # "ps": owner-local scatter-add + dense shard psum over replicas (2b/M)
    d = _scatter_rows(uids - m * vs_shard, d_rows, vs_shard, ctx)
    if ctx.batch_axes:
        with jax.named_scope(EXCHANGE):
            d = jax.lax.psum(d.astype(ctx.wire_dtype), ctx.batch_axes
                             ).astype(jnp.float32)
    return d


def _exchange_scope(ctx: EmbedCtx):
    """The ``exchange`` named scope when the push crosses replicas; none
    when it stays on one device (its scatter is then the backward's)."""
    return jax.named_scope(EXCHANGE) if ctx.batch_axes \
        else contextlib.nullcontext()


def _gather_pushed(uids, d_rows, ctx: EmbedCtx):
    """All-gather the pushed (ids, rows) over the replicas, flattened."""
    if not ctx.batch_axes:
        return uids, d_rows
    uids_all = jax.lax.all_gather(uids, ctx.batch_axes,
                                  tiled=False).reshape(-1)
    rows_all = jax.lax.all_gather(d_rows, ctx.batch_axes,
                                  tiled=False).reshape(-1, d_rows.shape[-1])
    return uids_all, rows_all


def pin_after(x, dep):
    """Return ``x`` bitwise-unchanged, with a scheduling dependence on
    ``dep``: one element of ``x`` is re-written with itself at an index
    derived from ``dep``'s first element. A dynamic self-write is exact for
    every value (NaN and -0.0 included — nothing from ``dep`` ever mixes
    into ``x``'s values) and the compiler cannot fold it away because the
    index is data-dependent, so every consumer of the result orders after
    ``dep`` is computed. Out-of-range indices are safe: dynamic slice and
    update clamp identically."""
    flat = x.reshape(-1)
    idx = jax.lax.convert_element_type(dep.reshape(-1)[0], jnp.int32)
    piece = jax.lax.dynamic_slice(flat, (idx,), (1,))
    return jax.lax.dynamic_update_slice(flat, piece, (idx,)).reshape(x.shape)


@jax.custom_vjp
def _gate(table, act):
    return table, act


def _gate_fwd(table, act):
    return (table, act), None


def _gate_bwd(_, cts):
    d_table, d_act = cts
    # d_table is the already-exchanged push result (the lookup VJP ran the
    # row-buffer collectives); pinning the activation cotangent on it makes
    # the rest of the backward depend on the push having been issued
    return d_table, pin_after(d_act, d_table)


_gate.defvjp(_gate_fwd, _gate_bwd)


def overlap_gate(table, activation):
    """Overlap-schedule gate for an in-backward sparse push (Parallax §4:
    sparse exchanges issue at gradient readiness, concurrent with the rest
    of the backward). Thread a sparse table and an activation whose
    cotangent feeds the *remaining* backward (e.g. the encoder output for a
    decoder-side table) through this identity pair: in the backward, the
    activation's cotangent gains a value-exact data dependence
    (``pin_after``) on the table's pushed gradient, so the scheduler must
    issue the push collectives before the remaining backward instead of
    parking them after it (the push result otherwise feeds only the
    optimizer, which constrains nothing). Bitwise no-op on every value in
    both directions."""
    return _gate(table, activation)


@jax.named_scope(EXCHANGE)
def deferred_push(g_local, uids, ctx: EmbedCtx, pin=None):
    """Post-backward gatherv push for a deferred table (``EmbedCtx.
    defer_push``): re-extract the deduped wire rows from the locally-
    densified gradient, all-gather (ids, rows) over the replicas, densify —
    the exact exchange ``_bwd_local`` would have run in-backward. Exact
    because the densify round-trip over unique ids is the identity (sentinel
    rows read the appended zero row, and dedupe rows carried zeros there
    anyway), and the wire cast chain replays bitwise when the table's param
    dtype holds wire values exactly (Runtime.sparse_defer_exact gates this).

    ``pin``: the overlap=False data-dependence vector — its sum rides an
    extra row of the all-gathered buffer (dropped after), so the scheduler
    cannot issue this collective before the backward has drained.
    """
    vs, e = g_local.shape
    gpad = jnp.concatenate([g_local.astype(jnp.float32),
                            jnp.zeros((1, e), jnp.float32)], axis=0)
    rows = jnp.take(gpad, uids, axis=0).astype(ctx.wire_dtype)
    cap = uids.shape[0]
    if pin is not None:
        pin_row = jnp.broadcast_to(jnp.sum(pin), (1, e))
        rows = jnp.concatenate([rows, pin_row.astype(rows.dtype)], axis=0)
    if ctx.batch_axes:
        uids_all = jax.lax.all_gather(uids, ctx.batch_axes,
                                      tiled=False).reshape(-1)
        rows_all = jax.lax.all_gather(rows, ctx.batch_axes,
                                      tiled=False).reshape(-1, rows.shape[0], e)
        rows_all = rows_all[:, :cap].reshape(-1, e)
    else:
        uids_all, rows_all = uids, rows[:cap]
    d = _scatter_rows(uids_all, rows_all, vs,
                      _dc_replace(ctx, local_agg=False))
    return d.astype(g_local.dtype)


# ---------------------------------------------------------------------------
# the differentiable global lookup (custom VJP around whole shard_maps)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lookup(table, ids, ctx: EmbedCtx, capacity: int):
    out, _, _, dropped, uniq = _lookup_fwd_impl(table, ids, ctx, capacity)
    return out, dropped, uniq


def _lookup_fwd_impl(table, ids, ctx: EmbedCtx, capacity: int):
    if ctx.mesh is None or ctx.method in ("dense", "allreduce") or ctx.manual:
        # dense/allreduce: global semantics, XLA owns the aggregation.
        # manual: core/buckets.py already mapped the batch axes — the
        # per-device body runs directly, its collectives on live named axes.
        out, uids, inv, dropped, uniq = _fwd_local(table, ids, ctx, capacity)
        return out, uids, inv, dropped, uniq
    ba = ctx.batch_axes or None
    table_spec = P(None, None) if ctx.method == "mpi_gatherv" \
        else P(ctx.model_axis, None)
    fn = shard_map(
        lambda t, i: _fwd_local(t, i, ctx, capacity),
        mesh=ctx.mesh,
        in_specs=(table_spec, P(ba, None)),
        out_specs=(P(ba, None, None), P(ba, None), P(ba, None), P(), P()),
        check_vma=False,
    )
    return fn(table, ids)


def _lookup_fwd(table, ids, ctx: EmbedCtx, capacity: int):
    out, uids, inv, dropped, uniq = _lookup_fwd_impl(table, ids, ctx,
                                                     capacity)
    return (out, dropped, uniq), (uids, inv, jnp.zeros((0,), table.dtype))


def _lookup_bwd(ctx: EmbedCtx, capacity: int, res, cts):
    d_out, _, _ = cts
    uids, inv, dtype_probe = res
    vocab_rows = ctx.vocab_padded
    vs = vocab_rows // ctx.model_shards
    if ctx.mesh is None or ctx.method in ("dense", "allreduce"):
        # global-semantics dense path: the scatter-add cotangent is the full
        # gradient; XLA inserts the dense all-reduce across replicas (no
        # named-axis collectives outside shard_map). Under a manual region
        # the same local partial gradient feeds the bucketed exchange.
        d_table = _bwd_local(uids, inv, d_out, vocab_rows,
                             _dc_replace(ctx, batch_axes=()))
    elif ctx.manual:
        # inside the bucketed-exchange manual region: the push collectives
        # (all-gathers for mpi_gatherv) run on the live named axes; the
        # resulting gradient is the replica-sum, rescaled by core/buckets.py
        d_table = _bwd_local(uids, inv, d_out, vs, ctx)
    else:
        ba = ctx.batch_axes or None
        table_spec = P(None, None) if ctx.method == "mpi_gatherv" \
            else P(ctx.model_axis, None)
        fn = shard_map(
            lambda u, i, d: _bwd_local(u, i, d, vs, ctx),
            mesh=ctx.mesh,
            in_specs=(P(ba, None), P(ba, None), P(ba, None, None)),
            out_specs=table_spec,
            check_vma=False,
        )
        d_table = fn(uids, inv, d_out)
    return (d_table.astype(dtype_probe.dtype),
            np.zeros(inv.shape, dtype=jax.dtypes.float0))


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def lookup(table: jax.Array, ids: jax.Array, *, ctx: EmbedCtx,
           capacity: int, name: str = "embed") -> tuple[jax.Array, dict]:
    """Embedding lookup through the PS exchange. ids: (B, S) global ids.

    ``name`` keys the observed-census metrics (``{name}_unique`` /
    ``{name}_dropped``) so a model with several sparse tables profiles each
    one separately — the per-parameter replan loop reads them by table.
    """
    if ctx.manual:
        local_tokens = max(ids.size, 1)   # ids are already per-replica local
    elif ctx.mesh is not None and ctx.method in ("dense", "allreduce"):
        local_tokens = ids.size        # global dedupe in global semantics
    else:
        local_tokens = max(ids.size // max(ctx.replicas, 1), 1)
    if ctx.exact:
        # exact mode never drops: buffer sized to this call's local tokens
        capacity = min(local_tokens, ctx.vocab_padded)
    else:
        capacity = min(capacity, local_tokens, ctx.vocab_padded)
    out, dropped, uniq = _lookup(table, ids, ctx, capacity)
    nrows = capacity if ctx.local_agg else local_tokens
    metrics = {f"{name}_rows": jnp.asarray(nrows, jnp.int32),
               f"{name}_dropped": jax.lax.stop_gradient(dropped),
               f"{name}_unique": jax.lax.stop_gradient(uniq)}
    if ctx.stale:
        # the jitter fallback is live for this table: its push is applied
        # one step late (bounded by RunConfig.max_staleness, asserted via
        # the staleness_violation metric in core/transform.py)
        metrics[f"{name}_stale_mode"] = jnp.ones((), jnp.float32)
    if ctx.defer_push:
        # smuggle the dedupe buffer out to the post-backward deferred push
        # (core/buckets.py pops this before the fused metrics psum). Same
        # args as the VJP's dedupe -> identical buffer (and XLA CSEs the
        # shared argsort).
        flat = ids.reshape(-1).astype(jnp.int32)
        uids, _, _, _ = _dedupe(flat, capacity, ctx.vocab_padded,
                                ctx.local_agg)
        metrics[f"{name}_uids"] = jax.lax.stop_gradient(uids)
    return out.astype(table.dtype), metrics
