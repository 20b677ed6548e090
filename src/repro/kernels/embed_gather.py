"""PS server-side pull as a Pallas TPU kernel (the sparse hot-spot).

Gathers the deduped rows a replica requested from this shard's slice of the
embedding table, zeroing rows owned by other shards. The row ids ride in
scalar-prefetch memory (SMEM) and drive the table BlockSpec's index_map —
the canonical TPU embedding-gather schedule: one tile-aligned DMA from HBM
per grid step, no host gather, no full-table traffic.

Mosaic only moves blocks whose last two dims are whole (sublane × lane)
tiles, so a step cannot DMA a single row: it DMAs the aligned group of
``sublanes(dtype)`` rows that holds the wanted row and picks it out in VMEM
(``load_row``). Output rows fill an aligned block the same way
(``store_row``): consecutive grid steps share an output block, which stays
resident and is written back once it is full. The table keeps its own
layout — no relayout copy of the (Vs, E) array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat.pallas import CompilerParams


def sublanes(dtype) -> int:
    """Rows in one native TPU tile of ``dtype`` (8 for 32-bit, 16 for 16-bit)."""
    return 32 // jnp.dtype(dtype).itemsize


def load_row(ref, k, rows: int):
    """Row ``k`` (traced, in [0, rows)) of a (rows, be) VMEM block as
    (1, be). Mosaic refuses a load at a sublane offset it cannot prove
    tile-aligned, so the row is picked by a select chain over static
    slices — exact for every value."""
    row = ref[0:1, :]
    for q in range(1, rows):
        row = jnp.where(k == q, ref[q:q + 1, :], row)
    return row


def store_row(ref, k, rows: int, val) -> None:
    """Write (1, be) ``val`` to row ``k`` (traced) of a (rows, be) block,
    through static-offset stores for the same reason as ``load_row``."""
    for q in range(rows):
        @pl.when(k == q)
        def _():
            ref[q:q + 1, :] = val


def _gather_kernel(ids_ref, table_ref, out_ref, *, row_offset: int,
                   vs: int, rows: int):
    i = pl.program_id(1)
    local = ids_ref[i] - row_offset
    owned = jnp.logical_and(local >= 0, local < vs)
    row = load_row(table_ref, jnp.clip(local, 0, vs - 1) % rows, rows)
    store_row(out_ref, i % rows, rows,
              jnp.where(owned, row, 0).astype(out_ref.dtype))


def embed_gather(table_shard: jax.Array, ids: jax.Array, row_offset: int,
                 *, block_e: int = 0, interpret: bool = False) -> jax.Array:
    """table_shard: (Vs, E); ids: (N,) global ids -> (N, E) owned rows.

    ``block_e`` tiles the feature dim: the grid becomes (E // block_e, N)
    and each step DMAs a (sublanes, block_e) slab, so wide rows pipeline
    through VMEM in slabs. 0 (or a non-divisor) keeps the full-row block.
    Lane-dim rules apply: block_e must be a multiple of 128 to tile cleanly
    (kernels/autotune.py only proposes such).
    """
    vs, e = table_shard.shape
    n = ids.shape[0]
    be = block_e if block_e and block_e < e and e % block_e == 0 else e
    rows = sublanes(table_shard.dtype)

    def table_index(j, i, ids_ref):
        local = jnp.clip(ids_ref[i] - row_offset, 0, vs - 1)
        return (local // rows, j)

    kernel = functools.partial(_gather_kernel, row_offset=row_offset,
                               vs=vs, rows=rows)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e // be, n),
            in_specs=[pl.BlockSpec((rows, be), table_index)],
            out_specs=pl.BlockSpec((rows, be),
                                   lambda j, i, ids_ref: (i // rows, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, e), table_shard.dtype),
        # an output block fills over consecutive steps: keep them in order
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(ids.astype(jnp.int32), table_shard)
