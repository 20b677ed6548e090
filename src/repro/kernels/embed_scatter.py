"""PS server-side push as a Pallas TPU kernel (backward of the sparse pull).

Scatters the deduped cotangent rows a replica produced into this shard's
slice of the gradient table. The local-space row ids ride in scalar-prefetch
memory (SMEM) and drive the *output* BlockSpec's index_map — grid step ``i``
writes cotangent row ``i`` straight onto table row ``ids[i]`` inside the
aligned block of rows that holds it; no (Vs, E) one-hot matmul, no
full-table scatter lowering.

Contract (matches ``_bwd_local``'s owner-local scatter):
  * ``ids`` are local-space (already offset by the shard's row base) and come
    from the dedupe buffer: sorted ascending and unique among owned rows, so
    every owned table row is written exactly once (a scatter-add over unique
    indices degenerates to a scatter-write — the adds across duplicate ids
    already happened in the segment-sum that built ``rows``).
  * sorted ids visit each output block in one run of consecutive steps: the
    block is zeroed when its run starts, stays resident while the run
    writes its rows, and is written back when the run ends. Unowned ids
    (other shards' rows, negative after offsetting, or the capacity
    sentinel) sort to either end, join the run of the first or last block,
    and write nothing.
  * the output aliases a zeros buffer so blocks no id touches read as zero
    gradient; accumulation is in f32 regardless of the wire dtype of
    ``rows``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat.pallas import CompilerParams
from repro.kernels.embed_gather import load_row, store_row, sublanes

_OUT_ROWS = sublanes(jnp.float32)


def _out_block(ids_ref, i, vs: int):
    return jnp.clip(ids_ref[i], 0, vs - 1) // _OUT_ROWS


def _scatter_kernel(ids_ref, rows_ref, zeros_ref, out_ref, *, vs: int,
                    in_rows: int):
    del zeros_ref  # the aliased output storage; never read in VMEM
    i = pl.program_id(1)
    lid = ids_ref[i]
    prev = jnp.maximum(i - 1, 0)
    run_start = jnp.logical_or(
        i == 0, _out_block(ids_ref, i, vs) != _out_block(ids_ref, prev, vs))

    @pl.when(run_start)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.logical_and(lid >= 0, lid < vs))
    def _():
        row = load_row(rows_ref, i % in_rows, in_rows)
        store_row(out_ref, lid % _OUT_ROWS, _OUT_ROWS,
                  row.astype(out_ref.dtype))


def embed_scatter_add(ids: jax.Array, rows: jax.Array, vs: int,
                      *, block_e: int = 0, interpret: bool = False) -> jax.Array:
    """ids: (N,) sorted local-space ids; rows: (N, E) -> (Vs, E) f32 grads.

    ``block_e`` tiles the feature dim exactly as in embed_gather: grid
    (E // block_e, N), each step routes one (1, block_e) slab onto its
    table row. 0 / non-divisor = full row.
    """
    n, e = rows.shape
    be = block_e if block_e and block_e < e and e % block_e == 0 else e
    in_rows = sublanes(rows.dtype)

    kernel = functools.partial(_scatter_kernel, vs=vs, in_rows=in_rows)
    zeros = jnp.zeros((vs, e), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e // be, n),
            in_specs=[pl.BlockSpec((in_rows, be),
                                   lambda j, i, ids_ref: (i // in_rows, j)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (_OUT_ROWS, be),
                lambda j, i, ids_ref: (_out_block(ids_ref, i, vs), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((vs, e), jnp.float32),
        # the zeros buffer IS the output storage: untouched blocks stay zero
        input_output_aliases={2: 0},
        # a block's run spans consecutive steps: keep them in order
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(ids.astype(jnp.int32), rows, zeros)
