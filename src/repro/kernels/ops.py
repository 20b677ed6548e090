"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels run in interpret mode (correctness path);
on any other backend they compile to Mosaic, and a backend that cannot
compile them fails rather than falling back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import embed_gather as _eg
from repro.kernels import embed_scatter as _es
from repro.kernels import wkv as _wkv


def interpret_mode() -> bool:
    """Pallas interpret mode: on the CPU backend, and only there."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("row_offset", "block_e"))
def embed_gather(table_shard, ids, row_offset: int = 0, *, block_e: int = 0):
    return _eg.embed_gather(table_shard, ids, row_offset, block_e=block_e,
                            interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("vs", "block_e"))
def embed_scatter_add(ids, rows, vs: int, *, block_e: int = 0):
    return _es.embed_scatter_add(ids, rows, vs, block_e=block_e,
                                 interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv(r, k, v, lw, bonus, state, *, chunk: int = 32):
    return _wkv.wkv(r, k, v, lw, bonus, state, chunk=chunk,
                    interpret=interpret_mode())
