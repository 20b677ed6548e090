"""GQA attention with RoPE.

Implementations:
  naive    full (S,S) score materialization — small tests only.
  chunked  the flash-attention *algorithm* in pure XLA ops; the default.
           Self-attention with one KV head per q head (``blocked_attention``)
           runs over (query block, key block) pairs, causal pairs above the
           diagonal skipped, with its own backward that recomputes each
           pair's scores from the saved log-sum-exp: forward and backward
           hold O(S·C) scores, and q/k and v head dims may differ. Other
           cases (cross, offset or grouped queries) scan online-softmax over
           KV blocks (``chunked_attention``), whose backward keeps each
           block's scores.
  pallas   kernels/flash_attention.py (TPU target, validated interpret=True).

Sharding (DESIGN.md): q heads padded to the model-axis size and sharded;
KV heads replicated (TP > n_kv); decode KV cache sequence-sharded over
``model`` with a psum'd online-softmax combine (flash-decoding).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D). positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def make_qmap(n_heads: int, n_kv: int, padded_heads: int):
    """q-head -> kv-head index map; padded q heads point at kv 0 (their
    weights are zero-initialized, so they contribute nothing). Returns None
    when the map is the identity (MHA, no padding)."""
    q_per_kv = max(n_heads // max(n_kv, 1), 1)
    idx = [min(i // q_per_kv, n_kv - 1) if i < n_heads else 0
           for i in range(padded_heads)]
    if idx == list(range(padded_heads)):
        return None
    return jnp.asarray(idx, jnp.int32)


def _expand_kv(k, qmap):
    """(B,S,KV,D) -> (B,S,H,D) via the q->kv map.

    Implemented as a one-hot einsum, not a gather: the contraction partitions
    cleanly under SPMD (replicated KV -> head-sharded expansion with zero
    communication) and its VJP is another einsum — a gather's scatter-add
    VJP forces involuntary resharding of (B,S,H,D) buffers per KV chunk.
    """
    if qmap is None:
        return k
    onehot = jax.nn.one_hot(qmap, k.shape[2], dtype=k.dtype)  # (H, KV)
    return jnp.einsum("bskd,hk->bshd", k, onehot)


def naive_attention(q, k, v, *, causal: bool = True,
                    q_offset: int = 0, qmap=None) -> jax.Array:
    """q: (B,Sq,H,D), k/v: (B,Sk,KV,D). Returns (B,Sq,H,D)."""
    kq = _expand_kv(k, qmap)
    vq = _expand_kv(v, qmap)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        kq.astype(jnp.float32))
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = jnp.arange(sk)[None, :]
        scores = jnp.where(qpos >= kpos, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vq.astype(jnp.float32))
    return out.astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool = True, chunk: int = 1024,
                      q_offset: int = 0, qmap=None) -> jax.Array:
    """Online-softmax attention, scanned over KV chunks (flash algorithm).

    Never materializes more than (B, Sq, H, chunk) of scores.
    """
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    chunk = min(chunk, sk)
    if sk % chunk != 0:  # pad kv to a chunk multiple (masked out)
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    scale = d ** -0.5

    kc = k.reshape(b, n_chunks, chunk, kv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kv, d).transpose(1, 0, 2, 3, 4)

    qpos = q_offset + jnp.arange(sq)[:, None]
    qf = q.astype(jnp.float32) * scale

    def body(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp
        kj = _expand_kv(kj, qmap).astype(jnp.float32)
        vj = _expand_kv(vj, qmap).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj)
        kpos = j * chunk + jnp.arange(chunk)[None, :]
        mask = kpos <= (sk - 1)
        if causal:
            mask = mask & (qpos >= kpos)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vj)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _block_pairs(n: int, causal: bool):
    """(query block, key block) index pairs, those wholly above the causal
    diagonal left out."""
    pairs = [(i, j) for i in range(n) for j in range(n) if j <= i or not causal]
    return (jnp.asarray([i for i, _ in pairs], jnp.int32),
            jnp.asarray([j for _, j in pairs], jnp.int32))


def _pair_scores(qb, kb, i, j, block, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = i * block + jnp.arange(block)[:, None]
        kpos = j * block + jnp.arange(block)[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    return s


def _blk(x, i, block):
    """Block ``i`` of the sequence axis (axis 2 of (B,H,S[,D]))."""
    return jax.lax.dynamic_slice_in_dim(x, i * block, block, axis=2)


def _put(x, upd, i, block):
    return jax.lax.dynamic_update_slice_in_dim(x, upd, i * block, axis=2)


def _blocked_fwd_core(q, k, v, causal, block):
    """(B,H,S,Dk) x2, (B,H,S,Dv) -> out (B,H,S,Dv) f32, lse (B,H,S)."""
    b, h, s, dk = q.shape
    scale = dk ** -0.5

    def body(carry, ij):
        m, l, acc = carry
        i, j = ij
        sc = _pair_scores(_blk(q, i, block), _blk(k, j, block), i, j, block,
                          causal, scale)
        mb, lb, ab = _blk(m, i, block), _blk(l, i, block), _blk(acc, i, block)
        m_new = jnp.maximum(mb, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(mb - m_new)
        l_new = lb * corr + jnp.sum(p, axis=-1)
        a_new = ab * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v.dtype), _blk(v, j, block),
            preferred_element_type=jnp.float32)
        return (_put(m, m_new, i, block), _put(l, l_new, i, block),
                _put(acc, a_new, i, block)), None

    carry = (jnp.full((b, h, s), NEG_INF, jnp.float32),
             jnp.zeros((b, h, s), jnp.float32),
             jnp.zeros((b, h, s, v.shape[-1]), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, carry,
                                  _block_pairs(s // block, causal))
    return acc / l[..., None], m + jnp.log(l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blocked(q, k, v, causal, block):
    out, _ = _blocked_fwd_core(q, k, v, causal, block)
    return out.astype(q.dtype)


def _blocked_fwd(q, k, v, causal, block):
    out, lse = _blocked_fwd_core(q, k, v, causal, block)
    out = out.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _blocked_bwd(causal, block, res, do):
    q, k, v, out, lse = res
    scale = q.shape[-1] ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)

    def body(carry, ij):
        dq, dk, dv = carry
        i, j = ij
        qb, kb, vb, dob = (_blk(q, i, block), _blk(k, j, block),
                           _blk(v, j, block), _blk(do, i, block))
        sc = _pair_scores(qb, kb, i, j, block, causal, scale)
        p = jnp.exp(sc - _blk(lse, i, block)[..., None])
        dp = jnp.einsum("bhqd,bhkd->bhqk", dob, vb,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - _blk(delta, i, block)[..., None]) * scale
              ).astype(q.dtype)
        dv = _put(dv, _blk(dv, j, block) + jnp.einsum(
            "bhqk,bhqd->bhkd", p.astype(do.dtype), dob,
            preferred_element_type=jnp.float32), j, block)
        dq = _put(dq, _blk(dq, i, block) + jnp.einsum(
            "bhqk,bhkd->bhqd", ds, kb, preferred_element_type=jnp.float32),
            i, block)
        dk = _put(dk, _blk(dk, j, block) + jnp.einsum(
            "bhqk,bhqd->bhkd", ds, qb, preferred_element_type=jnp.float32),
            j, block)
        return (dq, dk, dv), None

    zeros = lambda x: jnp.zeros(x.shape, jnp.float32)  # noqa: E731
    (dq, dk, dv), _ = jax.lax.scan(body, (zeros(q), zeros(k), zeros(v)),
                                   _block_pairs(q.shape[2] // block, causal))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


def blocked_attention(q, k, v, *, causal: bool = True,
                      block: int = 1024) -> jax.Array:
    """Self-attention, q/k (B,S,H,Dk), v (B,S,H,Dv) -> (B,S,H,Dv), over
    ``block``-sized query and key blocks (the whole sequence when it does
    not divide). Neither pass holds more than one pair's (B,H,C,C) scores."""
    s = q.shape[1]
    block = min(block, s)
    if s % block:
        block = s
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    return t(_blocked(t(q), t(k), t(v), causal, block))


def decode_attention(q, k_cache, v_cache, cache_len, *, qmap=None) -> jax.Array:
    """One-step attention against a (possibly sequence-sharded) KV cache.

    q: (B,1,H,D); caches: (B,S,KV,D) sharded P(batch, kv_seq, None, None).
    ``cache_len`` is a scalar (homogeneous batch — the dry-run decode cells)
    or a per-slot (B,) vector (the serving engine's slot-paged decode: each
    slot masks exactly its own valid prefix, so a freed-and-reused slot never
    attends over a previous request's stale rows).
    Written in global semantics — GSPMD partitions the softmax reduction over
    the sharded cache axis (flash-decoding's psum combine).
    """
    kq = _expand_kv(k_cache, qmap).astype(jnp.float32)
    vq = _expand_kv(v_cache, qmap).astype(jnp.float32)
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, kq)
    kpos = jnp.arange(k_cache.shape[1])[None, None, None, :]
    cl = jnp.asarray(cache_len)
    if cl.ndim == 1:
        cl = cl[:, None, None, None]
    s = jnp.where(kpos < cl, s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vq)
    return out.astype(q.dtype)


def attention(q, k, v, *, impl: str = "chunked", causal: bool = True,
              chunk: int = 1024, q_offset: int = 0, qmap=None) -> jax.Array:
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, q_offset=q_offset,
                               qmap=qmap)
    if impl == "chunked" and q_offset == 0 and qmap is None \
            and q.shape[1] == k.shape[1]:
        return blocked_attention(q, k, v, causal=causal, block=chunk)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, chunk=chunk,
                                 q_offset=q_offset, qmap=qmap)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(_expand_kv(q, None) if qmap is None else q,
                                    _expand_kv(k, qmap), _expand_kv(v, qmap),
                                    causal=causal)
    raise ValueError(f"unknown attention impl {impl!r}")
