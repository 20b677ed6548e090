"""Plain reference of the DeepSeek-V3 block (Moonlight-16B-A3B) and of its
training step, in straightforward ``jax.numpy``, on one chip's share of an
expert-parallel deployment.

It follows the published equations and imports nothing of the program:

- multi-head latent attention (DeepSeek-V2, no query compression): per
  head a query of ``qk_nope_head_dim`` + ``qk_rope_head_dim``; from
  ``wkv_a`` a latent of ``kv_lora_rank``, RMS-normed and expanded by
  ``wkv_b`` to each head's nope key and value, and one rope key shared by
  every head; RoPE (half-split layout) on the rope parts; causal softmax at
  scale (nope + rope)^-1/2; ``wo`` from the heads' values back;
- a leading dense layer with a SwiGLU MLP of ``dense_d_ff``; then MoE
  layers: a float32 router over all ``n_experts``, sigmoid scores, top-k,
  the chosen scores over their sum times ``routed_scale``; the experts held
  here (the first ``experts_held``) each applied to every token and
  multiplied by that token's weight for it (0 when not chosen), so nothing
  is dropped and nothing is sorted; routes to absent experts add nothing;
  shared experts (SwiGLU of ``shared_d_ff``) on every token;
- pre-norm residual layers (RMSNorm), a final RMSNorm, an untied head over
  the vocabulary slice, mean cross-entropy, global-norm clipping, AdamW
  (with the configuration's linear warmup, ``warmup_steps``, if any).

Memory: each layer runs under ``jax.checkpoint`` (a scan over the stacked
layers, so no layer's parameters are copied out), attention in query
blocks and the experts one at a time (each under ``jax.checkpoint``), and
the head over vocabulary blocks, so two rows of 8192 tokens fit one chip
beside the float32 parameters, gradient and moments once the program's
state is freed.

Precision: every product goes through ``mm`` or ``ein``, which round both
operands to ``dtype`` and accumulate in float32 at ``highest`` precision;
the control runs the same code at the precision below the configuration's
(``lstm_ref`` says how). The initial parameters are drawn row by row from
the seed (``lstm_ref._draw_rows``), so ``make_change_norms`` can draw any
block of them again.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from lib.lstm_ref import (B1, B2, EPS, _draw_rows, _is_spec, _mm,
                          _rows_cols, leaf_names, BLOCK_BYTES)

Q_BLOCK = 256          # attention query rows per block
V_BLOCK = 4096         # head vocabulary rows per block
# The embedding is drawn at unit scale, above the layers' outputs, so that
# each position's state at the router is its token's. At 0.02 the prefix
# mean that uniform attention gives every position at random weights
# outweighs the token, and every token goes to nearly the same experts.
EMBED_STD = 1.0


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _mat(shape, fan_in):
    return (shape, "normal", 1 / math.sqrt(fan_in))


def _ones(shape):
    return (shape, "ones", 0.0)


def param_specs(sizes: dict) -> dict:
    """name -> (shape, init, std): the program's parameter layout for the
    configuration ``sizes`` (its ``model``)."""
    d, v = sizes["d_model"], sizes["vocab_size"]
    h, r = sizes["n_heads"], sizes["kv_lora_rank"]
    nope, rd = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    vd = sizes["v_head_dim"]
    n_dense = sizes["first_k_dense"]
    n_moe = sizes["n_layers"] - n_dense
    held, f, sf = sizes["experts_held"], sizes["d_ff"], sizes["shared_d_ff"]

    def layer(n):
        return {"ln1": _ones((n, d)), "ln2": _ones((n, d)),
                "attn": {"wq": _mat((n, d, h * (nope + rd)), d),
                         "wkv_a": _mat((n, d, r + rd), d),
                         "kv_norm": _ones((n, r)),
                         "wkv_b": _mat((n, r, h * (nope + vd)), r),
                         "wo": _mat((n, h * vd, d), h * vd)}}

    dense = layer(n_dense)
    fd = sizes["dense_d_ff"]
    dense["mlp"] = {"w_gate": _mat((n_dense, d, fd), d),
                    "w_up": _mat((n_dense, d, fd), d),
                    "w_down": _mat((n_dense, fd, d), fd)}
    routed = layer(n_moe)
    routed["moe"] = {"router": ((n_moe, d, sizes["n_experts"]), "normal", 0.02),
                  "w_gate": _mat((n_moe, held, d, f), d),
                  "w_up": _mat((n_moe, held, d, f), d),
                  "w_down": _mat((n_moe, held, f, d), f),
                  "shared_gate": _mat((n_moe, d, sf), d),
                  "shared_up": _mat((n_moe, d, sf), d),
                  "shared_down": _mat((n_moe, sf, d), sf)}
    return {"dense_layers": dense,
            "embed": ((v, d), "normal", EMBED_STD),
            "final_norm": _ones((d,)),
            "head": ((v, d), "normal", 0.02),
            "layers": routed}


def _draw(key, i, spec, start, n):
    if spec[1] == "ones":
        return jnp.ones((n, spec[0][-1]), jnp.float32)
    return _draw_rows(key, i, spec, start, n)


def make_init(sizes: dict, dtype):
    """A jitted ``key -> params`` that draws every leaf on the device, in
    ``dtype``."""
    specs = param_specs(sizes)
    flat, tdef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)

    @jax.jit
    def init(key):
        out = [_draw(key, i, spec, 0, _rows_cols(spec[0])[0])
               .reshape(spec[0]).astype(dtype)
               for i, spec in enumerate(flat)]
        return jax.tree_util.tree_unflatten(tdef, out)

    init.names = leaf_names(specs)
    return init


def make_change_norms(sizes: dict, dtype, block_bytes: int = BLOCK_BYTES):
    """A jitted ``(params, key) -> per-leaf norms of params - init(key)``,
    the initial leaf drawn again one block of rows at a time, as
    ``lstm_ref.make_change_norms`` does."""
    flat = jax.tree_util.tree_flatten(param_specs(sizes), is_leaf=_is_spec)[0]

    def leaf(i, spec, p, key):
        rows, cols = _rows_cols(spec[0])
        blk = max(1, min(rows, block_bytes // (4 * cols)))
        p = p.reshape(rows, cols)

        def body(j, acc):
            start = jnp.minimum(j * blk, rows - blk)
            d = (jax.lax.dynamic_slice_in_dim(p, start, blk)
                 .astype(jnp.float32)
                 - _draw(key, i, spec, start, blk).astype(dtype)
                 .astype(jnp.float32))
            new = (start + jnp.arange(blk)) >= j * blk
            return acc + jnp.sum(jnp.where(new[:, None], d * d, 0.0))

        n = -(-rows // blk)
        return jnp.sqrt(jax.lax.fori_loop(0, n, body, jnp.float32(0.0)))

    @jax.jit
    def norms(params, key):
        return [leaf(i, spec, p, key) for i, (spec, p)
                in enumerate(zip(flat, jax.tree.leaves(params)))]

    return norms


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _ein(dtype):
    def ein(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype).astype(jnp.float32),
                          b.astype(dtype).astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    return ein


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B,S,H,D), positions 0..S-1; pairs (j, j + D/2) rotated by angle
    pos * theta^(-2j/D)."""
    s, dim = x.shape[1], x.shape[-1]
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(x, g, u, dn, mm):
    return mm(jax.nn.silu(mm(x, g)) * mm(x, u), dn)


def mla(p, x, sz, mm, ein):
    """Latent self-attention of x (B, S, d), causal from position 0."""
    b, s, _ = x.shape
    h, r = sz["n_heads"], sz["kv_lora_rank"]
    nope, rd = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"]
    vd = sz["v_head_dim"]
    theta = float(sz["rope_theta"])
    q = mm(x, p["wq"]).reshape(b, s, h, nope + rd)
    ckv = mm(x, p["wkv_a"])
    kv = mm(_rms(ckv[..., :r], p["kv_norm"], sz["norm_eps"]),
            p["wkv_b"]).reshape(b, s, h, nope + vd)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], theta)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = _rope(ckv[..., None, r:], theta)[:, :, 0]          # (B,S,rd)
    scale = (nope + rd) ** -0.5
    qb = min(Q_BLOCK, s)
    nb = s // qb

    @jax.checkpoint
    def block(i, qn, qp):
        sc = (ein("bqhd,bkhd->bhqk", qn, k_nope)
              + ein("bqhd,bkd->bhqk", qp, k_pe)) * scale
        qpos = i * qb + jnp.arange(qb)[:, None]
        sc = jnp.where(qpos >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return ein("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)

    def body(_, inp):
        i, qn, qp = inp
        return None, block(i, qn, qp)

    blocks = lambda a: a.reshape(b, nb, qb, *a.shape[2:]).swapaxes(0, 1)  # noqa: E731
    _, out = jax.lax.scan(body, None, (jnp.arange(nb), blocks(q_nope),
                                        blocks(q_pe)))
    out = out.swapaxes(0, 1).reshape(b, s, h * vd)
    return mm(out, p["wo"])


def route(p, x, sz, mm):
    """-> each token's top-k weights (normalised, scaled) and expert ids."""
    top, ids = jax.lax.top_k(jax.nn.sigmoid(mm(x, p["router"])),
                             sz["experts_per_token"])
    return top / (jnp.sum(top, -1, keepdims=True) + 1e-20) \
        * sz["routed_scale"], ids


def moe(p, x, sz, mm):
    """Routed (held experts, every token, weighted) plus shared experts,
    x (T, d)."""
    t, d = x.shape
    held = sz["experts_held"]
    top, ids = route(p, x, sz, mm)
    # each token's weight for each expert, 0 where it was not chosen
    gate = jnp.sum(jnp.where(ids[..., None] == jnp.arange(held), top[..., None],
                             0.0), axis=1)                        # (T, held)

    @jax.checkpoint
    def expert(x, g, wg, wu, wd):
        return g[:, None] * _swiglu(x, wg, wu, wd, mm)

    def body(acc, e):
        g, wg, wu, wd = e
        return acc + expert(x, g, wg, wu, wd), None

    out, _ = jax.lax.scan(body, jnp.zeros((t, d), jnp.float32),
                          (gate.T, p["w_gate"], p["w_up"], p["w_down"]))
    return out + _swiglu(x, p["shared_gate"], p["shared_up"],
                         p["shared_down"], mm)


def _layer(p, x, sz, mm, ein, dense):
    eps = sz["norm_eps"]
    x = x + mla(p["attn"], _rms(x, p["ln1"], eps), sz, mm, ein)
    h = _rms(x, p["ln2"], eps)
    b, s, d = h.shape
    h = h.reshape(b * s, d)
    if dense:
        m = p["mlp"]
        y = _swiglu(h, m["w_gate"], m["w_up"], m["w_down"], mm)
    else:
        y = moe(p["moe"], h, sz, mm)
    return x + y.reshape(b, s, d)


def xent(x, head, labels, mm):
    """Mean softmax cross-entropy of ``x`` (N, d) against ``head`` (V, d),
    streamed over blocks of ``V_BLOCK`` vocabulary rows."""
    v, d = head.shape
    blk = math.gcd(v, V_BLOCK)

    @jax.checkpoint
    def body(carry, w):
        m, s = carry
        lg = mm(x, w.T)
        m_new = jnp.maximum(m, jnp.max(lg, -1))
        s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(lg - m_new[:, None]), -1)
        return (m_new, s), None

    n = x.shape[0]
    (m, s), _ = jax.lax.scan(
        body, (jnp.full((n,), -jnp.inf, jnp.float32),
               jnp.zeros((n,), jnp.float32)), head.reshape(v // blk, blk, d))
    tgt = jnp.sum(x.astype(mm.dtype).astype(jnp.float32)
                  * head[labels].astype(mm.dtype).astype(jnp.float32), -1)
    return jnp.mean(jnp.log(s) + m - tgt)


def loss_fn(params, batch, sz, mm, ein):
    x = params["embed"][batch["tokens"]]
    for dense, key in ((True, "dense_layers"), (False, "layers")):
        layer = jax.checkpoint(functools.partial(
            _layer, sz=sz, mm=mm, ein=ein, dense=dense))
        x, _ = jax.lax.scan(lambda x, p: (layer(p, x), None), x, params[key])
    x = _rms(x, params["final_norm"], sz["norm_eps"])
    d = x.shape[-1]
    return xent(x.reshape(-1, d), params["head"],
                batch["labels"].reshape(-1), mm)


# ---------------------------------------------------------------------------
# training step
# ---------------------------------------------------------------------------

def make_step(sizes: dict, optim: dict, dtype=jnp.float32, rows=None):
    """A training step ``(params, m, v, t, batch) -> (params, m, v, loss,
    per-leaf norms of the clipped gradient)``, as ``lstm_ref.make_step``,
    in two jitted programs: the gradient, and clipping with AdamW. Between
    them and between steps the moments wait in host memory, so the
    gradient's program has the device to itself beside the parameters."""
    mm, ein = _mm(dtype), _ein(dtype)
    kind, lr, clip = optim["optimizer"], optim["learning_rate"], \
        optim["clip_norm"]
    warm = optim.get("warmup_steps", 0)
    if kind != "adamw" or optim.get("weight_decay", 0.0):
        raise ValueError(f"the reference has no {kind!r} with weight decay "
                         f"{optim.get('weight_decay')}")

    @jax.jit
    def grad(params, batch):
        if rows is not None:
            batch = {k: a[rows] for k, a in batch.items()}
        p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        return jax.value_and_grad(loss_fn)(p32, batch, sizes, mm, ein)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def apply(params, m, v, grads, t):
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        tf = t.astype(jnp.float32)
        bc1, bc2 = 1.0 - B1 ** tf, 1.0 - B2 ** tf
        lr_t = lr * jnp.minimum(1.0, tf / warm) if warm else lr
        m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, m, grads)
        v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, v, grads)
        params = jax.tree.map(
            lambda p, a, b: (p.astype(jnp.float32) - lr_t * (a / bc1)
                             / (jnp.sqrt(b / bc2) + EPS)).astype(p.dtype),
            params, m, v)
        return params, m, v, [jnp.sqrt(jnp.sum(jnp.square(g)))
                              for g in jax.tree.leaves(grads)]

    def step(params, m, v, t, batch):
        loss, grads = grad(params, batch)
        params, m, v, norms = apply(params, jax.device_put(m),
                                    jax.device_put(v), grads, t)
        return params, jax.device_get(m), jax.device_get(v), loss, norms

    return step


def run(sizes: dict, optim: dict, key_seed: int, batches: list,
        dtype=jnp.float32, rows=None) -> dict:
    """Three (or ``len(batches)``) training steps from the seeded initial
    parameters, as ``lstm_ref.run``: each step's loss, the first step's
    per-leaf gradient norm and the per-leaf parameter change after the
    last."""
    pdt = jnp.dtype(optim["param_dtype"])
    init = make_init(sizes, pdt)
    key = jax.random.key(key_seed)
    hold = dtype if jnp.dtype(dtype).itemsize < 4 else jnp.float32
    params = jax.tree.map(lambda a: a.astype(hold), init(key))
    m = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), params)
    v = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), params)
    step = make_step(sizes, optim, dtype, rows)
    losses, grad_norms = [], None
    for i, b in enumerate(batches):
        b = {k: jnp.asarray(a) for k, a in b.items()}
        params, m, v, loss, gn = step(params, m, v, jnp.int32(i + 1), b)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = [float(x) for x in gn]
    del m, v
    change = [float(x) for x in make_change_norms(sizes, pdt)(params, key)]
    return {"names": init.names, "losses": losses,
            "grad_norms": grad_norms, "change_norms": change}
