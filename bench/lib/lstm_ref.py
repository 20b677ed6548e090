"""Plain reference of the LSTM models (the paper's LM and its GNMT-style
NMT) and of their training step, in straightforward ``jax.numpy``.

It follows the configuration's stated mathematics and imports nothing of
the program: an LSTM cell with a projection (gates i, f, g, o; forget bias
+1), a residual around every layer whose input and output widths agree, for
the NMT a unidirectional encoder and a dot attention over its states mixed
into the decoder's output, a softmax over the whole vocabulary, mean
cross-entropy per target token, global-norm clipping and AdamW.

The initial parameters are drawn row by row from the seed, so the check
can draw any block of them again (``make_change_norms``) and never holds a
second whole copy beside the program's state.

Precision: every matrix product goes through ``mm``, which rounds both
operands to ``dtype`` and accumulates in float32 at ``highest`` precision.
The reference runs at ``float32``; the control runs the same code at the
next precision below the configuration's (float8 for bfloat16), and also
keeps its parameters in that precision, as the program keeps its own in
the configuration's. The head is computed in blocks of vocabulary rows, so
no (tokens x vocabulary) array is ever whole.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.95, 1e-8


def _mm(dtype):
    def mm(a, b):
        return jnp.matmul(a.astype(dtype).astype(jnp.float32),
                          b.astype(dtype).astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    mm.dtype = dtype
    return mm


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_specs(sizes: dict) -> dict:
    """name -> (shape, init, std): the program's parameter layout for the
    configuration ``sizes`` (vocab_size, d_model, d_ff, n_layers,
    enc_layers, is_encdec)."""
    v, d, h = sizes["vocab_size"], sizes["d_model"], sizes["d_ff"]

    def cell(n):
        return {"w_x": ((n, d, 4 * h), "normal", 1 / math.sqrt(d)),
                "w_h": ((n, d, 4 * h), "normal", 1 / math.sqrt(d)),
                "bias": ((n, 4 * h), "zeros", 0.0),
                "w_proj": ((n, h, d), "normal", 1 / math.sqrt(h))}

    specs = {"embed": ((v, d), "normal", 0.02),
             "layers": cell(sizes["n_layers"]),
             "head": ((v, d), "normal", 0.02)}
    if sizes.get("is_encdec"):
        specs["enc_layers"] = cell(sizes["enc_layers"])
        specs["enc_embed"] = ((v, d), "normal", 0.02)
        specs["attn_mix"] = ((2 * d, d), "normal", 1 / math.sqrt(2 * d))
    return specs


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def leaf_names(tree) -> list:
    """'/'-joined key paths of a dict tree, in flatten order."""
    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_spec)[0]
    return ["/".join(str(k.key) for k in p) for p, _ in paths]


def seed32(seed: int) -> int:
    """A 32-bit key from any whole-number seed (JAX keeps only the low 32
    bits of a larger one)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def _rows_cols(shape) -> tuple:
    return math.prod(shape[:-1]), shape[-1]


def _draw_rows(key, i: int, spec, start, n: int):
    """Rows ``start`` .. ``start + n`` of leaf ``i`` seen as (rows, cols),
    in float32: each row is drawn from its own fold of the leaf's key, so
    any block of rows can be drawn again without the rest."""
    shape, kind, std = spec
    cols = shape[-1]
    if kind == "zeros":
        return jnp.zeros((n, cols), jnp.float32)
    k = jax.random.fold_in(key, i)
    rows = start + jnp.arange(n)
    return jax.vmap(lambda r: jax.random.normal(
        jax.random.fold_in(k, r), (cols,), jnp.float32))(rows) * std


def make_init(sizes: dict, dtype):
    """A jitted ``key -> params`` that draws every leaf on the device, in
    ``dtype``."""
    specs = param_specs(sizes)
    names = leaf_names(specs)
    flat, tdef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)

    @jax.jit
    def init(key):
        out = [_draw_rows(key, i, spec, 0, _rows_cols(spec[0])[0])
               .reshape(spec[0]).astype(dtype)
               for i, spec in enumerate(flat)]
        return jax.tree_util.tree_unflatten(tdef, out)

    init.names = names
    return init


BLOCK_BYTES = 32 * 2 ** 20


def make_change_norms(sizes: dict, dtype, block_bytes: int = BLOCK_BYTES):
    """A jitted ``(params, key) -> per-leaf norms of params - init(key)``,
    in float32, the initial leaf drawn again in ``dtype`` one block of rows
    at a time (at most ``block_bytes`` of float32), so that no copy of the
    initial parameters is ever whole on the device."""
    flat = jax.tree_util.tree_flatten(param_specs(sizes), is_leaf=_is_spec)[0]

    def leaf(i, spec, p, key):
        rows, cols = _rows_cols(spec[0])
        blk = max(1, min(rows, block_bytes // (4 * cols)))
        p = p.reshape(rows, cols)

        def body(j, acc):
            # the last block is shifted back to fit; rows that an earlier
            # block counted are masked out
            start = jnp.minimum(j * blk, rows - blk)
            d = (jax.lax.dynamic_slice_in_dim(p, start, blk)
                 .astype(jnp.float32)
                 - _draw_rows(key, i, spec, start, blk).astype(dtype)
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

def _lstm(p, xs, mm):
    """One LSTM-with-projection layer over (B, S, Din) -> (B, S, P)."""
    b = xs.shape[0]
    h_units, proj = p["w_proj"].shape
    gx = mm(xs, p["w_x"])

    def step(carry, g_t):
        c, h = carry
        gates = g_t + mm(h, p["w_h"]) + p["bias"]
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = mm(jax.nn.sigmoid(o) * jnp.tanh(c), p["w_proj"])
        return (c, h), h

    init = (jnp.zeros((b, h_units), jnp.float32),
            jnp.zeros((b, proj), jnp.float32))
    _, ys = jax.lax.scan(step, init, gx.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2)


def _stack(layers, x, mm):
    n = layers["w_x"].shape[0]
    for i in range(n):
        y = _lstm({k: v[i] for k, v in layers.items()}, x, mm)
        x = x + y if y.shape == x.shape else y
    return x


def features(params, batch, mm):
    """Decoder output before the head, (B, S, d)."""
    x = _stack(params["layers"], params["embed"][batch["tokens"]], mm)
    if "enc_layers" in params:
        enc = _stack(params["enc_layers"],
                     params["enc_embed"][batch["src_tokens"]], mm)
        d = x.shape[-1]
        scores = jnp.einsum("bsd,btd->bst", x, enc,
                            precision=jax.lax.Precision.HIGHEST) * d ** -0.5
        ctx = jnp.einsum("bst,btd->bsd", jax.nn.softmax(scores, -1), enc,
                         precision=jax.lax.Precision.HIGHEST)
        x = mm(jnp.concatenate([x, ctx], -1), params["attn_mix"])
    return x


def vocab_block(v: int, limit: int = 25000) -> int:
    """The largest divisor of ``v`` not above ``limit`` (``v`` itself when
    small enough), so the head splits into equal blocks without a copy."""
    if v <= 2 * limit:
        return v
    return max(k for k in range(1, limit + 1) if v % k == 0)


def xent(x, head, labels, mm):
    """Mean softmax cross-entropy of ``x`` (N, d) against ``head`` (V, d),
    streamed over vocabulary blocks (log-sum-exp carried across them)."""
    v, d = head.shape
    blk = vocab_block(v)
    blocks = head.reshape(v // blk, blk, d)

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
               jnp.zeros((n,), jnp.float32)), blocks)
    tgt = jnp.sum(x.astype(mm.dtype).astype(jnp.float32)
                  * head[labels].astype(mm.dtype).astype(jnp.float32), -1)
    return jnp.mean(jnp.log(s) + m - tgt)


def loss_fn(params, batch, mm):
    x = features(params, batch, mm)
    d = x.shape[-1]
    return xent(x.reshape(-1, d), params["head"],
                batch["labels"].reshape(-1), mm)


# ---------------------------------------------------------------------------
# training step
# ---------------------------------------------------------------------------

def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
            for g in jax.tree.leaves(tree)]


def make_step(optim: dict, dtype=jnp.float32, rows=None):
    """A jitted training step ``(params, m, v, t, batch) -> (params, m, v,
    loss, per-leaf norms of the clipped gradient)`` in precision ``dtype``,
    by the configuration's ``optim`` (its ``run_config``: ``optimizer``
    ``adamw``, ``learning_rate``, ``clip_norm``). ``rows`` (a slice) takes
    the loss and the gradient from those rows of the batch alone, for the
    planted faults."""
    mm = _mm(dtype)
    kind, lr = optim["optimizer"], optim["learning_rate"]
    clip = optim["clip_norm"]
    if kind != "adamw" or optim.get("weight_decay", 0.0):
        raise ValueError(f"the reference has no {kind!r} with weight decay "
                         f"{optim.get('weight_decay')}")

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, batch):
        if rows is not None:
            batch = {k: a[rows] for k, a in batch.items()}
        p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        loss, grads = jax.value_and_grad(loss_fn)(p32, batch, mm)
        del p32
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        tf = t.astype(jnp.float32)
        bc1, bc2 = 1.0 - B1 ** tf, 1.0 - B2 ** tf
        m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, m, grads)
        v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, v, grads)
        params = jax.tree.map(
            lambda p, a, b: (p.astype(jnp.float32) - lr * (a / bc1)
                             / (jnp.sqrt(b / bc2) + EPS)).astype(p.dtype),
            params, m, v)
        return params, m, v, loss, _norms(grads)

    return step


def run(sizes: dict, optim: dict, key_seed: int, batches: list,
        dtype=jnp.float32, rows=None) -> dict:
    """Three (or ``len(batches)``) training steps from the seeded initial
    parameters: each step's loss, the first step's per-leaf gradient norm
    and the per-leaf parameter change after the last.

    The initial parameters are drawn in the configuration's
    ``param_dtype`` and then held in ``dtype`` when that is narrower, else
    in float32."""
    pdt = jnp.dtype(optim["param_dtype"])
    init = make_init(sizes, pdt)
    key = jax.random.key(key_seed)
    hold = dtype if jnp.dtype(dtype).itemsize < 4 else jnp.float32
    params = jax.tree.map(lambda a: a.astype(hold), init(key))
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    step = make_step(optim, dtype, rows)
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
