"""Decoder LM assembly for the dense / moe / vlm / ssm / hybrid families.

Layers run under lax.scan over a stacked parameter tree (one lowered layer →
small HLO, fast 512-device compiles) with configurable remat. The embedding
goes through the Parallax PS exchange (core/embedding.py); logits stay
vocab-sharded into the sharded cross-entropy (core/xent.py).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import embedding as emb
from repro.core import sp
from repro.core.xent import sharded_xent
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import ParamSpec, rms_norm, swiglu, stack_tree
from repro.utils.hlo import ATTENTION


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def attn_specs(cfg, rt) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    hp = rt.pad_heads(cfg.n_heads)
    kv = cfg.n_kv_heads
    return {
        "wq": ParamSpec((d, hp * hd), (None, "heads_hd"), fan_in_axes=(0,),
                        init="normal"),
        "wk": ParamSpec((d, kv * hd), (None, "kv_heads"), fan_in_axes=(0,)),
        "wv": ParamSpec((d, kv * hd), (None, "kv_heads"), fan_in_axes=(0,)),
        "wo": ParamSpec((hp * hd, d), ("heads_hd", None), fan_in_axes=(0,)),
    }


def mla_specs(cfg) -> dict:
    """Multi-head latent attention (DeepSeek-V2/V3, no query compression):
    queries of nope + rope dims per head; one latent of ``kv_lora_rank``
    (RMS-normed) and one rope key shared by every head from ``wkv_a``; the
    latent expanded to each head's nope key and value by ``wkv_b``."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((d, h * (nope + rd)), (None, "heads_hd"),
                        fan_in_axes=(0,)),
        "wkv_a": ParamSpec((d, r + rd), (None, None), fan_in_axes=(0,)),
        "kv_norm": ParamSpec((r,), (None,), init="ones"),
        "wkv_b": ParamSpec((r, h * (nope + vd)), (None, "heads_hd"),
                           fan_in_axes=(0,)),
        "wo": ParamSpec((h * vd, d), ("heads_hd", None), fan_in_axes=(0,)),
    }


def mlp_specs(cfg, width: int = 0) -> dict:
    d, f = cfg.d_model, width or cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), (None, "mlp"), fan_in_axes=(0,)),
        "w_up": ParamSpec((d, f), (None, "mlp"), fan_in_axes=(0,)),
        "w_down": ParamSpec((f, d), ("mlp", None), fan_in_axes=(0,)),
    }


def layer_specs(cfg, rt, moe_exec: str, dense: bool = False) -> dict:
    """One layer's specs; ``dense``: a leading dense layer of a MoE model
    (an MLP of ``dense_d_ff``)."""
    d = cfg.d_model
    if cfg.family == "ssm":
        return rwkv_mod.rwkv_block_specs(cfg)
    specs: dict[str, Any] = {
        "ln1": ParamSpec((d,), (None,), init="ones"),
        "attn": mla_specs(cfg) if cfg.mla else attn_specs(cfg, rt),
        "ln2": ParamSpec((d,), (None,), init="ones"),
    }
    if dense:
        specs["mlp"] = mlp_specs(cfg, cfg.dense_d_ff)
    elif cfg.family == "moe":
        specs["moe"] = moe_mod.moe_specs(cfg, moe_exec)
    else:
        specs["mlp"] = mlp_specs(cfg)
    if cfg.family == "hybrid":
        specs["ssm"] = ssm_mod.ssm_specs(cfg)
    return specs


def model_specs(cfg, rt) -> dict:
    d = cfg.d_model
    vp = rt.padded_vocab
    moe_exec = moe_mod.pick_exec_mode(cfg, rt) if cfg.n_experts else "tp"
    specs = {
        "embed": ParamSpec((vp, d), ("vocab", "embed"), init="embed",
                           sparse=True),
        "layers": stack_tree(layer_specs(cfg, rt, moe_exec),
                             cfg.n_layers - cfg.first_k_dense),
        "final_norm": ParamSpec((d,), (None,), init="ones"),
    }
    if cfg.first_k_dense:
        specs["dense_layers"] = stack_tree(
            layer_specs(cfg, rt, moe_exec, dense=True), cfg.first_k_dense)
    if not cfg.tie_embeddings:
        specs["head"] = ParamSpec((vp, d), ("vocab", "embed"), scale=0.02)
    return specs


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _zero_padded_qk(p_attn, cfg, rt):
    """Padded q-head columns must be zero for exactness; enforced at init
    (init_params masks them) — nothing to do at runtime."""
    return p_attn


def attn_block(p, x, *, cfg, rt, positions, layer_cache=None, cache_len=None,
               cross_kv=None, causal=True, return_kv=False):
    """Self (or cross) attention sub-block. Returns (out, new_cache).

    ``return_kv``: on the cache-less path, hand back this layer's (K, V)
    at the cache dtype — the serving engine's batched prefill collects them
    across layers and inserts the rows into the live decode cache at the
    request's slot (one dispatch per admission instead of prompt_len).
    """
    b, s, d = x.shape
    hd = cfg.head_dim
    hp = rt.pad_heads(cfg.n_heads)
    kv = cfg.n_kv_heads
    qmap = attn_mod.make_qmap(cfg.n_heads, kv, hp)

    use_sp = sp.sp_active(rt, x) and cross_kv is None and layer_cache is None
    if use_sp:
        # §Perf iteration A: one bf16 AG for the whole block half
        if sp.kv_local_favorable(rt, cfg):
            # replicated-KV weights: seq-local matmul + small output AG
            # beats the m-fold redundant full-seq matmul (§Perf iter A2)
            (qf,) = sp.proj_in(rt, x, [p["wq"]], [True])
            kf, vf = sp.local_proj(rt, x, [p["wk"], p["wv"]])
        else:
            qf, kf, vf = sp.proj_in(rt, x, [p["wq"], p["wk"], p["wv"]],
                                    [True, False, False])
        q = qf.reshape(b, s, hp, hd)
        k = kf.reshape(b, s, kv, hd)
        v = vf.reshape(b, s, kv, hd)
        if cfg.rope_theta:
            q = attn_mod.rope(q, positions, cfg.rope_theta)
            k = attn_mod.rope(k, positions, cfg.rope_theta)
    else:
        q = (x @ p["wq"]).reshape(b, s, hp, hd)
        q = rt.constrain(q, ("batch", None, "q_heads", None))
        if cross_kv is None:
            k = (x @ p["wk"]).reshape(b, s, kv, hd)
            v = (x @ p["wv"]).reshape(b, s, kv, hd)
            if cfg.rope_theta:
                q = attn_mod.rope(q, positions, cfg.rope_theta)
                k = attn_mod.rope(k, positions, cfg.rope_theta)
        else:
            k, v = cross_kv

    if layer_cache is not None:
        k_cache, v_cache = layer_cache
        if cross_kv is None:
            cl = jnp.asarray(cache_len)
            if cl.ndim == 1:
                # per-slot write: row b lands at its own length (the serving
                # engine's slot-paged decode). A one-hot select rather than a
                # scatter: it partitions cleanly on the sharded cache axis,
                # and an out-of-range slot (len >= S) simply writes nowhere.
                hit = jnp.arange(k_cache.shape[1])[None, :] == cl[:, None]
                k_cache = jnp.where(hit[:, :, None, None],
                                    k.astype(k_cache.dtype), k_cache)
                v_cache = jnp.where(hit[:, :, None, None],
                                    v.astype(v_cache.dtype), v_cache)
            else:
                # homogeneous batch: write the new K/V at cache_len
                # (sequence-sharded dim; GSPMD lowers the dynamic update on
                # the sharded axis)
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    k_cache, k.astype(k_cache.dtype), cache_len, axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    v_cache, v.astype(v_cache.dtype), cache_len, axis=1)
        out = attn_mod.decode_attention(
            q, k_cache, v_cache,
            cache_len + (1 if cross_kv is None else 0), qmap=qmap)
        new_cache = (k_cache, v_cache)
    else:
        out = attn_mod.attention(
            q, k, v, impl=rt.run_cfg.attention_impl,
            causal=(causal and cross_kv is None),
            chunk=rt.run_cfg.attention_chunk, qmap=qmap)
        new_cache = (k.astype(rt.dtype), v.astype(rt.dtype)) \
            if (return_kv and cross_kv is None) else None
    if hp > cfg.n_heads:
        # zero padded heads BEFORE the o-proj: keeps the padded columns
        # gradient-isolated, so padding is exactly output- and
        # training-equivalent to the unpadded model (DESIGN.md §2).
        mask = (jnp.arange(hp) < cfg.n_heads).astype(out.dtype)
        out = out * mask[None, None, :, None]
    if use_sp:
        return sp.proj_out(rt, out.reshape(b, s, hp * hd), p["wo"]), new_cache
    out = rt.constrain(out, ("batch", None, "q_heads", None))
    out = out.reshape(b, s, hp * hd) @ p["wo"]
    return out, new_cache


def mla_block(p, x, *, cfg, rt, positions):
    """Latent self-attention sub-block (training and prefill; no cache).
    Scores use the q/k head dim nope + rope (scale its -1/2 power); values
    have ``v_head_dim``."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (x @ p["wq"]).reshape(b, s, h, nope + rd)
    ckv = x @ p["wkv_a"]
    kv = (rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps) @ p["wkv_b"]
          ).reshape(b, s, h, nope + vd)
    q_pe = attn_mod.rope(q[..., nope:], positions, cfg.rope_theta)
    k_pe = attn_mod.rope(ckv[..., None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rd))], axis=-1)
    q = rt.constrain(q, ("batch", None, "q_heads", None))
    with jax.named_scope(ATTENTION):
        out = attn_mod.attention(q, k, kv[..., nope:],
                                 impl=rt.run_cfg.attention_impl, causal=True,
                                 chunk=rt.run_cfg.attention_chunk)
    out = rt.constrain(out, ("batch", None, "q_heads", None))
    return out.reshape(b, s, h * vd) @ p["wo"]


def _at_compute_dtype(p, rt):
    """A layer's floating parameters at the compute dtype, so that float32
    parameters (master weights) under bf16 matmuls keep the residual
    stream in bf16; the router stays as held, since it runs in float32."""
    out = jax.tree.map(lambda a: a.astype(rt.dtype)
                       if jnp.issubdtype(a.dtype, jnp.floating) else a, p)
    if "router" in p.get("moe", {}):
        out["moe"] = {**out["moe"], "router": p["moe"]["router"]}
    return out


def decoder_layer(p, x, *, cfg, rt, positions, layer_cache=None,
                  cache_len=None, moe_exec="tp", collect_kv=False):
    """Pre-norm decoder layer; returns (x, new_cache, metrics)."""
    metrics = {}
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.family == "hybrid":
        # hymba: parallel attention + SSM heads on the same normed input
        kv_cache = layer_cache[:2] if layer_cache is not None else None
        h_ssm = layer_cache[2] if layer_cache is not None else \
            ssm_mod.init_ssm_state(cfg, x.shape[0])
        attn_out, new_kv = attn_block(
            p["attn"], h, cfg=cfg, rt=rt, positions=positions,
            layer_cache=kv_cache, cache_len=cache_len, return_kv=collect_kv)
        ssm_out, h_ssm = ssm_mod.ssm_mix(p["ssm"], h, h_ssm, cfg=cfg, rt=rt)
        attn_out = (attn_out + ssm_out) * 0.5
        new_cache = (*new_kv, h_ssm) if new_kv is not None else None
    elif cfg.mla:
        if layer_cache is not None or collect_kv:
            raise NotImplementedError("latent attention has no decode cache")
        attn_out = mla_block(p["attn"], h, cfg=cfg, rt=rt,
                             positions=positions)
        new_cache = None
    else:
        attn_out, new_cache = attn_block(
            p["attn"], h, cfg=cfg, rt=rt, positions=positions,
            layer_cache=layer_cache, cache_len=cache_len,
            return_kv=collect_kv)
    x = x + attn_out
    x = rt.constrain(x, rt_residual_axes(rt, x))

    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        ffn_out, m = moe_mod.moe_ffn(p["moe"], h2, cfg=cfg, rt=rt,
                                     exec_mode=moe_exec)
        metrics.update(m)
    elif sp.sp_active(rt, h2):
        g, u = sp.proj_in(rt, h2, [p["mlp"]["w_gate"], p["mlp"]["w_up"]],
                          [True, True])
        ffn_out = sp.proj_out(rt, jax.nn.silu(g) * u, p["mlp"]["w_down"])
    else:
        ffn_out = swiglu(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                         p["mlp"]["w_down"], constrain=rt.constrain)
    x = x + ffn_out
    x = rt.constrain(x, rt_residual_axes(rt, x))
    return x, new_cache, metrics


def rt_residual_axes(rt, x):
    """Sequence-parallel residuals when the seq dim divides the model axis."""
    s = x.shape[1]
    m = rt.rules.axis_size("seq_sp")
    if rt.shape_cfg.kind != "decode" and m > 1 and s % m == 0:
        return ("batch", "seq_sp", None)
    return ("batch", None, None)


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def _layer_carry_init(cfg, rt, batch, cache_seq, dtype):
    """Per-layer decode cache (stacked over layers by the caller)."""
    hd, kv = cfg.head_dim, cfg.n_kv_heads
    if cfg.family == "ssm":
        return rwkv_mod.init_rwkv_carry(cfg, batch, dtype)
    kvc = (jnp.zeros((batch, cache_seq, kv, hd), dtype),
           jnp.zeros((batch, cache_seq, kv, hd), dtype))
    if cfg.family == "hybrid":
        return (*kvc, ssm_mod.init_ssm_state(cfg, batch))
    return kvc


def init_cache(cfg, rt, batch, cache_seq, dtype=jnp.bfloat16):
    one = _layer_carry_init(cfg, rt, batch, cache_seq, dtype)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers, *a.shape)), one)


def cache_pspec_tree(cfg, rt, batch, cache_seq):
    """PartitionSpecs matching init_cache's structure (for in_shardings)."""
    from repro.compat import P
    if rt.mesh is None:
        return None
    batch_axes = rt.rules.rules.get("batch")
    kv_seq = rt.rules.rules.get("kv_seq")
    if cfg.family == "ssm":
        return (P(None, batch_axes, None),
                P(None, batch_axes, None, None, None),
                P(None, batch_axes, None))
    kvspec = P(None, batch_axes, kv_seq, None, None)
    if cfg.family == "hybrid":
        return (kvspec, kvspec, P(None, batch_axes, None, None))
    return (kvspec, kvspec)


def forward(params, tokens, *, cfg, rt, cache=None, cache_len=None,
            embeds=None, collect_kv=False):
    """tokens (B,S) -> vocab-sharded logits (B,S,Vp), new cache, metrics.

    ``embeds``: precomputed frontend embeddings (modality stubs) added after
    lookup — for the chameleon VQ stub tokens suffice; seamless uses encdec.py.

    ``cache_len`` may be a scalar (homogeneous batch) or a per-slot (B,)
    vector — the serving engine's slot-paged decode, where every sequence in
    the batch sits at its own position. ``collect_kv`` makes the cache-less
    (prefill) path return the per-layer K/V stack instead of None, for
    insertion into a live decode cache.
    """
    moe_exec = moe_mod.pick_exec_mode(cfg, rt) if cfg.n_experts else "tp"
    if cfg.first_k_dense and (cache is not None or collect_kv):
        raise NotImplementedError("leading dense layers have no decode cache")
    b, s = tokens.shape
    ctx = rt.embed_ctx()
    x, emetrics = emb.lookup(params["embed"], tokens, ctx=ctx,
                             capacity=rt.embed_capacity_for("embed"))
    x = x.astype(rt.dtype)
    if embeds is not None:
        x = x + embeds.astype(rt.dtype)
    x = rt.constrain(x, rt_residual_axes(rt, x))

    if cache_len is None and cache is None:
        positions = jnp.arange(s)
    else:
        base = jnp.asarray(cache_len if cache_len is not None else 0)
        if base.ndim == 1:
            positions = base[:, None] + jnp.arange(s)[None, :]   # (B, S)
        else:
            positions = base + jnp.arange(s)

    remat = rt.run_cfg.remat
    policy = None if remat == "full" else \
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable

    def layer_fn(x, inp):
        p, layer_cache = inp
        if cfg.family == "ssm":
            x, new_carry = rwkv_mod.rwkv_block(p, x, layer_cache, cfg=cfg, rt=rt)
            return x, (new_carry, {})
        x, new_cache, metrics = decoder_layer(
            _at_compute_dtype(p, rt), x, cfg=cfg, rt=rt, positions=positions,
            layer_cache=layer_cache, cache_len=cache_len, moe_exec=moe_exec,
            collect_kv=collect_kv)
        return x, (new_cache, metrics)

    if cache is None and cfg.family == "ssm":
        # rwkv layers always carry (token-shift, wkv-state) — init fresh
        cache = init_cache(cfg, rt, b, 1, rt.dtype)

    if cache is not None:
        if remat in ("block", "full"):
            layer_fn = jax.checkpoint(layer_fn, policy=policy)
        xs = (params["layers"], cache)
        x, (new_cache, metrics) = jax.lax.scan(layer_fn, x, xs)
    elif collect_kv:
        # batched prefill: the scan stacks each layer's (K, V) into the
        # (n_layers, B, S, KV, hd) decode-cache layout
        def kv_fn(x, p):
            x, out = layer_fn(x, (p, None))
            return x, out
        if remat in ("block", "full"):
            kv_fn = jax.checkpoint(kv_fn, policy=policy)
        x, (new_cache, metrics) = jax.lax.scan(kv_fn, x, params["layers"])
    else:
        def no_cache_fn(x, p):
            x, (_, metrics) = layer_fn(x, (p, None))
            return x, metrics
        if remat in ("block", "full"):
            no_cache_fn = jax.checkpoint(no_cache_fn, policy=policy)
        if "dense_layers" in params:
            x, _ = jax.lax.scan(no_cache_fn, x, params["dense_layers"])
        x, metrics = jax.lax.scan(no_cache_fn, x, params["layers"])
        new_cache = None
    # per-layer counters add up over layers; a ``*_max`` takes the largest
    metrics = {k: (jnp.max if k.endswith("_max") else jnp.sum)(a, axis=0)
               for k, a in metrics.items()}
    metrics.update(emetrics)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,vd->bsv", x, head.astype(x.dtype))
    logits = rt.constrain(logits, ("batch", None, "vocab"))
    return logits, new_cache, metrics


def loss_fn(params, batch, *, cfg, rt):
    """batch: {tokens (B,S), labels (B,S)} -> scalar loss, metrics."""
    logits, _, metrics = forward(params, batch["tokens"], cfg=cfg, rt=rt,
                                 embeds=batch.get("embeds"))
    per_tok = sharded_xent(
        logits, batch["labels"], mesh=rt.mesh, model_axis="model",
        batch_axes=rt.batch_axes, vocab=cfg.vocab_size)
    loss = jnp.mean(per_tok)
    if "moe_aux" in metrics:
        loss = loss + 0.01 * metrics["moe_aux"] / cfg.n_layers
    metrics["xent"] = jnp.mean(per_tok)
    return loss, metrics


def decode_step(params, cache, tokens, cache_len, *, cfg, rt):
    """One serving step: tokens (B,1) + caches -> logits (B,1,Vp), cache'."""
    logits, new_cache, metrics = forward(
        params, tokens, cfg=cfg, rt=rt, cache=cache, cache_len=cache_len)
    return logits, new_cache, metrics
