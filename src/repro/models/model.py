"""Unified model API — the object the transform/launcher/trainer consume.

``build_model(cfg, rt)`` returns a Model with:
  specs()           ParamSpec tree (init / abstract / sharding all derive from it)
  init(key)         real parameters
  loss_fn           (params, batch) -> (loss, metrics)        [train shapes]
  prefill_fn        (params, batch) -> (logits, cache, metrics)
  decode_fn         (params, cache, tokens, cache_len) -> (logits, cache)
  input_specs(...)  ShapeDtypeStruct stand-ins for every input (dry-run)
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.runtime import Runtime
from repro.models import encdec, lstm, transformer
from repro.models.layers import abstract_tree, init_tree


@dataclass
class Model:
    cfg: ModelConfig
    rt: Runtime
    specs: Callable[[], Any]
    loss_fn: Callable
    prefill_fn: Callable
    decode_fn: Callable
    init_cache: Callable
    cache_pspecs: Callable
    # (params, tokens (B,S)) -> (logits, kv_cache) where kv_cache has the
    # decode-cache layout — the serving engine's batched prefill collects the
    # per-layer K/V for slot insertion. None for families whose recurrent
    # state cannot be prefix-prefilled exactly under padding (lstm/ssm) and
    # for enc-dec models (their prefill needs encoder inputs).
    prefill_cache_fn: Optional[Callable] = None

    def init(self, key) -> Any:
        return init_tree(key, self.specs(), self.rt.param_dtype)

    def abstract_params(self) -> Any:
        return abstract_tree(self.specs(), self.rt.param_dtype)

    # ------------------------------------------------------------------
    def input_specs(self, shape: Optional[ShapeConfig] = None) -> dict:
        """ShapeDtypeStruct stand-ins for the step inputs (no allocation)."""
        shape = shape or self.rt.shape_cfg
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        tok = lambda *sh: jax.ShapeDtypeStruct(sh, i32)
        if shape.kind in ("train", "prefill"):
            specs = {"tokens": tok(b, s)}
            if shape.kind == "train":
                specs["labels"] = tok(b, s)
            if cfg.is_encdec and cfg.family == "audio":
                se = s // encdec.enc_ratio(cfg)
                specs["frames"] = jax.ShapeDtypeStruct((b, se, cfg.d_model),
                                                       jnp.bfloat16)
            if cfg.is_encdec and cfg.family == "lstm":
                specs["src_tokens"] = tok(b, s)
            return specs
        # decode: one new token against a seq_len cache
        return {"tokens": tok(b, 1),
                "cache_len": jax.ShapeDtypeStruct((), i32)}

    def abstract_cache(self, shape: Optional[ShapeConfig] = None) -> Any:
        shape = shape or self.rt.shape_cfg
        cache = jax.eval_shape(
            lambda: self.init_cache(shape.global_batch, shape.seq_len))
        return cache


def build_model(cfg: ModelConfig, rt: Runtime) -> Model:
    if cfg.family == "lstm":
        return Model(
            cfg=cfg, rt=rt,
            specs=lambda: lstm.model_specs(cfg, rt),
            loss_fn=partial(lstm.loss_fn, cfg=cfg, rt=rt),
            prefill_fn=lambda p, b: lstm.forward(p, b, cfg=cfg, rt=rt),
            decode_fn=lambda p, state, tokens, cache_len: lstm.forward(
                p, {"tokens": tokens}, cfg=cfg, rt=rt, state=state)[:2],
            init_cache=lambda b, s: lstm._init_state(cfg, b, cfg.n_layers),
            cache_pspecs=lambda: None,
        )
    if cfg.is_encdec:
        def dec_fn(p, cache, tokens, cache_len):
            logits, new_cache, _ = encdec.forward(
                p, {"tokens": tokens}, cfg=cfg, rt=rt, cache=cache,
                cache_len=cache_len)
            return logits, new_cache
        return Model(
            cfg=cfg, rt=rt,
            specs=lambda: encdec.model_specs(cfg, rt),
            loss_fn=partial(encdec.loss_fn, cfg=cfg, rt=rt),
            prefill_fn=lambda p, b: encdec.forward(p, b, cfg=cfg, rt=rt),
            decode_fn=dec_fn,
            init_cache=lambda b, s: encdec.init_cache(
                cfg, rt, b, s, s // encdec.enc_ratio(cfg), rt.dtype),
            cache_pspecs=lambda: encdec.cache_pspec_tree(cfg, rt),
        )

    def dec_fn(p, cache, tokens, cache_len):
        logits, new_cache, _ = transformer.decode_step(
            p, cache, tokens, cache_len, cfg=cfg, rt=rt)
        return logits, new_cache

    def prefill_fn(p, b):
        return transformer.forward(p, b["tokens"], cfg=cfg, rt=rt,
                                   embeds=b.get("embeds"))

    def prefill_cache_fn(p, tokens):
        logits, kv, _ = transformer.forward(p, tokens, cfg=cfg, rt=rt,
                                            collect_kv=True)
        return logits, kv

    # exact bucketed prefill needs a purely positional cache: padded tail
    # tokens are masked out of attention by the per-slot length, but they
    # WOULD corrupt a recurrent carry (ssm) — so those families stay on the
    # decode loop. hybrid carries an ssm state alongside its KV: same story.
    paged = cfg.family in ("dense", "moe", "vlm") and not cfg.mla

    return Model(
        cfg=cfg, rt=rt,
        specs=lambda: transformer.model_specs(cfg, rt),
        loss_fn=partial(transformer.loss_fn, cfg=cfg, rt=rt),
        prefill_fn=prefill_fn,
        decode_fn=dec_fn,
        init_cache=lambda b, s: transformer.init_cache(cfg, rt, b, s, rt.dtype),
        cache_pspecs=lambda: transformer.cache_pspec_tree(
            cfg, rt, None, None),
        prefill_cache_fn=prefill_cache_fn if paged else None,
    )
