"""Optimizers with Parallax placement discipline.

Correctness rules from the paper (§3.1, §5.3.2) enforced structurally:
  * gradient clipping happens AFTER aggregation (grads from jax.grad in
    global semantics are post-aggregation by construction); the global norm
    is computed as per-shard partial ‖g‖² + scalar psum — only scalars cross
    shards (OPAU). The OPAU=off baseline force-replicates gradients first so
    the naive placement's extra all-gathers are visible in HLO.
  * AccumParams (Adam moments, momentum) live with their parameter shard
    (same sharding as the parameter, optionally further sharded by ZeRO-1).
  * EMA shadow parameters update when their parameter updates, on the same
    shard (the paper's moving-average placement rule).

Gradients arrive pre-aggregated either way the exchange ran: per-tensor
(XLA-inserted collectives, global semantics) or bucketed (core/buckets.py
fuses the dense push into flat buffers and unflattens before handing them
here) — so the update, clipping, and the moments stay per-tensor and
placement-identical under both exchanges; nothing below may re-aggregate.

Fused bucket-apply: under the bucketed exchange the all-reduced gradient
already exists as one flat buffer per bucket, so unflattening it into
per-parameter leaves only to re-walk them leaf-by-leaf in ``update`` is a
pure memory-traffic tax. ``fuse_state``/``unfuse_state`` re-lay the m/v/EMA
state as one flat f32 buffer per bucket (params stay per-leaf — the model
needs them), and ``Optimizer.update_fused`` reads each post-psum buffer
directly against that layout: one elementwise chain per bucket instead of
one per parameter. A shaped bucket (``Bucket.shaped``: one member, ring
schedule) keeps its buffers in the member leaf's shape: with nothing to
concatenate, a flat buffer would only re-lay the leaf, which on the TPU is
a physical copy of a tiled 2-D array, not a bitcast; its chain is the
per-leaf chain. Bit-identical to ``update`` at every dtype: the per-leaf
reference is elementwise, and every fused op applies the same cast chain to
the same linear values (the global-norm partial sums accumulate in the same
leaf order). Param-wise weight-decay masks become per-bucket segment
vectors (``_wd_segment``). core/transform.py fuses on build and the
trainer unfuses back to the canonical per-param layout for checkpoints,
replans, and remeshes.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    m: Any          # first moment / momentum (None for sgd)
    v: Any          # second moment (None for sgd/momentum)
    ema: Any        # EMA shadow params (None if disabled)
    # bounded-staleness buffers for sparse tables (None unless
    # RunConfig.max_staleness > 0): {table: {"g": f32 grad buffer,
    # "age": int32 scalar}}. The buffer exists for every eligible table
    # whenever the machinery is on — sync<->stale flips change only the
    # update rule in the train step, never the state pytree. Optimizer
    # update fns construct TrainState positionally and never touch this
    # field; the staleness wrapper in core/transform.py re-attaches it.
    stale: Any = None


@dataclass(frozen=True)
class Optimizer:
    name: str
    init: Callable[[Any], TrainState]
    update: Callable[[TrainState, Any], tuple[TrainState, dict]]
    # bucket-native apply: (state, grads, flat post-psum bucket buffers,
    # BucketPlan) -> (state, metrics); None = per-param only (sgd)
    update_fused: Optional[Callable] = None


# ---------------------------------------------------------------------------
# fused bucket-apply state layout
# ---------------------------------------------------------------------------

def is_fused(state: Optional[TrainState]) -> bool:
    """Is this state's optimizer memory in the bucket-fused layout?"""
    return (state is not None and isinstance(state.m, dict)
            and set(state.m) == {"bucket", "leaf"})


def bucket_segments(bp) -> dict:
    """leaf index -> (bucket k, offset, size) over the bucketed leaves."""
    out = {}
    for k, b in enumerate(bp.buckets):
        off = 0
        for i, sz in zip(b.idx, b.sizes):
            out[i] = (k, off, sz)
            off += sz
    return out


def _flat_with_none(tree):
    """Flatten keeping ``None`` placeholders as positional leaves — the
    fused leaf-trees hold None at bucketed positions (no buffer at all, so
    nothing to shard or donate), and indices must stay aligned with the
    params flatten order."""
    return jax.tree_util.tree_flatten(tree, is_leaf=lambda x: x is None)


def _member(buf, b, off: int, sz: int):
    """One member's extent of a bucket buffer: the whole buffer of a shaped
    bucket (already in the leaf's shape), else its flat ``[off, off+sz)``
    segment."""
    return buf if b.shaped else buf[off:off + sz]


def fuse_state(state: Optional[TrainState], bp) -> Optional[TrainState]:
    """Per-param -> bucket-fused optimizer-state layout: m/v/EMA become one
    f32 buffer per bucket ({"bucket": [...], "leaf": tree}) — flat, or the
    member leaf's own shape for a shaped bucket; bucketed positions in the
    leaf tree hold ``None`` placeholders so the structure still mirrors
    params positionally (flatten with ``_flat_with_none``). Exact — buffers
    are concatenations of the per-leaf f32 values in bucket member order."""
    if state is None or bp is None or is_fused(state):
        return state

    def fuse(tree):
        if tree is None:
            return None
        leaves, tdef = jax.tree_util.tree_flatten(tree)
        bufs = [leaves[b.idx[0]].astype(jnp.float32) if b.shaped
                else jnp.concatenate([leaves[i].astype(jnp.float32)
                                      .reshape(-1) for i in b.idx])
                for b in bp.buckets]
        for b in bp.buckets:
            for i in b.idx:
                leaves[i] = None
        return {"bucket": bufs,
                "leaf": jax.tree_util.tree_unflatten(tdef, leaves)}

    return state._replace(m=fuse(state.m), v=fuse(state.v),
                          ema=fuse(state.ema))


def unfuse_state(state: Optional[TrainState], bp) -> Optional[TrainState]:
    """Bucket-fused -> canonical per-param layout (checkpoint/replan form).
    Exact inverse of ``fuse_state`` for the same bucket plan."""
    if state is None or bp is None or not is_fused(state):
        return state
    pleaves = jax.tree_util.tree_leaves(state.params)

    def unfuse(tree):
        if tree is None or not (isinstance(tree, dict)
                                and set(tree) == {"bucket", "leaf"}):
            return tree
        leaves, tdef = _flat_with_none(tree["leaf"])
        for k, b in enumerate(bp.buckets):
            buf, off = tree["bucket"][k], 0
            for i, sz in zip(b.idx, b.sizes):
                leaves[i] = _member(buf, b, off, sz).reshape(pleaves[i].shape)
                off += sz
        return jax.tree_util.tree_unflatten(tdef, leaves)

    return state._replace(m=unfuse(state.m), v=unfuse(state.v),
                          ema=unfuse(state.ema))


def _wd_segment(b, weight_decay: float, mask_leaves: Optional[list]):
    """Per-bucket weight-decay segment: the param-wise mask expanded over
    the bucket's member extents (scalar when the mask is absent or the
    bucket has one member)."""
    if not mask_leaves:
        return weight_decay
    if len(b.idx) == 1:
        return weight_decay * float(mask_leaves[b.idx[0]])
    return jnp.concatenate([
        jnp.full((sz,), float(weight_decay) * float(mask_leaves[i]),
                 jnp.float32) for i, sz in zip(b.idx, b.sizes)])


def global_norm(grads, rt=None) -> jax.Array:
    """Post-aggregation global norm; partial-sums + scalar reduction (OPAU)."""
    leaves = jax.tree.leaves(grads)
    if rt is not None and not rt.run_cfg.opau and rt.mesh is not None:
        # naive placement baseline: replicate the aggregated grads first
        from repro.compat import NamedSharding, P
        leaves = [jax.lax.with_sharding_constraint(
            g, NamedSharding(rt.mesh, P())) for g in leaves]
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def clip_by_global_norm(grads, max_norm: float, rt=None):
    norm = global_norm(grads, rt)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        grads), norm


def _ema_update(ema, params, decay):
    if ema is None:
        return None
    return jax.tree.map(
        lambda e, p: (e.astype(jnp.float32) * decay
                      + p.astype(jnp.float32) * (1 - decay)).astype(e.dtype),
        ema, params)


def adamw(lr: float | Callable = 1e-3, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.0,
          clip_norm: Optional[float] = 1.0, ema_decay: float = 0.0,
          wd_mask=None, rt=None) -> Optimizer:
    """``wd_mask``: optional params-structured tree of per-parameter floats
    multiplying ``weight_decay`` (0.0 = no decay for that leaf); the fused
    path expands it into per-bucket segment vectors."""
    lr_fn = lr if callable(lr) else (lambda step: lr)

    def init(params) -> TrainState:
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        # EMA shadow is a *copy*: astype(f32) on f32 params would alias the
        # param buffer and break donation (same buffer donated twice)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            m=jax.tree.map(zeros, params),
            v=jax.tree.map(zeros, params),
            ema=jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                             params)
            if ema_decay > 0 else None,
        )

    def update(state: TrainState, grads) -> tuple[TrainState, dict]:
        metrics = {}
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm, rt)
            metrics["grad_norm"] = gnorm
        step = state.step + 1
        t = step.astype(jnp.float32)
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t
        lr_t = lr_fn(step)

        def upd(p, g, m, v, wdm=1.0):
            g32 = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g32
            v = b2 * v + (1 - b2) * jnp.square(g32)
            upd32 = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if weight_decay:
                upd32 = upd32 + (weight_decay * float(wdm)) \
                    * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * upd32).astype(p.dtype), m, v

        if wd_mask is not None:
            out = jax.tree.map(upd, state.params, grads, state.m, state.v,
                               wd_mask)
        else:
            out = jax.tree.map(upd, state.params, grads, state.m, state.v)
        params = jax.tree.map(lambda o: o[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
        m = jax.tree.map(lambda o: o[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
        v = jax.tree.map(lambda o: o[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
        ema = _ema_update(state.ema, params, ema_decay)
        return TrainState(step, params, m, v, ema), metrics

    def update_fused(state: TrainState, grads, bufs, bp):
        """Bucket-native adamw: each all-reduced bucket buffer (flat, or in
        its leaf's shape for a shaped bucket) drives one elementwise chain
        against the fused m/v/EMA buffers; only the
        unbucketed leaves (sparse tables) walk the per-leaf path. The cast
        chain per bucket (wire f32 -> param dtype -> f32, clip, moments,
        param slice-back) replays the per-param reference op for op, so the
        two paths are bit-identical."""
        metrics = {}
        pleaves, ptree = jax.tree_util.tree_flatten(state.params)
        gleaves = list(jax.tree_util.tree_leaves(grads))
        seg = bucket_segments(bp)
        mask_leaves = (jax.tree_util.tree_leaves(wd_mask)
                       if wd_mask is not None else None)
        # mirror the per-param buf -> g.dtype -> f32 chain bitwise
        gbufs = [bufs[k].astype(pleaves[b.idx[0]].dtype).astype(jnp.float32)
                 for k, b in enumerate(bp.buckets)]
        if clip_norm is not None:
            sq = []
            for i in range(len(pleaves)):
                if i in seg:
                    # reshape to the leaf's shape before reducing: the
                    # per-param reference reduces each leaf in its natural
                    # shape (the exchange slice-back reshapes first), and a
                    # flat 1-D reduction associates differently at size
                    k, off, sz = seg[i]
                    sq.append(jnp.sum(jnp.square(_member(
                        gbufs[k], bp.buckets[k], off, sz)
                        .reshape(pleaves[i].shape))))
                else:
                    sq.append(jnp.sum(jnp.square(
                        gleaves[i].astype(jnp.float32))))
            gnorm = jnp.sqrt(sum(sq))
            scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
            gbufs = [(gb * scale).astype(pleaves[b.idx[0]].dtype)
                     .astype(jnp.float32)
                     for gb, b in zip(gbufs, bp.buckets)]
            gleaves = [g if i in seg else
                       (g.astype(jnp.float32) * scale).astype(g.dtype)
                       for i, g in enumerate(gleaves)]
            metrics["grad_norm"] = gnorm
        step = state.step + 1
        t = step.astype(jnp.float32)
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t
        lr_t = lr_fn(step)
        mB, vB = list(state.m["bucket"]), list(state.v["bucket"])
        emaB = list(state.ema["bucket"]) if state.ema is not None else None
        new_p = list(pleaves)
        for k, b in enumerate(bp.buckets):
            g32 = gbufs[k]
            pdt = pleaves[b.idx[0]].dtype
            m = b1 * mB[k] + (1 - b1) * g32
            v = b2 * vB[k] + (1 - b2) * jnp.square(g32)
            mB[k], vB[k] = m, v
            # the final param stage walks flat slices of the moment chains —
            # params stay per-leaf (the model needs them), so a flat param
            # buffer would only add a concat the per-param path never pays,
            # and slicing m/v (kernel outputs either way) lets each leaf's
            # tail fuse into one kernel instead of materialising a
            # bucket-wide update intermediate
            wd_seg = (_wd_segment(b, weight_decay, mask_leaves)
                      if weight_decay else None)
            off, enew = 0, []
            for i, sz in zip(b.idx, b.sizes):
                mi = _member(m, b, off, sz)
                p32 = pleaves[i].astype(jnp.float32).reshape(mi.shape)
                u = (mi / bc1) \
                    / (jnp.sqrt(_member(v, b, off, sz) / bc2) + eps)
                if wd_seg is not None:
                    w = wd_seg if jnp.ndim(wd_seg) == 0 \
                        else wd_seg[off:off + sz]
                    u = u + w * p32
                pn = p32 - lr_t * u
                new_p[i] = pn.reshape(pleaves[i].shape).astype(pdt)
                if emaB is not None:
                    # per member, as the per-leaf chain: one chain over
                    # the concatenated bucket rounds differently on the CPU
                    enew.append(_member(emaB[k], b, off, sz) * ema_decay
                                + pn.astype(pdt).astype(jnp.float32)
                                * (1 - ema_decay))
                off += sz
            if emaB is not None:
                emaB[k] = (jnp.concatenate(enew) if len(enew) > 1
                           else enew[0])
        mL, mdef = _flat_with_none(state.m["leaf"])
        vL = _flat_with_none(state.v["leaf"])[0]
        emaL = (_flat_with_none(state.ema["leaf"])[0]
                if state.ema is not None else None)
        for i in range(len(pleaves)):
            if i in seg:
                continue
            wdm = mask_leaves[i] if mask_leaves else 1.0
            p, g = pleaves[i], gleaves[i]
            g32 = g.astype(jnp.float32)
            mi = b1 * mL[i] + (1 - b1) * g32
            vi = b2 * vL[i] + (1 - b2) * jnp.square(g32)
            upd32 = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps)
            if weight_decay:
                upd32 = upd32 + (weight_decay * float(wdm)) \
                    * p.astype(jnp.float32)
            new_p[i] = (p.astype(jnp.float32) - lr_t * upd32).astype(p.dtype)
            mL[i], vL[i] = mi, vi
            if emaL is not None:
                emaL[i] = (emaL[i].astype(jnp.float32) * ema_decay
                           + new_p[i].astype(jnp.float32) * (1 - ema_decay))
        params = jax.tree_util.tree_unflatten(ptree, new_p)
        m = {"bucket": mB, "leaf": jax.tree_util.tree_unflatten(mdef, mL)}
        v = {"bucket": vB, "leaf": jax.tree_util.tree_unflatten(mdef, vL)}
        ema = ({"bucket": emaB,
                "leaf": jax.tree_util.tree_unflatten(mdef, emaL)}
               if state.ema is not None else None)
        return TrainState(step, params, m, v, ema), metrics

    return Optimizer("adamw", init, update, update_fused)


def momentum(lr: float | Callable = 1e-2, mu: float = 0.9,
             clip_norm: Optional[float] = None, ema_decay: float = 0.0,
             rt=None) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda step: lr)

    def init(params) -> TrainState:
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            m=jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            v=None,
            ema=jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                             params)
            if ema_decay > 0 else None)

    def update(state, grads):
        metrics = {}
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm, rt)
            metrics["grad_norm"] = gnorm
        step = state.step + 1
        lr_t = lr_fn(step)
        m = jax.tree.map(lambda mm, g: mu * mm + g.astype(jnp.float32),
                         state.m, grads)
        params = jax.tree.map(
            lambda p, mm: (p.astype(jnp.float32) - lr_t * mm).astype(p.dtype),
            state.params, m)
        ema = _ema_update(state.ema, params, ema_decay)
        return TrainState(step, params, m, None, ema), metrics

    def update_fused(state: TrainState, grads, bufs, bp):
        metrics = {}
        pleaves, ptree = jax.tree_util.tree_flatten(state.params)
        gleaves = list(jax.tree_util.tree_leaves(grads))
        seg = bucket_segments(bp)
        gbufs = [bufs[k].astype(pleaves[b.idx[0]].dtype).astype(jnp.float32)
                 for k, b in enumerate(bp.buckets)]
        if clip_norm is not None:
            sq = []
            for i in range(len(pleaves)):
                if i in seg:
                    # leaf-shaped reduction — see adamw.update_fused
                    k, off, sz = seg[i]
                    sq.append(jnp.sum(jnp.square(_member(
                        gbufs[k], bp.buckets[k], off, sz)
                        .reshape(pleaves[i].shape))))
                else:
                    sq.append(jnp.sum(jnp.square(
                        gleaves[i].astype(jnp.float32))))
            gnorm = jnp.sqrt(sum(sq))
            scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
            gbufs = [(gb * scale).astype(pleaves[b.idx[0]].dtype)
                     .astype(jnp.float32)
                     for gb, b in zip(gbufs, bp.buckets)]
            gleaves = [g if i in seg else
                       (g.astype(jnp.float32) * scale).astype(g.dtype)
                       for i, g in enumerate(gleaves)]
            metrics["grad_norm"] = gnorm
        step = state.step + 1
        lr_t = lr_fn(step)
        mB = list(state.m["bucket"])
        emaB = list(state.ema["bucket"]) if state.ema is not None else None
        new_p = list(pleaves)
        for k, b in enumerate(bp.buckets):
            pdt = pleaves[b.idx[0]].dtype
            mB[k] = mu * mB[k] + gbufs[k]
            off, enew = 0, []
            for i, sz in zip(b.idx, b.sizes):
                mi = _member(mB[k], b, off, sz)
                pn = pleaves[i].astype(jnp.float32).reshape(mi.shape) \
                    - lr_t * mi
                new_p[i] = pn.reshape(pleaves[i].shape).astype(pdt)
                if emaB is not None:
                    # per member, as the per-leaf chain: one chain over
                    # the concatenated bucket rounds differently on the CPU
                    enew.append(_member(emaB[k], b, off, sz) * ema_decay
                                + pn.astype(pdt).astype(jnp.float32)
                                * (1 - ema_decay))
                off += sz
            if emaB is not None:
                emaB[k] = (jnp.concatenate(enew) if len(enew) > 1
                           else enew[0])
        mL, mdef = _flat_with_none(state.m["leaf"])
        emaL = (_flat_with_none(state.ema["leaf"])[0]
                if state.ema is not None else None)
        for i in range(len(pleaves)):
            if i in seg:
                continue
            mL[i] = mu * mL[i] + gleaves[i].astype(jnp.float32)
            new_p[i] = (pleaves[i].astype(jnp.float32)
                        - lr_t * mL[i]).astype(pleaves[i].dtype)
            if emaL is not None:
                emaL[i] = (emaL[i].astype(jnp.float32) * ema_decay
                           + new_p[i].astype(jnp.float32) * (1 - ema_decay))
        params = jax.tree_util.tree_unflatten(ptree, new_p)
        m = {"bucket": mB, "leaf": jax.tree_util.tree_unflatten(mdef, mL)}
        ema = ({"bucket": emaB,
                "leaf": jax.tree_util.tree_unflatten(mdef, emaL)}
               if state.ema is not None else None)
        return TrainState(step, params, m, None, ema), metrics

    return Optimizer("momentum", init, update, update_fused)


def sgd(lr: float | Callable = 1e-2, clip_norm: Optional[float] = None,
        rt=None) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda step: lr)

    def init(params) -> TrainState:
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          m=None, v=None, ema=None)

    def update(state, grads):
        metrics = {}
        if clip_norm is not None:
            grads, gnorm = clip_by_global_norm(grads, clip_norm, rt)
            metrics["grad_norm"] = gnorm
        step = state.step + 1
        lr_t = lr_fn(step)
        params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr_t * g.astype(jnp.float32)).astype(p.dtype),
            state.params, grads)
        return TrainState(step, params, None, None, None), metrics

    return Optimizer("sgd", init, update)


def warmup(lr: float, steps: int) -> float | Callable:
    """``lr``, or for ``steps`` > 0 the schedule that rises linearly from 0
    and reaches ``lr`` at update ``steps`` (updates count from 1)."""
    if not steps:
        return lr
    return lambda step: lr * jnp.minimum(1.0, step.astype(jnp.float32)
                                         / steps)


def make_optimizer(rt) -> Optimizer:
    rc = rt.run_cfg
    lr = warmup(rc.learning_rate, rc.warmup_steps)
    if rc.optimizer == "adamw":
        return adamw(lr, weight_decay=rc.weight_decay,
                     clip_norm=rc.clip_norm, ema_decay=rc.ema_decay, rt=rt)
    if rc.optimizer == "momentum":
        return momentum(lr, clip_norm=rc.clip_norm,
                        ema_decay=rc.ema_decay, rt=rt)
    if rc.optimizer == "sgd":
        return sgd(lr, clip_norm=rc.clip_norm, rt=rt)
    raise ValueError(f"unknown optimizer {rc.optimizer!r}")
