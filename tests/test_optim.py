"""Optimizers vs hand-rolled references; OPAU clip semantics; EMA;
fused bucket-apply layout + bit-exactness vs the per-param path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.buckets import Bucket, BucketPlan
from repro.optim.optimizer import adamw, momentum, sgd, global_norm, \
    clip_by_global_norm, bucket_segments, fuse_state, is_fused, \
    unfuse_state, warmup


def _params():
    k = jax.random.key(0)
    return {"a": jax.random.normal(k, (4, 8), jnp.float32),
            "b": {"w": jax.random.normal(jax.random.fold_in(k, 1), (8,),
                                         jnp.float32)}}


def _grads(scale=1.0):
    k = jax.random.key(9)
    return {"a": scale * jax.random.normal(k, (4, 8), jnp.float32),
            "b": {"w": scale * jax.random.normal(jax.random.fold_in(k, 2),
                                                 (8,), jnp.float32)}}


def test_adamw_matches_reference():
    lr, b1, b2, eps = 1e-2, 0.9, 0.95, 1e-8
    opt = adamw(lr, b1, b2, eps, clip_norm=None)
    state = opt.init(_params())
    g = _grads()
    state2, _ = opt.update(state, g)

    # manual reference, step 1
    for name, p0, gl in [("a", _params()["a"], g["a"]),
                         ("bw", _params()["b"]["w"], g["b"]["w"])]:
        m = (1 - b1) * gl
        v = (1 - b2) * jnp.square(gl)
        mhat = m / (1 - b1)
        vhat = v / (1 - b2)
        want = p0 - lr * mhat / (jnp.sqrt(vhat) + eps)
        got = state2.params["a"] if name == "a" else state2.params["b"]["w"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5)


def test_warmup_rises_linearly_to_the_lr():
    """No warmup keeps the plain float (the step compiles as before); a
    warmup of 4 updates moves update t by t/4 of the lr, then by the lr."""
    assert warmup(1e-2, 0) == 1e-2
    sched = warmup(1e-2, 4)
    got = [float(sched(jnp.int32(t))) for t in (1, 2, 4, 9)]
    np.testing.assert_allclose(got, [2.5e-3, 5e-3, 1e-2, 1e-2], rtol=1e-6)
    # through adamw: the first update (sign-like) moves each element by lr/4
    opt = adamw(sched, clip_norm=None)
    state2, _ = opt.update(opt.init(_params()), _grads())
    moved = np.abs(np.asarray(state2.params["a"]) - np.asarray(_params()["a"]))
    np.testing.assert_allclose(moved, 2.5e-3, rtol=1e-4)


def test_clip_by_global_norm_matches_formula():
    g = _grads(scale=10.0)
    norm = float(global_norm(g))
    want = np.sqrt(sum(float(jnp.sum(x * x)) for x in jax.tree.leaves(g)))
    assert abs(norm - want) / want < 1e-6
    clipped, _ = clip_by_global_norm(g, 1.0)
    post = float(global_norm(clipped))
    assert abs(post - 1.0) < 1e-4


def test_clip_noop_below_threshold():
    g = _grads(scale=1e-3)
    clipped, norm = clip_by_global_norm(g, 1.0)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(clipped)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_momentum_and_sgd_step():
    p = _params()
    g = _grads()
    s_sgd = sgd(0.1).init(p)
    s_sgd2, _ = sgd(0.1).update(s_sgd, g)
    np.testing.assert_allclose(np.asarray(s_sgd2.params["a"]),
                               np.asarray(p["a"] - 0.1 * g["a"]), rtol=1e-6)
    opt = momentum(0.1, mu=0.9, clip_norm=None)
    s2, _ = opt.update(opt.init(p), g)
    np.testing.assert_allclose(np.asarray(s2.params["a"]),
                               np.asarray(p["a"] - 0.1 * g["a"]), rtol=1e-6)
    s3, _ = opt.update(s2, g)
    want = s2.params["a"] - 0.1 * (0.9 * g["a"] + g["a"])
    np.testing.assert_allclose(np.asarray(s3.params["a"]), np.asarray(want),
                               rtol=1e-5)


def test_ema_tracks_params():
    opt = adamw(1e-2, ema_decay=0.5, clip_norm=None)
    state = opt.init(_params())
    state2, _ = opt.update(state, _grads())
    want = 0.5 * np.asarray(state.ema["a"]) + 0.5 * np.asarray(
        state2.params["a"], np.float32)
    np.testing.assert_allclose(np.asarray(state2.ema["a"]), want, rtol=1e-5)


# ---------------------------------------------------------------------------
# fused bucket-apply: state layout + bit-exactness vs the per-param path
# ---------------------------------------------------------------------------

def _bucket_plan():
    """One bucket holding leaf 0 ('a', 32 elements); leaf 1 ('b/w') stays
    unbucketed — both the bucket-native and the surviving per-leaf path of
    update_fused are exercised. The bucket rides the two-level schedule,
    which pads and scatters a flat buffer, so even with one member it keeps
    the flat layout (a one-member ring bucket would be shaped: see
    ``_mixed_plan``)."""
    b = Bucket(key=("allreduce", "float32", ()), idx=(0,), sizes=(32,),
               nbytes=32 * 4, schedule="two_level")
    return BucketPlan(buckets=[b], batch_axes=("data",), replicas=1,
                      n_params=1, wire_bytes=b.nbytes, bucket_bytes=1 << 20)


def _assert_states_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_bucket_segments_layout():
    bp = _bucket_plan()
    assert bucket_segments(bp) == {0: (0, 0, 32)}


def test_fuse_unfuse_roundtrip_exact():
    opt = adamw(1e-2, ema_decay=0.5, clip_norm=None)
    state = opt.init(_params())
    bp = _bucket_plan()
    fused = fuse_state(state, bp)
    assert is_fused(fused) and not is_fused(state)
    # bucketed leaf positions hold no buffer in the fused layout
    assert fused.m["leaf"]["a"] is None
    assert fused.m["leaf"]["b"]["w"] is not None
    _assert_states_equal(state, unfuse_state(fused, bp))


@pytest.mark.parametrize("make_opt", [
    lambda: adamw(1e-2, b1=0.9, b2=0.95, weight_decay=0.1, clip_norm=1.0,
                  ema_decay=0.9),
    lambda: adamw(1e-2, weight_decay=0.0, clip_norm=None, ema_decay=0.0),
    lambda: momentum(1e-1, mu=0.9, clip_norm=1.0, ema_decay=0.5),
])
def test_fused_update_bit_identical_f32(make_opt):
    """update_fused replays update's cast/reduce chain op for op: at f32 the
    two trajectories (params, moments, EMA, grad_norm) are bitwise equal
    over multiple steps, including clipping and weight decay."""
    opt = make_opt()
    bp = _bucket_plan()
    ref = opt.init(_params())
    fused = fuse_state(opt.init(_params()), bp)
    # jit both, as the train step does: on the CPU XLA canonicalizes the
    # reshape between a leaf and its flat bucket segment to a bitcast, so
    # the clip-norm reduction associates identically (eager dispatch would
    # differ at ULP). On the TPU that reshape of a tiled 2-D leaf is a
    # physical relayout, which is why a one-member ring bucket keeps its
    # leaf's shape instead (test_fused_update_bit_identical_shaped_bucket)
    upd = jax.jit(opt.update)
    upd_fused = jax.jit(lambda s, g, bufs: opt.update_fused(s, g, bufs, bp))
    for step in range(3):
        g = _grads(scale=0.5 + step)            # crosses the clip threshold
        bufs = [jnp.reshape(g["a"], (-1,)).astype(jnp.float32)]
        ref, m_ref = upd(ref, g)
        fused, m_fused = upd_fused(fused, g, bufs)
        _assert_states_equal(ref, unfuse_state(fused, bp))
        if "grad_norm" in m_ref:
            assert float(m_ref["grad_norm"]) == float(m_fused["grad_norm"])


def test_fused_wd_mask_segments():
    """A param-wise weight-decay mask becomes a per-bucket segment vector;
    fused and per-param agree bitwise under a non-uniform mask."""
    mask = {"a": 0.0, "b": {"w": 1.0}}
    opt = adamw(1e-2, weight_decay=0.2, clip_norm=None, wd_mask=mask)
    bp = _bucket_plan()
    ref, _ = opt.update(opt.init(_params()), _grads())
    fused = fuse_state(opt.init(_params()), bp)
    bufs = [jnp.reshape(_grads()["a"], (-1,)).astype(jnp.float32)]
    fused, _ = opt.update_fused(fused, _grads(), bufs, bp)
    _assert_states_equal(ref, unfuse_state(fused, bp))


# ---------------------------------------------------------------------------
# shaped buckets: a one-member ring bucket keeps its leaf's shape
# ---------------------------------------------------------------------------

def _mixed_params():
    k = jax.random.key(3)
    shapes = {"a": (4, 8), "b": {"w": (8,)}, "c": (3,), "d": (2, 5)}
    leaves, tdef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(
        x, tuple))
    return jax.tree.unflatten(tdef, [
        jax.random.normal(jax.random.fold_in(k, i), s, jnp.float32)
        for i, s in enumerate(leaves)])


def _mixed_grads(scale=1.0):
    return jax.tree.map(
        lambda p: scale * jax.random.normal(jax.random.key(p.size), p.shape,
                                            jnp.float32), _mixed_params())


def _mixed_plan(shaped=True):
    """Leaves a (4, 8), b/w (8,), c (3,), d (2, 5), flattened 0..3. Bucket
    0 holds the 2-D leaf 'a' alone: a shaped bucket on the ring schedule
    (flat under ``shaped=False``, which puts it on the two-level schedule).
    Bucket 1 holds 'd' and 'b/w', flat in reverse-topological member order;
    'c' stays unbucketed."""
    one = Bucket(key=("allreduce", "float32", ()), idx=(0,), sizes=(32,),
                 nbytes=32 * 4, schedule="ring" if shaped else "two_level")
    two = Bucket(key=("allreduce", "float32", ()), idx=(3, 1),
                 sizes=(10, 8), nbytes=18 * 4)
    return BucketPlan(buckets=[one, two], batch_axes=("data",), replicas=1,
                      n_params=3, wire_bytes=one.nbytes + two.nbytes,
                      bucket_bytes=64)


def _exchanged_bufs(bp, grads):
    """What the bucketed exchange hands the fused apply: a shaped bucket's
    exchanged leaf itself, else the flat concatenation of its members."""
    g = jax.tree.leaves(grads)
    return [g[b.idx[0]] if b.shaped else jnp.concatenate(
        [g[i].reshape(-1) for i in b.idx]).astype(jnp.float32)
        for b in bp.buckets]


def test_fuse_state_keeps_shaped_bucket_in_leaf_shape():
    opt = adamw(1e-2, ema_decay=0.5, clip_norm=None)
    bp = _mixed_plan()
    assert [b.shaped for b in bp.buckets] == [True, False]
    fused = fuse_state(opt.init(_mixed_params()), bp)
    for tree in (fused.m, fused.v, fused.ema):
        assert tree["bucket"][0].shape == (4, 8)     # the leaf's own shape
        assert tree["bucket"][0].dtype == jnp.float32
        assert tree["bucket"][1].shape == (18,)      # flat concatenation
    # the flat variant of the same plan lays the one-member bucket flat
    flat = fuse_state(opt.init(_mixed_params()), _mixed_plan(shaped=False))
    assert flat.m["bucket"][0].shape == (32,)


@pytest.mark.parametrize("shaped", [True, False])
def test_fuse_unfuse_roundtrip_exact_mixed_plan(shaped):
    opt = adamw(1e-2, ema_decay=0.5, clip_norm=None)
    state = opt.init(_mixed_params())
    # non-zero moments, so a misplaced segment cannot pass as zeros
    state = state._replace(m=_mixed_grads(2.0), v=_mixed_grads(3.0))
    bp = _mixed_plan(shaped)
    fused = fuse_state(state, bp)
    assert fused.m["leaf"]["a"] is None and fused.m["leaf"]["d"] is None
    assert fused.m["leaf"]["c"] is not None
    _assert_states_equal(state, unfuse_state(fused, bp))


@pytest.mark.parametrize("make_opt", [
    lambda: adamw(1e-2, b1=0.9, b2=0.95, weight_decay=0.1, clip_norm=1.0,
                  ema_decay=0.9),
    lambda: adamw(1e-2, weight_decay=0.0, clip_norm=None, ema_decay=0.0),
    lambda: momentum(1e-1, mu=0.9, clip_norm=1.0, ema_decay=0.5),
    lambda: adamw(1e-2, weight_decay=0.2, clip_norm=1.0, wd_mask={
        "a": 0.5, "b": {"w": 1.0}, "c": 1.0, "d": 0.0}),
])
def test_fused_update_bit_identical_shaped_bucket(make_opt):
    """A shaped bucket's chain is the per-leaf chain: over 3 steps the
    fused apply on a plan with a shaped 2-D bucket and a two-member flat
    bucket is bitwise equal to the per-param ``update`` and to the fused
    apply with that bucket laid flat (params, moments, EMA, grad_norm)."""
    opt = make_opt()
    plans = {"shaped": _mixed_plan(), "flat": _mixed_plan(shaped=False)}
    ref = opt.init(_mixed_params())
    fused = {k: fuse_state(opt.init(_mixed_params()), bp)
             for k, bp in plans.items()}
    upd = jax.jit(opt.update)
    upd_fused = {k: jax.jit(lambda s, g, bufs, bp=bp: opt.update_fused(
        s, g, bufs, bp)) for k, bp in plans.items()}
    for step in range(3):
        g = _mixed_grads(scale=0.5 + step)      # crosses the clip threshold
        ref, m_ref = upd(ref, g)
        for k, bp in plans.items():
            fused[k], m_fused = upd_fused[k](fused[k], g,
                                             _exchanged_bufs(bp, g))
            _assert_states_equal(ref, unfuse_state(fused[k], bp))
            if "grad_norm" in m_ref:
                assert float(m_ref["grad_norm"]) == \
                    float(m_fused["grad_norm"])
    assert fused["shaped"].m["bucket"][0].shape == (4, 8)


def test_sgd_has_no_fused_path():
    assert sgd(0.1).update_fused is None
    # and a stateless sgd state never reads as fused
    assert not is_fused(sgd(0.1).init(_params()))
