"""moonlight-16b-a3b (DeepSeek-V3 block) against the benchmark's plain
float32 reference (bench/lib/moe_ref.py), at a tiny size on the CPU:
latent attention, the held-experts MoE layer (one share of an
expert-parallel layer, dropless), the whole model through the normal
Trainer path, and the memory bound of the blocked attention core."""
import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from lib import check, moe_ref  # noqa: E402
from lib.registry import load_module  # noqa: E402

from repro.configs import RunConfig, ShapeConfig, get_config  # noqa: E402
from repro.core.runtime import Runtime  # noqa: E402
from repro.models import attention as attn_mod  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models import transformer  # noqa: E402

# d 64, 4 heads, latent 32, nope 16, rope 8, v 16; 8 experts of width 32,
# 2 held, top-3; one dense layer and two MoE layers; vocab 512
SIZES = {"vocab_size": 512, "d_model": 64, "n_layers": 3, "first_k_dense": 1,
         "dense_d_ff": 128, "d_ff": 32, "n_heads": 4, "n_kv_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "n_experts": 8, "experts_held": 2,
         "experts_per_token": 3, "routed_scale": 2.446, "shared_expert": True, "shared_d_ff": 64,
         "rope_theta": 50000.0, "norm_eps": 1e-05, "tie_embeddings": False}
F32 = dict(param_dtype="float32", compute_dtype="float32",
           wire_dtype="float32", remat="block", attention_impl="chunked",
           attention_chunk=8)
MM, EIN = moe_ref._mm(jnp.float32), moe_ref._ein(jnp.float32)


def _cfg(**over):
    return dataclasses.replace(get_config("moonlight-16b-a3b"),
                               **{**SIZES, **over})


def _rt(cfg, seq=16, batch=2):
    return Runtime(cfg, RunConfig(**F32), ShapeConfig("t", seq, batch,
                                                      "train"))


def _params(sizes=SIZES, seed=0):
    return moe_ref.make_init(sizes, jnp.float32)(jax.random.key(seed))


def _x(shape, seed=1):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


def test_mla_forward_equals_reference():
    # f32 on both sides, the same weights; the program's blocked core and
    # the reference's query blocks sum in other orders: 1e-5 of round-off
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _params()["layers"]["attn"])
    x = _x((2, 16, 64))
    got = transformer.mla_block(p, x, cfg=cfg, rt=_rt(cfg),
                                positions=jnp.arange(16))
    want = moe_ref.mla(p, x, SIZES, MM, EIN)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _moe_params(seed=0):
    return jax.tree.map(lambda a: a[0], _params(seed=seed)["layers"]["moe"])


def test_four_shares_add_up_to_the_uncut_layer():
    # each 2-expert share computes its experts' part for the tokens routed
    # to them; the shares' held parts summed, with the shared experts
    # counted once, are the whole 8-expert layer of the reference
    whole = {**SIZES, "experts_held": 8}
    p_all = jax.tree.map(lambda a: a[0],
                         _params(whole)["layers"]["moe"])
    x = _x((2, 16, 64))
    cfg = _cfg(shared_expert=False)
    rt = _rt(cfg)
    total = 0.0
    for s in range(4):
        share = {k: (v[2 * s:2 * s + 2] if k.startswith("w_") else v)
                 for k, v in p_all.items()}
        out, m = moe_mod.held_moe_ffn(share, x, cfg=cfg, rt=rt,
                                      first_expert=2 * s)
        assert int(m["moe_dropped"]) == 0
        total = total + out
    shared = moe_ref._swiglu(x, p_all["shared_gate"], p_all["shared_up"],
                             p_all["shared_down"], MM)
    want = moe_ref.moe(p_all, x.reshape(32, 64), whole, MM).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dropless_under_forced_imbalance():
    # every token's top-3 is experts 0, 1, 2, so both held experts see
    # every token: a capacity of 1.25 x the mean would drop most of them
    p = _moe_params()
    router = jnp.zeros((64, 8)).at[0, :3].set(jnp.array([3.0, 2.5, 2.0]))
    p = {**p, "router": router}
    x = _x((2, 16, 64)).at[..., 0].set(10.0)
    cfg = _cfg()
    out, m = moe_mod.held_moe_ffn(p, x, cfg=cfg, rt=_rt(cfg))
    flat = x.reshape(32, 64)
    _, ids = moe_ref.route(p, flat, SIZES, MM)
    assert int(m["moe_dropped"]) == 0
    assert int(m["moe_held_rows"]) == int(jnp.sum(ids < 2)) == 64
    assert float(m["moe_load_max"]) == 1.0
    want = moe_ref.moe(p, flat, SIZES, MM).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_load_counters_follow_the_routing():
    p = _moe_params(seed=3)
    x = _x((2, 16, 64), seed=4)
    cfg = _cfg()
    _, m = moe_mod.held_moe_ffn(p, x, cfg=cfg, rt=_rt(cfg))
    _, ids = moe_ref.route(p, x.reshape(32, 64), SIZES, MM)
    per = [int(jnp.sum(ids == e)) for e in range(2)]
    assert int(m["moe_held_rows"]) == sum(per)
    np.testing.assert_allclose(float(m["moe_load_max"]),
                               max(per) / (sum(per) / 2), rtol=1e-6)


def _trainer_against_reference(**run_over):
    cfg = {"arch": "moonlight-16b-a3b", "model": SIZES,
           "run_config": {**F32, "optimizer": "adamw",
                          "learning_rate": 1e-3, "weight_decay": 0.0,
                          "clip_norm": 1.0, **run_over}}
    mix = {"seq_len": 16, "global_batch": 2, "zipf_a": 1.1}
    cmod = load_module(os.path.join(BENCH, "configs", "moonlight_16b_a3b.py"),
                       "config_moonlight_16b_a3b")
    gen = load_module(os.path.join(BENCH, "traffic", "generator.py"),
                      "traffic")
    traffic = gen.Traffic(mix, 512, 2 ** 31 + 7, False)
    trainer, key_seed = bench_run.build({"name": "t", "mesh": None}, cfg,
                                        mix, 2 ** 31 + 7, cmod, traffic)
    prog = bench_run.setup_steps(trainer, cmod, cfg, key_seed)
    ref = cmod.reference(SIZES, cfg["run_config"], key_seed,
                         [traffic.draw(s) for s in range(3)])
    return prog, ref, check.compare(prog, ref)


def test_three_trainer_steps_equal_the_reference():
    """Loss, first gradient's and parameter change's per-leaf norms over 3
    AdamW steps, the program through bench/run.py's Trainer (as the
    benchmark drives it) against the reference, both in float32."""
    prog, ref, gaps = _trainer_against_reference()
    # f32 against f32: the losses agree to round-off; a leaf's gradient
    # norm to 1e-4 of the largest of it and the median leaf's; its change
    # (Adam divides by the root of the second moment, so round-off in a
    # small gradient shows more) to 1e-3
    assert gaps["loss_gap"] < 1e-5, (gaps, prog["losses"], ref["losses"])
    assert gaps["grad_gap"] < 1e-4, gaps
    assert gaps["change_gap"] < 1e-3, gaps
    assert len(prog["grad_norms"]) == len(ref["names"]) == 27


def test_the_cells_run_config_passes_its_check_at_a_tiny_size():
    """The benchmark cell's own run settings (float32 parameters under bf16
    matmuls, warmup) through the Trainer: the residual stream stays bf16
    (a float32 leaf would promote it and fail the layers' scan), and the
    three steps pass the cell's limits against the float32 reference."""
    cell = json.load(open(os.path.join(BENCH, "workloads",
                                       "moonlight-1chip-8k.json")))
    rc = json.load(open(os.path.join(BENCH, "configs",
                                     "moonlight_16b_a3b.json")))["run_config"]
    assert rc["param_dtype"] == "float32" and rc["warmup_steps"]
    prog, ref, gaps = _trainer_against_reference(
        **{**rc, "attention_chunk": 8})
    correct, _ = check.judge(gaps, cell["limits"])
    assert correct, (gaps, cell["limits"])


def test_blocked_attention_backward_holds_no_whole_score_array():
    """The compiled backward at S = 2048 holds no buffer with two dims of
    2048, and its gradients equal naive_attention's."""
    s = 2048
    q, k = _x((1, s, 2, 24), 1), _x((1, s, 2, 24), 2)
    v, w = _x((1, s, 2, 16), 3), _x((1, s, 2, 16), 4)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    blocked = loss(lambda q, k, v: attn_mod.blocked_attention(
        q, k, v, causal=True, block=512))
    text = jax.jit(jax.grad(blocked, (0, 1, 2))).lower(q, k, v).compile() \
        .as_text()
    shapes = re.findall(r"[a-z0-9]+\[([0-9,]+)\]", text)
    assert shapes
    assert not [d for d in shapes if d.split(",").count(str(s)) >= 2]
    got = jax.grad(blocked, (0, 1, 2))(q, k, v)
    want = jax.grad(loss(attn_mod.naive_attention), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_step_time_splits_by_model_scope():
    """Every op of the tiny model's compiled train step that lies in a
    model scope is found, forward and backward, for each of the four
    scopes; split_device_time adds up to what it was given."""
    from repro.core.transform import analyze, make_train_step
    from repro.models.model import build_model
    from repro.optim.optimizer import make_optimizer
    from repro.utils import hlo

    cfg = _cfg()
    rt = Runtime(cfg, RunConfig(attention_impl="chunked", attention_chunk=8,
                                remat="full"), ShapeConfig("t", 16, 2, "train"))
    model = build_model(cfg, rt)
    rt.plan = plan = analyze(model, rt)
    opt = make_optimizer(rt)
    state = jax.eval_shape(lambda: opt.init(model.init(jax.random.key(0))))
    text = jax.jit(make_train_step(model, opt, rt, plan)).lower(
        state, model.input_specs()).compile().as_text()
    ops = {name: 1.0 for name in hlo.step_phases(text)}
    split = hlo.split_device_time(ops, text)
    for scope in hlo.MODEL_SCOPES:
        assert split.get(f"{hlo.FORWARD}/{scope}", 0) > 0, (scope, split)
        assert split.get(f"{hlo.BACKWARD}/{scope}", 0) > 0, (scope, split)
    assert split.get(hlo.OPTIMIZER, 0) > 0
    assert sum(split.values()) == len(ops)
    assert hlo.model_scope("jit(f)/forward/attention/while/body/moe_route"
                           "/gather") == "moe_route"
    assert hlo.model_scope("jit(f)/transpose(jvp(forward))/bsd,vd->bsv") \
        is None


def test_benchmark_config_counts_the_programs_parameters():
    """The benchmark configuration's parameter count (669 M: the dense
    layer, 5 MoE layers of 8 held experts, a 20480-row vocabulary) is the
    program's, by jax.eval_shape of its init, and the reference's layout."""
    import json
    from repro.models.model import build_model

    with open(os.path.join(BENCH, "configs", "moonlight_16b_a3b.json")) as f:
        sizes = json.load(f)["model"]
    cmod = load_module(os.path.join(BENCH, "configs", "moonlight_16b_a3b.py"),
                       "config_moonlight_16b_a3b")
    cfg = _cfg(**sizes)
    model = build_model(cfg, _rt(cfg, seq=8192))
    prog = jax.eval_shape(model.init, jax.random.key(0))
    ref = jax.eval_shape(moe_ref.make_init(sizes, jnp.bfloat16),
                         jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(prog))
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    assert [a.shape for a in jax.tree.leaves(prog)] == \
        [a.shape for a in jax.tree.leaves(ref)]
    assert n == cmod.params(sizes) == 668_890_112


def test_rows_past_the_groups_reach_no_gradient(monkeypatch):
    """lax.ragged_dot leaves the rows past its last group undefined (the
    TPU's grouped kernel does not write them, forward or backward). With
    those rows poisoned with NaN in every product and in its input
    gradient, the layer's output and gradients are those of the CPU's
    zero-filling lowering."""
    orig = jax.lax.ragged_dot

    def poison(x, sizes):
        return jnp.where((jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None],
                         x, jnp.nan)

    @jax.custom_vjp
    def poisoned(a, w, sizes):
        return poison(orig(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        return poisoned(a, w, sizes), (a, w, sizes)

    def bwd(res, ct):
        a, w, sizes = res
        da, dw = jax.vjp(lambda a, w: orig(a, w, sizes), a, w)[1](ct)
        return poison(da, sizes), dw, None

    poisoned.defvjp(fwd, bwd)
    p, x = _moe_params(seed=5), _x((2, 16, 64), seed=6)
    cfg = _cfg()

    def loss(p, x):
        out, _ = moe_mod.held_moe_ffn(p, x, cfg=cfg, rt=_rt(cfg))
        return jnp.sum(out * jnp.cos(x))

    want = jax.grad(loss, (0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got = jax.grad(loss, (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
