"""The main path's kernels and the parallax-lm train step, compiled for a
described TPU v5e (no chip attached): what the TPU compiler refuses fails
here, where interpret mode would pass.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, so the test workers that do
not run this file must not touch it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import embed_gather, embed_scatter, flash_attention

V, E, N = 800000, 512, 640          # parallax-lm: vocab x embedding, 32x20
HBM_BYTES = 16 * 2 ** 30            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_embed_gather_compiles(one_chip, dtype):
    c = jax.jit(lambda t, i: embed_gather.embed_gather(t, i, 0)).lower(
        _sds((V, E), dtype, one_chip), _sds((N,), jnp.int32, one_chip)
    ).compile()
    _assert_kernel(c)
    # the table is read in place: no relayout copy of the (V, E) array
    assert c.memory_analysis().temp_size_in_bytes < V * E // 8


def test_embed_scatter_add_compiles(one_chip):
    c = jax.jit(lambda i, r: embed_scatter.embed_scatter_add(i, r, V)).lower(
        _sds((N,), jnp.int32, one_chip), _sds((N, E), jnp.bfloat16, one_chip)
    ).compile()
    _assert_kernel(c)
    # the f32 gradient is written into the aliased zeros buffer itself
    assert c.memory_analysis().temp_size_in_bytes < V * E // 8


def test_flash_attention_compiles(one_chip):
    q = _sds((1, 4096, 8, 128), jnp.bfloat16, one_chip)
    c = jax.jit(lambda q, k, v: flash_attention.flash_attention(q, k, v)
                ).lower(q, q, q).compile()
    _assert_kernel(c)


def test_parallax_lm_step_fits_one_chip(one_chip):
    """The default-RunConfig parallax-lm step at seq 20, batch 32 — the
    chip_smoke.py training phase — compiles for one v5e and fits its HBM."""
    from repro.configs import RunConfig, ShapeConfig, get_config
    from repro.core.runtime import Runtime
    from repro.core.transform import analyze, make_train_step
    from repro.models.model import build_model
    from repro.optim.optimizer import make_optimizer

    rt = Runtime(get_config("parallax-lm"), RunConfig(),
                 ShapeConfig("smoke", 20, 32, "train"))
    model = build_model(rt.model_cfg, rt)
    rt.plan = plan = analyze(model, rt)
    opt = make_optimizer(rt)
    state = jax.eval_shape(lambda: opt.init(model.init(jax.random.key(0))))
    place = lambda a: _sds(a.shape, a.dtype, one_chip)
    state = jax.tree.map(place, state)
    batch = jax.tree.map(place, model.input_specs())
    c = jax.jit(make_train_step(model, opt, rt, plan), donate_argnums=0
                ).lower(state, batch).compile()
    ma = c.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, (total, ma)
