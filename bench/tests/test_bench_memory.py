"""The check's own programs in set-up hold no second copy of the
parameters: the peak after set-up stays the program's state plus its
step's temporaries."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)
sys.path.insert(0, TESTS)
import run  # noqa: E402
from benchtree import make_tree  # noqa: E402
from lib import lstm_ref  # noqa: E402
from lib.registry import Registry  # noqa: E402

SIZES = {"vocab_size": 1000, "d_model": 64, "d_ff": 128, "n_layers": 1,
         "enc_layers": 1, "is_encdec": True}


def _moved(key):
    p = lstm_ref.make_init(SIZES, jnp.bfloat16)(key)
    return jax.tree.map(lambda a: (a.astype(jnp.float32) + 0.01)
                        .astype(jnp.bfloat16), p)


@pytest.mark.parametrize("block_bytes", [24 * 1024, 2 ** 25])
def test_change_norms_by_blocks_equal_those_of_a_whole_copy(block_bytes):
    # 24 KiB is 96 rows of 64 float32: 1000 rows leave a last block that is
    # shifted back over rows counted before
    key = jax.random.key(5)
    p = _moved(key)
    p0 = lstm_ref.make_init(SIZES, jnp.bfloat16)(key)
    want = [np.linalg.norm(np.asarray(a, np.float32)
                           - np.asarray(b, np.float32))
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))]
    got = lstm_ref.make_change_norms(SIZES, jnp.bfloat16, block_bytes)(p, key)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_change_norms_hold_a_block_not_a_leaf():
    # float32 leaves: the CPU compiler widens a bfloat16 leaf to float32
    # whole before slicing it, which the TPU compiler does not (for
    # parallax_lm's bfloat16 leaves on a described v5e: 0.7 MB of
    # temporaries against 1.66 GB of parameters)
    key = jax.random.key(5)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), _moved(key))
    block = 24 * 1024
    norms = lstm_ref.make_change_norms(SIZES, jnp.bfloat16, block)
    mem = norms.lower(p, key).compile().memory_analysis()
    leaf = 1000 * 64 * 4          # one table in float32
    assert mem.temp_size_in_bytes < leaf / 2, mem.temp_size_in_bytes


def test_set_up_programs_fit_inside_the_steps_temporaries(tmp_path):
    root = make_tree(str(tmp_path))
    reg = Registry(root)
    cell = reg.cell("tiny-cell")
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    cmod = reg.config_module(cell["config"])
    gen = run.load_module(os.path.join(reg.dir, "traffic", "generator.py"),
                          "traffic")
    traffic = gen.Traffic(mix, cfg["model"]["vocab_size"], 3, False)
    trainer, key_seed = run.build(cell, cfg, mix, 3, cmod, traffic)
    trainer.tcfg.total_steps = 1
    trainer.run()
    state = trainer.state
    step = trainer.train_step.lower(state, traffic.draw(1)).compile()
    step_temp = step.memory_analysis().temp_size_in_bytes
    change = cmod.make_change_norms(cfg["model"], jnp.bfloat16).lower(
        state.params, jax.random.key(key_seed)).compile()
    moments = run.moment_norms_fn(trainer).lower(state).compile()
    for prog in (change, moments):
        mem = prog.memory_analysis()
        assert mem.temp_size_in_bytes + mem.output_size_in_bytes \
            <= step_temp, (mem.temp_size_in_bytes, step_temp)
