"""A whole run of the harness, past its look for a chip, at a size the
CPU holds: a sound program comes out correct, and each fault that a
training cell can have, planted underneath the timed path, comes out not
correct; so does the control, the reference one precision below the
configuration's put in the program's place."""
import os
import subprocess
import sys
import textwrap

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)
sys.path.insert(0, TESTS)
import run  # noqa: E402
from benchtree import make_tree  # noqa: E402
from readings import LOWER  # noqa: E402
from lib.registry import Registry  # noqa: E402


def _unchanged(trainer):
    """A step that returns its state unchanged."""
    import jax
    import jax.numpy as jnp
    step = trainer.train_step

    def broken(state, batch):
        old = jax.tree.map(jnp.copy, state)
        return old, step(state, batch)[1]

    trainer.train_step = broken


def _half_batch(trainer):
    """Half of the batch left out: the mean is over the first half (its
    rows fill both halves, so the step's shapes stay)."""
    import numpy as np
    step = trainer.train_step

    def broken(state, batch):
        half = {k: np.concatenate([v[: len(v) // 2]] * 2)
                for k, v in batch.items()}
        return step(state, half)

    trainer.train_step = broken


def _plant_control(monkeypatch):
    """The program's readings of its checked steps replaced by those of the
    reference at the next precision below the configuration's, on the same
    seeded weights and batches; the trainer still warms up as in any run."""
    import jax.numpy as jnp
    sound = run.setup_steps

    def control(trainer, cmod, cfg, key_seed):
        sound(trainer, cmod, cfg, key_seed)
        batches = [trainer.dataset.draw(s) for s in range(run.CHECKED_STEPS)]
        optim = cfg["run_config"]
        return cmod.reference(cfg["model"], optim, key_seed, batches,
                              dtype=jnp.dtype(LOWER[optim["param_dtype"]]))

    monkeypatch.setattr(run, "setup_steps", control)


def _run(root, cell, hook=None):
    args = run._parse(["--workload", cell, "--seed", str(2 ** 33 + 5),
                       "--seconds", "0.5", "--trace", "0"])
    result, lines = run.run_cell(args, Registry(root), require_tpu=False,
                                 hook=hook)
    return result


@pytest.mark.parametrize("base", ["parallax_lm", "parallax_nmt"])
@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "control"])
def test_one_chip_faults_fail_the_check(tmp_path, monkeypatch, base, fault):
    root = make_tree(str(tmp_path), base=base)
    if fault == "control":
        _plant_control(monkeypatch)
    hook = {"unchanged": _unchanged, "half_batch": _half_batch}.get(fault)
    result = _run(root, "tiny-cell", hook)
    assert result["correct"] is (fault is None), result["check"]
    assert result["attempted"] > 0
    assert list(result)[-1] == "check"


SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {bench!r})
    import jax
    import run
    from lib.registry import Registry
    from repro.core import buckets

    def no_exchange(trainer):
        # the dense gradient buckets' all-reduce left out: each chip
        # applies its own gradient
        orig = buckets._exchange_bucket

        def local(*a, **k):
            psum = jax.lax.psum
            jax.lax.psum = lambda x, *_, **__: x
            try:
                return orig(*a, **k)
            finally:
                jax.lax.psum = psum

        buckets._exchange_bucket = local

    out = {{}}
    for name, hook in (("sound", None), ("no_exchange", no_exchange)):
        args = run._parse(["--workload", "tiny-dp", "--seed", "77",
                           "--seconds", "0.5", "--trace", "0"])
        res, _ = run.run_cell(args, Registry({root!r}), require_tpu=False,
                              hook=hook)
        out[name] = res["correct"]
    print(json.dumps(out))
""")


def test_four_chip_exchange_left_out_fails_the_check(tmp_path):
    root = make_tree(str(tmp_path), mesh=[4, 1], cell="tiny-dp")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(bench=BENCH, root=root)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1] == \
        '{"sound": true, "no_exchange": false}', p.stderr[-3000:]
