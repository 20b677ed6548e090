"""Entry-point set-up: where the compile cache goes, and the training
launcher driven in-process (as chip_smoke.py drives it)."""
import math
import os

import jax

from repro.launch import compile_cache


def test_compile_cache_dir_rule(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: JAX's own setting, nothing is set here
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        # unset: the fixed directory in the checkout, which git ignores
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        root = compile_cache.REPO_ROOT
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert os.path.exists(os.path.join(root, "chip_smoke.py"))
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_train_launcher_runs_in_process(monkeypatch):
    from repro.launch import train
    # leave this process's compile cache as it was
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       jax.config.jax_compilation_cache_dir or "unset")
    trainer, history = train.main(
        ["--arch", "parallax-lm", "--reduced", "--seq", "8", "--batch", "4",
         "--steps", "3", "--log-every", "1"])
    assert [h["step"] for h in history] == [1, 2, 3]
    assert trainer.step == 3
    assert all(math.isfinite(h["loss"]) and h["wall_s"] > 0
               for h in history)
