"""The configurations' count functions against hand counts."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from lib.registry import load_module  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        sizes = json.load(f)["model"]
    return sizes, load_module(os.path.join(BENCH, "configs", name + ".py"),
                              "config_" + name)


def test_parallax_lm_counts():
    m, mod = _config("parallax_lm")
    mix = {"seq_len": 20, "global_batch": 32}
    # multiply-adds per token: LSTM input 512x8192, recurrent 512x8192,
    # projection 2048x512; head 512x800000
    macs = 512 * 8192 + 512 * 8192 + 2048 * 512 + 512 * 800000
    assert mod.model_flops_per_token(m, mix) == 6 * macs
    assert mod.model_flops_per_token(m, mix) * 640 == pytest.approx(
        1.609e12, rel=1e-3)
    # dense: head 409.6M + LSTM (2 x 512x8192 + 8192 + 2048x512)
    dense = 800000 * 512 + 2 * 512 * 8192 + 8192 + 2048 * 512
    # under AdamW every parameter, the embedding's 800000x512 too: bf16
    # read+write (4 B) and two f32 moments read+write (16 B)
    assert mod.table_params(m) == 800000 * 512
    adamw = {"optimizer": "adamw", "param_dtype": "bfloat16"}
    assert mod.least_step_bytes(m, adamw) == (dense + 800000 * 512) * 20
    assert mod.least_step_bytes(m, adamw) == pytest.approx(16.57e9,
                                                           rel=1e-3)


def test_parallax_nmt_counts():
    m, mod = _config("parallax_nmt")
    mix = {"seq_len": 50, "global_batch": 128}
    cell = 1024 * 4096 * 2 + 1024 * 1024
    macs = 4 * cell + 4 * cell + 2 * 50 * 1024 + 2048 * 1024 + 1024 * 36548
    assert mod.model_flops_per_token(m, mix) == 6 * macs
    assert mod.model_flops_per_token(m, mix) == pytest.approx(690.7e6,
                                                              rel=1e-3)
    dense = 8 * (cell + 4096) + 2048 * 1024 + 36548 * 1024
    assert mod.dense_params(m) == dense
    # both 36548x1024 tables and every dense parameter, each with its two
    # f32 moments: 20 B in bf16, 24 B in f32
    assert mod.table_params(m) == 2 * 36548 * 1024
    for dtype, per in (("bfloat16", 20), ("float32", 24)):
        adamw = {"optimizer": "adamw", "param_dtype": dtype}
        assert mod.least_step_bytes(m, adamw) == \
            (dense + 2 * 36548 * 1024) * per
