"""The copied traffic generator yields the program's batches."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from lib.registry import load_module  # noqa: E402

from repro.data.pipeline import SyntheticLM  # noqa: E402

gen = load_module(os.path.join(BENCH, "traffic", "generator.py"), "traffic")


@pytest.mark.parametrize("config,mix", [
    ("parallax_lm", "lm_b32_seq20_zipf1.3"),
    ("parallax_lm", "lm_b96_seq20_zipf1.3"),
    ("parallax_nmt", "nmt_b128_seq50_zipf1.3"),
])
def test_traffic_matches_the_program_pipeline(config, mix):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        model = json.load(f)["model"]
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        m = json.load(f)
    seed = 2 ** 33 + 17
    enc = model["is_encdec"]
    ours = gen.Traffic(m, model["vocab_size"], seed, enc)
    theirs = SyntheticLM(model["vocab_size"], m["seq_len"], m["global_batch"],
                         seed=seed, zipf_a=m["zipf_a"], is_encdec=enc,
                         src_zipf_a=m.get("src_zipf_a"))
    for step in (0, 1, 7):
        a, b = ours.batch(step), theirs.batch(step)
        assert sorted(a) == sorted(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    assert len(ours.seconds) == 3
    assert ours.target_tokens == m["global_batch"] * m["seq_len"]
