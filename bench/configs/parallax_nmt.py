"""parallax-nmt: the paper's GNMT-style translation model (GNMT, Wu et al.
2016, tensorflow/nmt ``wmt16_gnmt_4_layer``): 4 encoder and 4 decoder LSTM
layers of 1024 units (each projected to 1024), two 36548-row embeddings,
a dot attention over the encoder states and a 36548-way softmax head.
Sizes, run settings and the departures from GNMT are in
``parallax_nmt.json``.

The counts and the reference are those of the LSTM family
(``parallax_lm.py`` explains them): per target token, the encoder's work
on one source token is included, since every pair has as many source as
target tokens, and so is the attention's 2 x 50 x 1024 multiply-adds.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import lstm_ref  # noqa: E402
from lib.registry import load_module  # noqa: E402

_lm = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "parallax_lm.py"), "config_parallax_lm")

model_flops_per_token = _lm.model_flops_per_token
least_step_bytes = _lm.least_step_bytes
dense_params = _lm.dense_params
table_params = _lm.table_params
make_init = lstm_ref.make_init
make_change_norms = lstm_ref.make_change_norms
reference = lstm_ref.run
