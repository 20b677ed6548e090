"""parallax-lm: the paper's LM (Jozefowicz et al. 2016, TF ``lm_1b``):
an 800000-word embedding of width 512, one LSTM of 2048 units projected to
512, and an 800000-way softmax head. Sizes and run settings are in
``parallax_lm.json``; this file holds what the benchmark computes from
them, and its plain reference.

Counts, from the published shapes only:

``model_flops_per_token``: forward and backward (3x the forward's
2 x multiply-adds) of every matrix product one target token needs: the
LSTM's input, recurrent and projection matrices and the head's full
800000 x 512 product. The embedding gather, the gates' elementwise work
and any recomputation are not counted.

``least_step_bytes``: what a correct step of the configuration's optimizer
cannot avoid moving through HBM, at the configuration's dtypes (its
``param_dtype``, f32 moments). Under AdamW every parameter of every table,
sparse or dense, is read and written once, and so are its two moments:
a row that no token touched has a zero gradient, but its moments still
decay and it still moves, so an update that skips it is another
optimizer, not a cheaper step.
"""
from __future__ import annotations

import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import lstm_ref  # noqa: E402

MOMENT_BYTES = 4     # f32, two moments


def _cell_macs(d_in: int, hidden: int, proj: int) -> int:
    return d_in * 4 * hidden + proj * 4 * hidden + hidden * proj


def dense_params(m: dict) -> int:
    d, h, v = m["d_model"], m["d_ff"], m["vocab_size"]
    cell = 2 * d * 4 * h + 4 * h + h * d
    n = m["n_layers"] * cell + v * d
    if m.get("is_encdec"):
        n += m["enc_layers"] * cell + 2 * d * d
    return n


def model_flops_per_token(m: dict, mix: dict) -> float:
    d, h, v = m["d_model"], m["d_ff"], m["vocab_size"]
    macs = m["n_layers"] * _cell_macs(d, h, d) + d * v
    if m.get("is_encdec"):
        s = mix["seq_len"]
        macs += m["enc_layers"] * _cell_macs(d, h, d)   # one source token
        macs += 2 * s * d                                # scores and context
        macs += 2 * d * d                                # attention mix
    return 6.0 * macs


def table_params(m: dict) -> int:
    """Parameters of the sparse embedding tables."""
    return (2 if m.get("is_encdec") else 1) * m["vocab_size"] * m["d_model"]


def least_step_bytes(m: dict, optim: dict) -> float:
    param_bytes = {"bfloat16": 2, "float32": 4}[optim["param_dtype"]]
    if optim["optimizer"] == "adamw":
        return float((dense_params(m) + table_params(m))
                     * 2 * (param_bytes + 2 * MOMENT_BYTES))
    raise ValueError(f"no byte count for {optim['optimizer']!r}")


make_init = lstm_ref.make_init
make_change_norms = lstm_ref.make_change_norms
reference = lstm_ref.run
