"""moonlight-16b-a3b: Moonlight-16B-A3B (moonshotai, ``deepseek_v3``
config), one chip's share of an 8-chip expert-parallel deployment: the
dense layer and 5 MoE layers, 8 of the 64 routed experts, a 20480-row
vocabulary slice, every width as published. Sizes, run settings and the
departures are in ``moonlight_16b_a3b.json``; the plain reference is
``lib/moe_ref.py``.

Counts, from the shapes of the chip's share:

``model_flops_per_token``: forward and backward (3x the forward's
2 x multiply-adds) of every matrix product one token needs: each layer's
latent attention projections, its attention core over a mean causal
prefix of (S + 1) / 2 positions (scores at nope + rope dims, values at
v dims), the dense layer's MLP, each MoE layer's router, its shared
experts and the expected share of its routed experts that are held here
(experts_per_token x experts_held / n_experts expert MLPs a token), and
the head over the vocabulary slice. Recomputation is not counted.

``least_step_bytes``: as ``parallax_lm.py``: under AdamW every parameter
and both of its f32 moments are read and written once.

``grouped_flops``: the held experts' grouped products for a number of
routed rows, forward and backward, for a reader of their roofline.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import moe_ref  # noqa: E402

MOMENT_BYTES = 4     # f32, two moments


def _mla_macs(m: dict) -> int:
    d, h, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rd, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["v_head_dim"]
    return (d * h * (nope + rd) + d * (r + rd) + r * h * (nope + vd)
            + h * vd * d)


def _mlp_macs(d: int, f: int) -> int:
    return 3 * d * f


def params(m: dict) -> int:
    """Parameters held on the chip."""
    d, held = m["d_model"], m["experts_held"]
    n_dense = m["first_k_dense"]
    n_moe = m["n_layers"] - n_dense
    norms = 2 * d + m["kv_lora_rank"]
    dense = _mla_macs(m) + _mlp_macs(d, m["dense_d_ff"]) + norms
    moe = (_mla_macs(m) + held * _mlp_macs(d, m["d_ff"])
           + _mlp_macs(d, m["shared_d_ff"]) + d * m["n_experts"] + norms)
    return n_dense * dense + n_moe * moe + 2 * m["vocab_size"] * d + d


def model_flops_per_token(m: dict, mix: dict) -> float:
    d, s = m["d_model"], mix["seq_len"]
    n_dense = m["first_k_dense"]
    n_moe = m["n_layers"] - n_dense
    attn = m["n_heads"] * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
                           + m["v_head_dim"]) * (s + 1) / 2
    routed = (m["experts_per_token"] * m["experts_held"] / m["n_experts"]
              * _mlp_macs(d, m["d_ff"]))
    macs = (m["n_layers"] * (_mla_macs(m) + attn)
            + n_dense * _mlp_macs(d, m["dense_d_ff"])
            + n_moe * (d * m["n_experts"] + routed
                       + _mlp_macs(d, m["shared_d_ff"]))
            + m["vocab_size"] * d)
    return 6.0 * macs


def least_step_bytes(m: dict, optim: dict) -> float:
    param_bytes = {"bfloat16": 2, "float32": 4}[optim["param_dtype"]]
    if optim["optimizer"] == "adamw":
        return float(params(m) * 2 * (param_bytes + 2 * MOMENT_BYTES))
    raise ValueError(f"no byte count for {optim['optimizer']!r}")


def grouped_flops(m: dict, rows: int) -> float:
    """FLOPs of the held experts' grouped products over ``rows`` routed
    (token, slot) pairs, forward and backward."""
    return 6.0 * rows * _mlp_macs(m["d_model"], m["d_ff"])


make_init = moe_ref.make_init
make_change_norms = moe_ref.make_change_norms
reference = moe_ref.run
