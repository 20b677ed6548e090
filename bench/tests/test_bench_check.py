"""The comparison that decides ``correct`` (lib/check.py)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import check  # noqa: E402

REF = {"losses": [10.0, 8.0, 5.0], "grad_norms": [1.0, 2.0, 4.0, 1e-6],
       "change_norms": [0.1, 0.2, 0.4, 0.3]}


def test_numbers_are_relative_and_leave_out_unmoving_leaves():
    prog = {"losses": [10.0, 8.08, 5.0], "grad_norms": [1.0, 2.2, 4.0, 0.0],
            "change_norms": [0.1, 0.2, 0.44, 7.0]}
    n = check.compare(prog, REF)
    assert n["loss_gap"] == pytest.approx(0.01)
    # leaf 2: 0.2 over max(2.0, median 1.5); the tiny leaf's gap is over
    # the median leaf's norm
    assert n["grad_gap"] == pytest.approx(0.1)
    # the last leaf's reference gradient is under 1e-3 of the median: its
    # change (7.0 against 0.3) is round-off and is left out
    assert n["change_gap"] == pytest.approx(0.1)
    assert check.compare(prog, REF, loss_steps=1)["loss_gap"] == 0.0


def test_judge_holds_each_number_with_a_limit_to_it():
    ok, out = check.judge({"loss_gap": 0.5, "grad_gap": 0.1},
                          {"grad_gap": 0.2})
    assert ok and list(out) == ["grad_gap"]
    assert out["grad_gap"] == {"value": 0.1, "limit": 0.2}
    assert not check.judge({"grad_gap": 0.3}, {"grad_gap": 0.2})[0]
    assert not check.judge({"grad_gap": float("nan")}, {"grad_gap": 0.2})[0]


def test_detail_names_the_widest_leaves():
    prog = {"losses": [10.0, 8.0, 5.5], "grad_norms": [1.0, 2.0, 3.0, 0.0],
            "change_norms": [0.1, 0.3, 0.4, 0.3]}
    d = check.detail(prog, REF, ["a", "b", "c", "d"])
    assert d["loss_gaps"] == pytest.approx([0.0, 0.0, 0.1])
    assert d["grad_leaf"] == "c" and d["change_leaf"] == "b"
