"""The comparison that decides ``correct``: the program's first training
steps against the plain reference, started from the same seeded weights
on the same batches.

Three numbers, each held to a limit of its own (set per cell in
``workloads/<cell>.json`` from measured readings; see PERF.md):

  loss_gap    the largest |program loss - reference loss| over the steps,
              as a share of the reference loss;
  grad_gap    over leaves, the largest gap between the norms of the first
              (clipped) gradient, the program's read from its first Adam
              moment, over the larger of the reference leaf's norm and the
              median leaf's;
  change_gap  the same for the norm of each leaf's change over the steps.
              Leaves whose reference gradient is under a thousandth of the
              median leaf's move by round-off alone and are left out.
"""
from __future__ import annotations

import statistics

NEGLIGIBLE = 1e-3


def _gaps(prog, ref, keep) -> list:
    kept = [r for r, k in zip(ref, keep) if k]
    med = statistics.median(kept) if kept else 0.0
    return [abs(p - r) / max(r, med, 1e-30) if k else 0.0
            for p, r, k in zip(prog, ref, keep)]


def _worst(prog, ref, keep) -> float:
    return max(_gaps(prog, ref, keep))


def _moving(ref) -> list:
    gmed = statistics.median(ref["grad_norms"])
    return [g >= NEGLIGIBLE * gmed for g in ref["grad_norms"]]


def loss_gaps(prog: dict, ref: dict) -> list:
    return [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                ref["losses"])]


def detail(prog: dict, ref: dict, names: list) -> dict:
    """Where each number comes from: the gap of every step's loss and the
    leaf with the widest gradient and change gaps."""
    g = _gaps(prog["grad_norms"], ref["grad_norms"],
              [True] * len(names))
    c = _gaps(prog["change_norms"], ref["change_norms"], _moving(ref))
    return {"loss_gaps": loss_gaps(prog, ref),
            "grad_leaf": names[g.index(max(g))],
            "change_leaf": names[c.index(max(c))]}


def compare(prog: dict, ref: dict, loss_steps: int = 0) -> dict:
    """``prog`` and ``ref`` hold ``losses``, ``grad_norms`` and
    ``change_norms`` (per leaf, in one order). ``loss_steps`` > 0 compares
    the losses of that many first steps only. -> {number: value}."""
    n = len(ref["grad_norms"])
    gaps = loss_gaps(prog, ref)
    return {
        "loss_gap": max(gaps[:loss_steps] if loss_steps else gaps),
        "grad_gap": _worst(prog["grad_norms"], ref["grad_norms"], [True] * n),
        "change_gap": _worst(prog["change_norms"], ref["change_norms"],
                             _moving(ref)),
    }


def judge(numbers: dict, limits: dict) -> tuple:
    """-> (correct, {number: {"value", "limit"}}) over the numbers that
    have a limit. A number that is not finite fails."""
    out, ok = {}, True
    for k, lim in limits.items():
        v = numbers[k]
        out[k] = {"value": v, "limit": lim}
        if not (v == v and abs(v) != float("inf") and v <= lim):
            ok = False
    return ok, out
