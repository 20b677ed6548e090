"""Share of the traced window in which no operation ran on the device,
mean over the cell's devices (lib/trace.py)."""


def read(rec):
    share = rec["trace"]["idle_share"]
    return None if share is None else 100.0 * share
