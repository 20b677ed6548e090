"""Operand bytes of the collectives in the compiled step, per device and
per step, from the step's HLO (lib/hlo.py). A count: it repeats exactly."""


def read(rec):
    coll = rec.get("collectives")
    if not coll or not coll["count"]:
        return None
    return coll["total_bytes"] / 1e6
