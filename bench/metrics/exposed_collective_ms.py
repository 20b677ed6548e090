"""Device time per step in which a collective ran and no other operation
ran on that device, mean over the cell's devices (lib/trace.py)."""


def read(rec):
    steps, tr = rec.get("trace_steps"), rec["trace"]
    if not steps or not tr["collective_s"]:
        return None
    return 1e3 * tr["exposed_collective_s"] / steps
