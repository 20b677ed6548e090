"""The least time a step could take on one chip, over the device's busy
time per step: the larger of the step's model FLOPs over the bf16 peak and
its least bytes over the HBM bandwidth (both divided over the chips), over
busy seconds per step from the trace. The run prints which bound it is."""


def read(rec):
    steps, busy = rec.get("trace_steps"), rec["trace"]["busy_s"]
    if not steps or not busy:
        return None
    return 100.0 * rec["least_step_s"] / (busy / steps)
