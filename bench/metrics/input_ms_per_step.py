"""Host time of the traffic's ``batch()`` call that the trainer makes each
step, mean over the window's steps."""


def read(rec):
    secs = rec.get("input_s") or []
    return 1e3 * sum(secs) / len(secs) if secs else None
