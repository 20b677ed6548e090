"""Peak device memory in use, on the fullest of the cell's devices, read
from ``memory_stats()["peak_bytes_in_use"]`` after the window."""


def read(rec):
    peak = rec.get("peak_bytes")
    return None if not peak else peak / 1e9
