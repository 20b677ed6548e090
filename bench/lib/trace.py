"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Device time is the union of the intervals in which an operation ran on a
device, inside the traced window; a device's idle share is one minus that
over the window. A collective's exposed time is the part of the union of
collective intervals during which no other operation ran on that device.
Idle gaps are named after the innermost host span that covers their
midpoint, so a gap says what the host was doing while the device waited.

Times are seconds. ``read_xplane`` turns the profiler's ``.xplane.pb`` into
plain lists, so the reduction itself is tested on synthetic events.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|allreduce|allgather|reducescatter")


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(name: str, limit: int = 96) -> str:
    """An op's trace name without layouts, cut to ``limit`` characters:
    the TPU trace names an op by its whole HLO instruction."""
    return " ".join(_LAYOUT.sub("", name).split())[:limit]


def op_kind(name: str) -> str:
    """The instruction name and opcode of a trace op name, without its
    operands: a TPU trace names an op by its HLO text, ``%x = <type>
    <opcode>(<operands>)``, and an operand may itself be a collective's
    result. A name without `` = `` is returned as it is."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return head + " " + rest.strip().partition("(")[0]


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(op_kind(name).lower().replace("_", "-")))


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into sorted, disjoint ones."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b) -> list:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    return subtract([(lo, hi)], merged)


def _label(spans, starts, t: float) -> str:
    """Innermost host span covering time ``t``; ``spans`` sorted by start."""
    best, best_len = "none", float("inf")
    i = bisect.bisect_right(starts, t)
    for s, e, name in reversed(spans[:i]):
        if e >= t and e - s < best_len:
            best, best_len = name, e - s
    return best


def reduce(devices: dict, host: list, window: tuple, top: int = 10) -> dict:
    """``devices``: {device: [(start, end, op name)]}; ``host``: [(start,
    end, span name)]; ``window``: (start, end) on the same clock.

    Returns per-device means: ``busy_s``, ``window_s``, ``idle_share``,
    ``collective_s``, ``exposed_collective_s``; and ``device_ops`` (op name,
    seconds per device) and ``idle_gaps`` (host span, seconds per device),
    each the ``top`` largest."""
    lo, hi = window
    n = max(len(devices), 1)
    spans = sorted(host)
    starts = [s for s, _, _ in spans]
    busy = coll = exposed = 0.0
    by_op, by_gap = {}, {}
    for evs in devices.values():
        evs = [(max(s, lo), min(e, hi), name) for s, e, name in evs
               if e > lo and s < hi]
        all_ops = union((s, e) for s, e, _ in evs)
        c = union((s, e) for s, e, name in evs if is_collective(name))
        other = union((s, e) for s, e, name in evs if not is_collective(name))
        busy += length(all_ops)
        coll += length(c)
        exposed += length(subtract(c, other))
        for s, e, name in evs:
            key = short_name(name)
            by_op[key] = by_op.get(key, 0.0) + (e - s)
        for s, e in gaps(all_ops, lo, hi):
            lab = _label(spans, starts, (s + e) / 2)
            by_gap[lab] = by_gap.get(lab, 0.0) + (e - s)
    window_s = hi - lo
    busy_s = busy / n

    def ranked(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "collective_s": coll / n, "exposed_collective_s": exposed / n,
            "device_ops": ranked(by_op), "idle_gaps": ranked(by_gap)}


def _device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


OP_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"


def read_xplane(trace_dir: str) -> tuple:
    """The newest ``.xplane.pb`` under ``trace_dir`` -> (devices, host) as
    ``reduce`` takes them, in seconds. Device events are each device
    plane's ``XLA Ops``, and the collectives among its ``Async XLA Ops``
    (a TPU plane draws an asynchronous operation from its start to its
    done there; the asynchronous copies on that line overlap the ops that
    wait for them and are left out); host events are every event on the
    host planes' threads."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if _device_plane(plane.name):
            evs = []
            for line in plane.lines:
                if line.name in (OP_LINE, ASYNC_LINE):
                    evs.extend((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, e.name)
                               for e in line.events
                               if line.name == OP_LINE
                               or is_collective(e.name))
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9, e.name)
                            for e in line.events)
    return devices, host


def find_span(host: list, name: str) -> tuple:
    """(start, end) of the last host span called ``name``."""
    hits = [(s, e) for s, e, n in host if n == name]
    if not hits:
        raise KeyError(f"no host span {name!r} in the trace")
    return max(hits)
