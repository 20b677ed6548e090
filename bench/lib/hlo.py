"""Collective count and bytes from a compiled step's HLO text.

A copy of the collective part of the program's ``utils/hlo.py``
(``parse_module``, trip-count multipliers, async ``-start`` result halving,
replica-group sizes), kept here so that no later change to the program can
move what the benchmark counts. ``compiled.as_text()`` shows per-partition
shapes, so every byte here is per device and per step.
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute", "collective-broadcast")

_BLOCK_START = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_OP_RE = re.compile(
    r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)"
    r"\s+([a-z0-9\-]+)\(")
_TRIP_RE = re.compile(r'"known_trip_count":\s*\{\s*"n":\s*"?(\d+)"?')
_CALLED = re.compile(r"(?:body|condition|calls|to_apply)=\{?%?([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{?[0-9,{} ]*\}\}?)")


def shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        n = 1
        for d in dims.split(",") if dims else ():
            n *= int(d)
        total += n * nbytes
    return total


def _result_type(kind: str, type_str: str) -> str:
    """An async ``-start`` collective is typed (operand, result); the result
    half alone is what it produces."""
    if not kind.endswith("-start") or not type_str.startswith("("):
        return type_str
    shapes = _SHAPE_RE.findall(type_str)
    if len(shapes) >= 2 and len(shapes) % 2 == 0:
        half = shapes[len(shapes) // 2:]
        return ", ".join(f"{d}[{dims}]" for d, dims in half)
    return type_str


def group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        body = m.group(1).replace(" ", "").strip("{}")
        groups = [[x for x in part.strip("{} ").split(",") if x.strip()]
                  for part in body.split("},{")]
        groups = [g for g in groups if g]
        if groups:
            return max(len(g) for g in groups)
    m2 = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m2:
        return int(m2.group(2))
    return 2


def _parse(text: str):
    """-> ({computation: [(kind, type, line)]}, {computation: edges},
    entry name)."""
    ops, edges, cur, entry = {}, {}, None, None
    for raw in text.splitlines():
        line = raw.strip()
        if cur is None:
            m = _BLOCK_START.match(line)
            if m:
                cur = m.group(1)
                ops[cur], edges[cur] = [], []
                if line.startswith("ENTRY"):
                    entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        _, type_str, kind = m.groups()
        ops[cur].append((kind, type_str, line))
        if kind == "while":
            tm = _TRIP_RE.search(line)
            trip = float(tm.group(1)) if tm else 1.0
            for cm in re.finditer(r"(?:body|condition)=\{?%?([\w.\-]+)",
                                  line):
                edges[cur].append((cm.group(1), trip))
        else:
            for cm in _CALLED.finditer(line):
                edges[cur].append((cm.group(1), 1.0))
    if entry is None and ops:
        entry = list(ops)[-1]
    return ops, edges, entry


def _multipliers(edges: dict, entry: str) -> dict:
    mult: dict = {}

    def visit(name, m):
        if name not in edges:
            return
        first = name not in mult
        mult[name] = mult.get(name, 0.0) + m
        if not first:
            return
        for callee, k in edges[name]:
            visit(callee, m * k)

    visit(entry, 1.0)
    return mult


def collectives(text: str) -> dict:
    """Per device and per execution of the module: ``count`` and operand
    ``bytes`` of each collective kind, loop bodies counted per trip.

    Operand bytes: an all-reduce's operand is its result; an all-gather's
    is its result over the group; a reduce-scatter's is its result times
    the group."""
    ops, edges, entry = _parse(text)
    mult = _multipliers(edges, entry)
    count, nbytes = {}, {}
    for comp, m in mult.items():
        for kind, type_str, line in ops[comp]:
            base = next((c for c in KINDS
                         if kind == c or kind == c + "-start"), None)
            if base is None:
                continue
            b = shape_bytes(_result_type(kind, type_str))
            n = group_size(line)
            if base == "all-gather":
                b /= n
            elif base == "reduce-scatter":
                b *= n
            count[base] = count.get(base, 0.0) + m
            nbytes[base] = nbytes.get(base, 0.0) + m * b
    return {"count": count, "bytes": nbytes,
            "total_bytes": sum(nbytes.values())}
