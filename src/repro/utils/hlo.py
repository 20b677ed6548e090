"""Post-SPMD HLO analysis for the roofline (§Roofline).

``compiled.as_text()`` shows *per-partition* shapes, so byte/FLOP counts here
are per-chip. XLA's ``cost_analysis()`` counts while-loop bodies ONCE
(verified: a 6-iteration scan reports 1x body FLOPs), which would undercount
scan-over-layers models by ~n_layers. This parser instead:

  1. splits the module into computation blocks,
  2. builds the call graph (while body/condition via
     ``backend_config={"known_trip_count":{"n":...}}``, fusion/call via
     ``calls=``, reduce via ``to_apply=``),
  3. propagates trip-count multipliers from ENTRY,
  4. sums collective output bytes and dot FLOPs × multiplier.

The collective term uses ring-cost scaling per op kind (all-reduce moves
2(N-1)/N × bytes; gather/scatter/a2a (N-1)/N; permute 1) with N from the
op's replica_groups.
"""
from __future__ import annotations

import json
import math
import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * nbytes
    return total


def _result_type(kind: str, type_str: str) -> str:
    """The *result* part of a collective's type string.

    Async ``-start`` collectives are typed as a tuple aliasing the operand
    with the result — e.g. ``all-gather-start`` prints
    ``(f32[4,8], f32[32,8])`` = (operand, result). Summing every shape in
    that tuple double-counts the wire traffic; the result half alone is
    what the op moves. Sync collectives (and ``-start`` ops whose tuple is
    a fused multi-operand result) pass through unchanged."""
    if not kind.endswith("-start") or not type_str.startswith("("):
        return type_str
    shapes = _SHAPE_RE.findall(type_str)
    if len(shapes) >= 2 and len(shapes) % 2 == 0:
        half = shapes[len(shapes) // 2:]
        return ", ".join(f"{d}[{dims}]" for d, dims in half)
    return type_str


def _shape_elems(type_str: str) -> int:
    n = 0
    for _, dims in _SHAPE_RE.findall(type_str):
        k = 1
        if dims:
            for d in dims.split(","):
                k *= int(d)
        n += k
    return n


def _shape_dtype(type_str: str) -> str | None:
    """Dtype of the largest shape in a (possibly tuple) type string."""
    best, best_n = None, -1
    for dtype, dims in _SHAPE_RE.findall(type_str):
        k = 1
        if dims:
            for d in dims.split(","):
                k *= int(d)
        if k > best_n:
            best, best_n = dtype, k
    return best


def _shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclass
class Op:
    name: str
    kind: str
    type_str: str
    line: str


@dataclass
class Computation:
    name: str
    ops: list[Op] = field(default_factory=list)
    # edges: (callee_name, multiplier)
    edges: list[tuple[str, float]] = field(default_factory=list)
    fused_callees: set = field(default_factory=set)


@dataclass
class HloSummary:
    collective_bytes: float = 0.0          # per-chip, ring-cost scaled
    collective_raw_bytes: float = 0.0      # per-chip, unscaled operand sums
    collective_by_kind: dict = field(default_factory=dict)
    collective_count: dict = field(default_factory=dict)
    dot_flops: float = 0.0                 # per-chip, trip-count corrected
    hbm_bytes: float = 0.0                 # per-chip traffic estimate: 2x the
                                           # materialized (post-fusion) buffer
                                           # writes x trip multipliers + params

    def to_dict(self) -> dict:
        return {
            "collective_bytes": self.collective_bytes,
            "collective_raw_bytes": self.collective_raw_bytes,
            "collective_by_kind": self.collective_by_kind,
            "collective_count": self.collective_count,
            "dot_flops": self.dot_flops,
            "hbm_bytes": self.hbm_bytes,
        }


_BLOCK_START = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_OP_RE = re.compile(
    r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+([a-z0-9\-]+)\(")
_TRIP_RE = re.compile(r'"known_trip_count":\s*\{\s*"n":\s*"?(\d+)"?')
_CALLED = re.compile(r"(?:body|condition|calls|to_apply)=\{?%?([\w.\-]+)")
_GROUPS_RE = re.compile(r"replica_groups=(\{\{?[0-9,{} ]*\}\}?)")


def _ring_factor(kind: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (n - 1) / n
    if kind in ("all-gather", "reduce-scatter", "all-to-all"):
        return (n - 1) / n
    return 1.0  # permute / broadcast


def _replica_groups(line: str) -> list[list[int]]:
    """All replica groups on an op line, e.g. ``{{0,1},{2,3}}`` ->
    [[0, 1], [2, 3]]. Handles the single-group ``{0,1,2}`` spelling and
    unequal groups like ``{{0},{1,2,3}}``."""
    m = _GROUPS_RE.search(line)
    if not m:
        return []
    body = m.group(1).replace(" ", "").strip("{}")
    groups = []
    for part in body.split("},{"):
        part = part.strip("{} ")
        if part:
            groups.append([int(x) for x in part.split(",") if x.strip()])
    return [g for g in groups if g]


def _group_size(line: str) -> int:
    groups = _replica_groups(line)
    if groups:
        # ring cost is set by the largest group the op participates in
        return max(len(g) for g in groups)
    # replica_groups=[4,2]<=[8] style (iota tile assignment)
    m2 = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m2:
        return int(m2.group(2))
    return 2


def parse_module(text: str) -> tuple[dict, str, dict]:
    """-> (computations, entry_name, name->type symbol table)."""
    comps: dict[str, Computation] = {}
    symbols: dict[str, str] = {}
    cur: Computation | None = None
    entry = None
    for raw in text.splitlines():
        line = raw.strip()
        if cur is None:
            m = _BLOCK_START.match(line)
            if m:
                cur = Computation(m.group(1))
                if raw.startswith("ENTRY") or line.startswith("ENTRY"):
                    entry = cur.name
                # params in header: name: type
                for pm in re.finditer(r"([\w.\-]+):\s*([a-z0-9]+\[[0-9,]*\][^\s,)]*)", line):
                    symbols[pm.group(1)] = pm.group(2)
            continue
        if line.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        name, type_str, kind = m.groups()
        symbols[name] = type_str
        cur.ops.append(Op(name, kind, type_str, line))
        if kind in ("while",):
            trip = 1.0
            tm = _TRIP_RE.search(line)
            if tm:
                trip = float(tm.group(1))
            for cm in re.finditer(r"body=\{?%?([\w.\-]+)", line):
                cur.edges.append((cm.group(1), trip))
            for cm in re.finditer(r"condition=\{?%?([\w.\-]+)", line):
                cur.edges.append((cm.group(1), trip))
        else:
            for cm in _CALLED.finditer(line):
                cur.edges.append((cm.group(1), 1.0))
            if kind == "fusion":
                for cm in re.finditer(r"calls=\{?%?([\w.\-]+)", line):
                    cur.fused_callees.add(cm.group(1))
    if cur is not None:
        comps[cur.name] = cur
    if entry is None and comps:
        # ENTRY block header sometimes lacks the keyword in our regex; the
        # last computation in an HLO dump is the entry
        entry = list(comps)[-1]
    return comps, entry, symbols


def _multipliers(comps: dict, entry: str) -> tuple[dict, set]:
    """-> ({name: multiplier}, {names reachable only inside fusions})."""
    mult: dict[str, float] = {}
    top_level: set[str] = set()

    def visit(name: str, m: float, fused: bool):
        if name not in comps:
            return
        first = name not in mult
        mult[name] = mult.get(name, 0.0) + m
        if not fused:
            top_level.add(name)
        if not first and (fused or name in top_level):
            return  # avoid exponential revisits; multipliers already summed
        comp = comps[name]
        for callee, k in comp.edges:
            visit(callee, m * k, fused or callee in comp.fused_callees)

    visit(entry, 1.0, False)
    fusion_internal = set(mult) - top_level
    return mult, fusion_internal


def _dot_flops(op: Op, symbols: dict) -> float:
    out_dims = _shape_dims(op.type_str)
    out = math.prod(out_dims) if out_dims else 0
    # Operands start at "<kind>(" — NOT at the first occurrence of the kind
    # substring: the op's own name usually contains it ("%dot.0 = ... dot("),
    # which previously captured the lhs *type* token instead of its name and
    # silently dropped the contraction factor. Optimized dumps also inline
    # the operand type ("dot(f32[64,32]{1,0} %gte.4, ...)"); prefer it.
    lhs_m = re.search(
        r"\s" + re.escape(op.kind)
        + r"\((?:([a-z0-9]+\[[0-9,]*\])(?:\{[^}]*\})?\s+)?%?([\w.\-]+)",
        op.line)
    contracting = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
    if not lhs_m or not contracting:
        return 2.0 * out
    lhs_type = lhs_m.group(1) or symbols.get(lhs_m.group(2))
    if lhs_type is None:
        return 2.0 * out
    lhs_dims = _shape_dims(lhs_type)
    k = 1
    cd = contracting.group(1)
    if cd:
        for d in cd.split(","):
            i = int(d)
            if i < len(lhs_dims):
                k *= lhs_dims[i]
    return 2.0 * out * k


_NO_TRAFFIC = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "copy", "reshape", "after-all", "partition-id",
               "replica-id", "iota"}


def analyze_hlo(text: str, param_bytes: float = 0.0,
                f32_collective_scale: float = 1.0) -> HloSummary:
    """f32_collective_scale: the CPU backend upcasts bf16 arithmetic to f32,
    so collectives that would ride the wire in bf16 on TPU appear as f32 in
    the dry-run HLO. Pass 0.5 (when the wire dtype is bf16/OPSW) to count
    them at their TPU width. Intentionally-f32 collectives (scalar norms,
    opsw=off ablations) are either negligible or accounted consistently
    because the ablation compares like against like."""
    comps, entry, symbols = parse_module(text)
    mult, fusion_internal = _multipliers(comps, entry)
    s = HloSummary()
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        materialized = cname not in fusion_internal
        for op in comp.ops:
            kind = op.kind
            base = None
            for c in _COLLECTIVE_KINDS:
                if kind == c or kind == c + "-start":
                    base = c
                    break
            if base is not None:
                nbytes = _shape_bytes(_result_type(kind, op.type_str))
                if "f32[" in op.type_str:
                    nbytes *= f32_collective_scale
                n = _group_size(op.line)
                s.collective_raw_bytes += m * nbytes
                s.collective_bytes += m * nbytes * _ring_factor(base, n)
                s.collective_by_kind[base] = \
                    s.collective_by_kind.get(base, 0.0) + m * nbytes
                s.collective_count[base] = \
                    s.collective_count.get(base, 0) + m
            elif kind in ("dot", "dot-general"):
                s.dot_flops += m * _dot_flops(op, symbols)
            if materialized and kind not in _NO_TRAFFIC:
                s.hbm_bytes += 2.0 * m * _shape_bytes(op.type_str)
    s.hbm_bytes += param_bytes
    return s


# scheduled-module helpers --------------------------------------------------

def _dot_bearing(comps: dict) -> set:
    """Names of computations that (transitively) contain a dot — needed to
    recognize matmul work after the backend fuses it away from a top-level
    dot op (CPU lowers most dots into fusions / library custom-calls)."""
    bearing: set[str] = set()
    changed = True
    while changed:
        changed = False
        for name, comp in comps.items():
            if name in bearing:
                continue
            has = any(op.kind in ("dot", "dot-general") for op in comp.ops)
            if not has:
                has = any(callee in bearing for callee, _ in comp.edges)
            if has:
                bearing.add(name)
                changed = True
    return bearing


_MATMUL_CALL = re.compile(
    r'custom_call_target="[^"]*(?:matmul|gemm|dot)[^"]*"', re.I)
_CALLS_RE = re.compile(r"calls=\{?%?([\w.\-]+)")


def is_scheduled(text: str) -> bool:
    return "is_scheduled=true" in text


def scheduled_events(text: str) -> list[dict]:
    """Execution-order event stream of the ENTRY computation of a
    *scheduled* HLO dump — once the module header says
    ``is_scheduled=true``, ``compiled.as_text()`` prints ops in schedule
    order, so text position IS execution position. Each event:
    ``{pos, name, kind, collective: base-kind-or-None, bytes, elems,
    dtype, grad_math}`` — ``bytes``/``elems``/``dtype`` describe the
    collective's *result* (``-start`` operand aliases excluded), so they
    match plan-side wire sizes directly.

    ``grad_math`` catches matmul work however the backend lowered it: raw
    dot/dot-general ops, fusions and while loops whose called computations
    (transitively) contain a dot, and matmul/gemm library custom-calls —
    scan-over-layers models run all their layer matmuls inside dot-bearing
    while bodies, which appear as ONE event each. The overlap regression
    (tests/test_perf_paths.py) uses this to assert the first bucket's
    all-reduce is scheduled before the last backward-bearing loop."""
    comps, entry, _ = parse_module(text)
    events: list[dict] = []
    if entry not in comps:
        return events
    bearing = _dot_bearing(comps)
    for pos, op in enumerate(comps[entry].ops):
        coll = None
        for c in _COLLECTIVE_KINDS:
            if op.kind == c or op.kind == c + "-start":
                coll = c
                break
        grad_math = op.kind in ("dot", "dot-general")
        if not grad_math and op.kind in ("fusion", "while", "call"):
            grad_math = any(cm.group(1) in bearing
                            for cm in _CALLED.finditer(op.line))
        if not grad_math and op.kind == "custom-call":
            grad_math = bool(_MATMUL_CALL.search(op.line))
        rtype = _result_type(op.kind, op.type_str) if coll else ""
        events.append({"pos": pos, "name": op.name, "kind": op.kind,
                       "collective": coll,
                       "bytes": _shape_bytes(rtype) if coll else 0,
                       "elems": _shape_elems(rtype) if coll else 0,
                       "dtype": _shape_dtype(rtype) if coll else None,
                       "grad_math": grad_math})
    return events


def dot_bearing_events(text: str, *, collective: str = "all-reduce",
                       min_bytes: int = 0) -> dict:
    """Scheduling summary shared by the overlap tests and the contract
    checker: positions of the chosen collective kind (result payload >
    ``min_bytes``) and of the dot-bearing while loops in the ENTRY
    schedule. ``first_collective``/``last_loop`` are ``None`` when the
    respective set is empty; comparing them answers "did the exchange
    start before the backward drained?" without each caller re-deriving
    grad-math detection."""
    ev = scheduled_events(text)
    colls = [e["pos"] for e in ev
             if e["collective"] == collective and e["bytes"] > min_bytes]
    loops = [e["pos"] for e in ev if e["kind"] == "while" and e["grad_math"]]
    return {
        "scheduled": is_scheduled(text),
        "events": ev,
        "collectives": colls,
        "loops": loops,
        "first_collective": min(colls) if colls else None,
        "last_loop": max(loops) if loops else None,
    }


# backwards-compatible helpers --------------------------------------------

def parse_collectives(hlo_text: str) -> HloSummary:
    return analyze_hlo(hlo_text)


def collective_bytes_by_kind(hlo_text: str) -> dict[str, float]:
    return analyze_hlo(hlo_text).collective_by_kind


# ---------------------------------------------------------------------------
# step phases
# ---------------------------------------------------------------------------
# The training step opens a ``jax.named_scope`` per phase: ``FORWARD`` around
# the loss (core/transform.py, core/buckets.py), ``OPTIMIZER`` around the
# update, clipping included (core/transform.py), and ``EXCHANGE`` around each
# explicit collective of the gradient exchange with its packing, casting and
# relayout (core/buckets.py, core/embedding.py). A scope changes only the
# ``op_name`` metadata of the ops it holds. The backward needs no scope: JAX
# names each of its ops ``transpose(jvp(...))``.

FORWARD, BACKWARD, OPTIMIZER, EXCHANGE = (
    "forward", "backward", "optimizer", "exchange")

# Scopes inside the model, nested in ``forward`` (their backward ops carry
# them under ``transpose(``): the attention core (models/attention.py
# callers), and the MoE layer's routing with its sort and scatter-back, its
# grouped expert products and its shared experts (models/moe.py).
ATTENTION, MOE_ROUTE, MOE_EXPERTS, MOE_SHARED = (
    "attention", "moe_route", "moe_experts", "moe_shared")
MODEL_SCOPES = (ATTENTION, MOE_ROUTE, MOE_EXPERTS, MOE_SHARED)


def _scope_in(name: str):
    # a path component is the scope itself or a transform of it,
    # e.g. ``jvp(forward)`` or ``transpose(jvp(forward))``
    return re.compile(r"(?:^|[/(])" + name + r"(?:[)/]|$)")


_PHASE_RULES = ((EXCHANGE, _scope_in(EXCHANGE)),
                (OPTIMIZER, _scope_in(OPTIMIZER)),
                (BACKWARD, re.compile(r"transpose\(")),
                (FORWARD, _scope_in(FORWARD)))


def step_phase(op_name: str) -> str | None:
    """The phase of a compiled step's op from its ``op_name`` metadata: the
    first rule that matches, of ``exchange`` scope, ``optimizer`` scope,
    ``transpose(`` (the backward), ``forward`` scope. None: unattributed."""
    for phase, rule in _PHASE_RULES:
        if rule.search(op_name):
            return phase
    return None


_PHASE_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|body)=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def model_scope(op_name: str) -> str | None:
    """The innermost of ``MODEL_SCOPES`` that an op's ``op_name`` names,
    else None."""
    best, at = None, -1
    for scope in MODEL_SCOPES:
        for m in _scope_in(scope).finditer(op_name):
            if m.start() > at:
                best, at = scope, m.start()
    return best


def step_phases(hlo_text: str, classify=step_phase,
                named: bool = False) -> dict:
    """``{instruction name: phase}`` for the instructions of a compiled
    module's text (``.compile().as_text()``) that have one, by ``classify``
    of their ``op_name``. An instruction takes the phase of its own
    op_name. One whose op_name names no phase (unless ``named``: then it
    has none), or that has no op_name (a backend may leave a fusion, a
    convert or a layout copy without metadata), takes that of the
    computation it calls (its root's, else its instructions' most common),
    else that of its first operand that has one, else that of its first
    user that has one (a buffer of zeros is the work of the phase that
    fills it)."""
    out, comps, cur, users, todo = {}, {}, None, {}, []
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{") and " = " not in line:
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            cur = comps[name.lstrip("%")] = {"root": None, "phases": []}
            continue
        m = _PHASE_INSTR.match(line)
        if not m or cur is None:
            continue
        name, rest = m.group(2), m.group(3)
        refs = _REF.findall(rest.partition(", metadata=")[0])
        for r in refs:
            users.setdefault(r, []).append(name)
        op = _OP_NAME.search(rest)
        phase = classify(op.group(1)) if op else None
        if phase is None and op and named:
            continue
        if phase is None:
            call = _CALLS.search(rest)
            called = comps.get(call.group(1)) if call else None
            if called:
                phase = out.get(called["root"]) or max(
                    set(called["phases"]), key=called["phases"].count,
                    default=None)
            if phase is None:
                phase = next((out[r] for r in refs if r in out), None)
        if phase is not None:
            out[name] = phase
            cur["phases"].append(phase)
        else:
            todo.append(name)
        if m.group(1):
            cur["root"] = name
    for name in reversed(todo):
        phase = next((out[u] for u in users.get(name, ()) if u in out),
                     None)
        if phase is not None:
            out[name] = phase
    return out


def split_device_time(op_seconds: dict, hlo_text: str) -> dict:
    """``{instruction name: device seconds}`` of a traced step, joined to
    its compiled text -> seconds by ``phase`` and, inside the model's
    scopes, by ``phase/scope`` (an op named outside every scope stays in
    its phase); ops of no phase count under ``unattributed``."""
    phases = step_phases(hlo_text)
    scopes = step_phases(hlo_text, model_scope, named=True)
    out = {}
    for name, sec in op_seconds.items():
        key = phases.get(name, "unattributed")
        if name in scopes:
            key += "/" + scopes[name]
        out[key] = out.get(key, 0.0) + sec
    return out
