"""Table 3 of the paper, generalized (DESIGN.md §2) — now latency-aware.

Per-chip wire bytes per training step for one parameter of size ``b`` bytes:

  dense:
    allreduce (MPI/ring):  2 (N-1)/N · b          [paper Table 3, dense-MPI]
    fsdp  (PS-for-dense):  2 b                    [pull b (all-gather) + push
                                                   b (reduce-scatter); paper
                                                   Table 3, dense-PS]
  sparse (α = touched fraction per replica-step):
    ps (row-sharded):      pull 2α b (M-1)/M  +  push 2 b_shard (D-1)/D
                           where b_shard = b/M   [shard psum over data]
    ps_gather push:        pull 2α b + push D α b [sparse all-gather over data]
    mpi_gatherv:           2 (N-1) α b            [paper Table 3, sparse-MPI]

N = total replicas (data·pod), M = model-axis size, D = data(+pod) size.

Bytes alone mispredict small parameters: each collective also pays a fixed
per-message launch latency (the α term in Shi et al.'s α + β·b model,
arXiv:1711.05979), so the planner's argmin runs over *seconds*:

  t(method) = messages(method) · HW.link_latency + wire_bytes / HW.link_bw

``method_messages`` counts the collective launches each method issues per
step, and ``exchange_seconds`` is the shared α + β·b evaluator — the same
model core/buckets.py uses to score fusing n dense all-reduces into k
bucketed ones. RunConfig.comm_mode can still force the paper's baselines
(ps / mpi).

Hierarchical topology (Shi et al. §IV, arXiv:1711.05979): real meshes have
two link tiers — fast intra-host ICI/NVLink (α₁, β₁ = ``Hardware.
link_latency``/``link_bw``) and a slower inter-host fabric (α₂, β₂ =
``inter_latency``/``inter_bw``). When ``MeshDims.hosts > 1`` and the inter
constants are set, collectives that span hosts are priced at the inter tier
(the slowest link governs a flat ring), and a dense all-reduce may instead
ride a *two-level* schedule — intra-host reduce-scatter, inter-host
all-reduce of the 1/L shard, intra-host all-gather:

  t(two_level) = 2α₁ + α₂ + 2·(L−1)/L·b/β₁ + 2·(H−1)/H·(b/L)/β₂

with H hosts and L local replicas per host — only b/L bytes ever cross the
slow tier. ``choose_dense_schedule`` is the argmin the bucket planner uses;
single-host (or inter constants unset) reduces every formula here exactly
to the flat model, so the hierarchy is strictly additive.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, replace
from typing import Optional

from repro.utils.roofline import HW, Hardware, device_hw

# Hardware fields a fitted hw_profile.json (tools/profile_collectives.py
# fit) may override; anything else in the file is ignored.
_PROFILE_FIELDS = ("name", "link_bw", "link_latency", "inter_bw",
                   "inter_latency")
_profile_cache: dict = {}


def load_hw_profile(path: str, hw: Optional[Hardware] = None) -> Hardware:
    """Overlay a fitted α/β profile onto the hardware model. The file is a
    flat JSON object; only ``_PROFILE_FIELDS`` keys apply (extra keys — the
    fitter records its raw measurements — pass through untouched)."""
    hw = hw or HW
    key = (os.path.abspath(path), os.path.getmtime(path), hw)
    if key in _profile_cache:
        return _profile_cache[key]
    with open(path) as f:
        prof = json.load(f)
    fields = {k: (str(v) if k == "name" else float(v))
              for k, v in prof.items()
              if k in _PROFILE_FIELDS and v is not None}
    hw = replace(hw, **fields)
    _profile_cache[key] = hw
    return hw


def resolve_hw(run_cfg=None, hw: Optional[Hardware] = None) -> Hardware:
    """The hardware model the planner prices against: the chip's roofline
    entry (``roofline.device_hw``), overlaid with RunConfig.hw_profile (a fitted α₁β₁/α₂β₂ profile from
    tools/profile_collectives.py) when set, then RunConfig.link_latency
    (when set) overriding the intra α term — the config path for pinning
    the pure-byte Table-3 argmin (link_latency=0) without mutating module
    state."""
    hw = hw or device_hw()
    prof = getattr(run_cfg, "hw_profile", None) if run_cfg is not None else None
    if prof:
        hw = load_hw_profile(prof, hw)
    ll = getattr(run_cfg, "link_latency", None) if run_cfg is not None else None
    if ll is not None:
        hw = replace(hw, link_latency=float(ll))
    return hw


def mesh_hosts(mesh) -> int:
    """Host-group count among a mesh's devices — the H of the two-level
    schedule. Real multi-host: the spread of ``device.process_index``.
    Single-process simulation: the "pod" axis models the inter-host tier
    (launch/mesh.make_production_mesh places it outermost), so its size
    stands in for H when every device reports one process."""
    if mesh is None:
        return 1
    procs = 1
    try:
        devs = mesh.devices.flat
        procs = len({getattr(d, "process_index", 0) for d in devs})
    except AttributeError:
        pass                    # fake meshes in unit tests: no device array
    if procs > 1:
        return procs
    if "pod" in getattr(mesh, "axis_names", ()):
        return max(int(dict(mesh.shape)["pod"]), 1)
    return 1


@dataclass(frozen=True)
class MeshDims:
    model: int = 1
    data: int = 1
    pod: int = 1
    hosts: int = 1                      # H: host groups among the replicas

    @property
    def replicas(self) -> int:          # N in the paper
        return self.data * self.pod

    @property
    def chips(self) -> int:
        return self.model * self.data * self.pod

    @property
    def local_replicas(self) -> int:
        """L: replicas per host (the intra-tier group of the two-level
        schedule). Hosts that don't divide the replicas cleanly fall back
        to 1 — the pricing then degrades to all-inter, never crashes."""
        h = max(self.hosts, 1)
        n = self.replicas
        return n // h if h > 1 and n % h == 0 else (n if h <= 1 else 1)


def dense_allreduce_bytes(b: float, dims: MeshDims) -> float:
    n = dims.replicas
    if n <= 1:
        return 0.0
    return 2.0 * (n - 1) / n * b


def dense_fsdp_bytes(b: float, dims: MeshDims) -> float:
    n = dims.replicas
    if n <= 1:
        return 0.0
    # all-gather params (fwd+bwd counted once: XLA rematerializes the gather
    # in bwd under remat; we count the roofline-honest 2x) + reduce-scatter;
    # ring AG+RS == AR volume, ≈ 2b for large N
    return 2.0 * (n - 1) / n * b


def sparse_ps_bytes(b: float, alpha: float, dims: MeshDims) -> float:
    m, d = dims.model, dims.replicas
    pull = 2.0 * alpha * b * (m - 1) / m if m > 1 else 0.0
    push = 2.0 * (b / max(m, 1)) * (d - 1) / d if d > 1 else 0.0
    return pull + push


def sparse_ps_gather_bytes(b: float, alpha: float, dims: MeshDims) -> float:
    m, d = dims.model, dims.replicas
    pull = 2.0 * alpha * b * (m - 1) / m if m > 1 else 0.0
    push = d * alpha * b if d > 1 else 0.0
    return pull + push


def sparse_mpi_bytes(b: float, alpha: float, dims: MeshDims) -> float:
    n = dims.replicas
    if n <= 1:
        return 0.0
    return 2.0 * (n - 1) * alpha * b


def method_bytes(b: float, alpha: float, dims: MeshDims) -> dict:
    return {
        "allreduce": dense_allreduce_bytes(b, dims),
        "fsdp": dense_fsdp_bytes(b, dims),
        "ps": sparse_ps_bytes(b, alpha, dims),
        "ps_gather": sparse_ps_gather_bytes(b, alpha, dims),
        "mpi_gatherv": sparse_mpi_bytes(b, alpha, dims),
    }


def method_messages(method: str, dims: MeshDims) -> int:
    """Collective launches per step for one parameter under ``method``."""
    m, d = dims.model, dims.replicas
    if method == "allreduce":
        return 1 if d > 1 else 0
    if method == "fsdp":
        return 2 if d > 1 else 0                    # all-gather + reduce-scatter
    if method == "ps":                              # pull psum + push shard psum
        return (1 if m > 1 else 0) + (1 if d > 1 else 0)
    if method == "ps_gather":                       # pull psum + (ids, rows) AG
        return (1 if m > 1 else 0) + (2 if d > 1 else 0)
    if method == "mpi_gatherv":                     # (ids, rows) all-gather
        return 2 if d > 1 else 0
    raise ValueError(f"unknown method {method!r}")


def _tier_constants(hw: Hardware, tier: str) -> tuple[float, float]:
    """(α, β) for a link tier. The inter tier only exists when both inter
    constants are set; otherwise every tier prices at the intra link — the
    exact single-tier reduction the flat model had."""
    if tier == "inter" and hw.hierarchical:
        return hw.inter_latency, hw.inter_bw
    return hw.link_latency, hw.link_bw


def span_tier(dims: MeshDims, hw: Hardware = HW) -> str:
    """The tier a replica-spanning collective runs at: a flat ring that
    crosses hosts is governed by its slowest link (inter); single-host
    meshes never leave the intra fabric."""
    return "inter" if dims.hosts > 1 and hw.hierarchical else "intra"


def exchange_seconds(wire_bytes: float, messages: float,
                     hw: Hardware = HW, tier: str = "intra") -> float:
    """The α + β·b transfer model: messages·α + bytes/bandwidth, at the
    given link tier."""
    alpha, beta = _tier_constants(hw, tier)
    return messages * alpha + wire_bytes / beta


def dense_schedule_seconds(b: float, dims: MeshDims,
                           hw: Hardware = HW) -> dict:
    """Execution-schedule candidates for ONE dense all-reduce of ``b``
    bytes: the flat ring (priced at the tier it spans) and — on multi-host
    meshes with fitted inter constants — the two-level
    reduce-scatter → inter all-reduce → all-gather schedule, which moves
    only b/L bytes across the slow tier (module docstring formula)."""
    n = dims.replicas
    out = {"ring": exchange_seconds(dense_allreduce_bytes(b, dims),
                                    1 if n > 1 else 0, hw,
                                    tier=span_tier(dims, hw))}
    h, loc = dims.hosts, dims.local_replicas
    if hw.hierarchical and h > 1 and loc > 1:
        intra_bytes = 2.0 * (loc - 1) / loc * b
        inter_bytes = 2.0 * (h - 1) / h * (b / loc)
        out["two_level"] = (2.0 * hw.link_latency + hw.inter_latency
                            + intra_bytes / hw.link_bw
                            + inter_bytes / hw.inter_bw)
    return out


def choose_dense_schedule(b: float, dims: MeshDims,
                          hw: Hardware = HW) -> tuple[str, dict]:
    """Pick the execution schedule for one dense all-reduce (the bucket
    planner's per-bucket argmin). Returns (schedule, seconds-by-schedule)."""
    secs = dense_schedule_seconds(b, dims, hw)
    return min(secs, key=secs.get), secs


def method_seconds(*, b: float, alpha: float, dims: MeshDims,
                   hw: Hardware = HW) -> dict:
    """Per-method step seconds for one parameter (the planner's argmin).

    On a multi-host mesh with inter constants every method's collectives
    span hosts, so messages and bytes price at the inter tier; the dense
    all-reduce additionally gets the best of its execution schedules (a
    two-level schedule can undercut the flat inter-tier ring). Single-host
    (or no inter constants) reduces exactly to the flat α + β·b model."""
    bts = method_bytes(b, alpha, dims)
    tier = span_tier(dims, hw)
    secs = {k: exchange_seconds(v, method_messages(k, dims), hw, tier=tier)
            for k, v in bts.items()}
    if tier == "inter":
        secs["allreduce"] = min(
            dense_schedule_seconds(b, dims, hw).values())
    return secs


def choose_method(*, b: float, sparse: bool, alpha: float, dims: MeshDims,
                  comm_mode: str = "hybrid", memory_forced_fsdp: bool = False,
                  can_shard_rows: bool = True,
                  hw: Optional[Hardware] = None) -> tuple[str, dict]:
    """Pick the exchange method for one parameter; returns (method, costs).

    ``costs`` keys are per-chip wire bytes (Table 3); the argmin itself runs
    over ``method_seconds`` so a small sparse parameter whose gatherv bytes
    undercut a dense all-reduce can still lose on message count.

    can_shard_rows: False when no mesh axis can row-shard the table (e.g.
    the dp dense strategy uses every axis for batch) — the PS family is then
    infeasible and the sparse param competes as dense allreduce vs gatherv.
    """
    hw = hw or HW
    costs = method_bytes(b, alpha, dims)
    secs = method_seconds(b=b, alpha=alpha, dims=dims, hw=hw)
    if not sparse:
        if comm_mode == "ps" or memory_forced_fsdp:
            return "fsdp", costs
        return "allreduce", costs
    # sparse parameter
    if comm_mode == "mpi":
        return "mpi_gatherv", costs
    if comm_mode in ("ps", "hybrid"):
        cands = ["mpi_gatherv", "allreduce"] if comm_mode == "hybrid" else []
        if can_shard_rows:
            cands += ["ps", "ps_gather"]
        if not cands:
            cands = ["mpi_gatherv"]
        best = min(cands, key=lambda k: secs[k])
        return best, costs
    raise ValueError(f"unknown comm_mode {comm_mode!r}")


def serve_pull_bytes(b: float, alpha: float, method: str,
                     dims: MeshDims) -> float:
    """Per-decode-step wire bytes for one sparse table's serve-time pull.

    Inference has no push leg: a row-sharded table (ps / ps_gather) pays the
    deduped row-buffer psum over the model axis every decode step (2αb of
    the *step's* activated fraction — α here must come from a decode-shape
    census, where the per-replica token count is the decode batch, not
    B·S); a replicated table (allreduce / mpi_gatherv / dense) gathers
    locally and moves nothing. The trade a serve mesh actually makes is
    wire-per-step vs M× table HBM — the memory-escalation pass arbitrates
    the latter, this prices the former.
    """
    m = dims.model
    if method in ("ps", "ps_gather") and m > 1:
        return 2.0 * alpha * b * (m - 1) / m
    return 0.0


def serve_pull_messages(method: str, dims: MeshDims) -> int:
    return 1 if method in ("ps", "ps_gather") and dims.model > 1 else 0


def serve_pull_seconds(*, b: float, alpha: float, method: str,
                       dims: MeshDims, hw: Optional[Hardware] = None) -> float:
    """α + β·b seconds one decode step spends pulling this table."""
    hw = hw or HW
    return exchange_seconds(serve_pull_bytes(b, alpha, method, dims),
                            serve_pull_messages(method, dims), hw,
                            tier=span_tier(dims, hw))


def serve_table_pricing(*, b: float, alpha: float, method: str,
                        dims: MeshDims, batch_tokens: int,
                        hw: Optional[Hardware] = None) -> dict:
    """Serve-mesh pricing for one table at decode batch shapes: the wire
    bytes and seconds one decode step pays for the pull, and the per-token
    exchange seconds at this batch (one token per sequence per step).
    Stamped into ``Plan.table_serve`` when the planner runs at a decode
    ShapeConfig and surfaced via ``Plan.tables()``."""
    hw = hw or HW
    pull_b = serve_pull_bytes(b, alpha, method, dims)
    pull_s = serve_pull_seconds(b=b, alpha=alpha, method=method, dims=dims,
                                hw=hw)
    return {"pull_bytes": pull_b, "pull_s": pull_s,
            "s_per_token": pull_s / max(int(batch_tokens), 1)}


def stale_push_seconds(*, b: float, alpha: float, method: str,
                       dims: MeshDims, hw: Optional[Hardware] = None) -> dict:
    """Price one sparse table's push under the bounded-staleness fallback.

    The stale mode changes *scheduling*, not volume: the row-buffer
    exchange still runs every step (replica consistency — every replica
    must buffer the same aggregate), but the applied gradient no longer
    gates this step's optimizer update, so the exchange overlaps the next
    step's forward instead of sitting on the critical path. Returned:

      ``sync_s``      the synchronous critical-path cost (method_seconds)
      ``stale_s``     the wire seconds still paid, off the critical path
      ``critical_s``  what remains ON the path in stale mode (0.0 — the
                      whole exchange is deferrable once nothing waits on it)

    The trainer logs this alongside a stale flip so the jitter fallback's
    expected win is visible before the throughput confirms it."""
    hw = hw or HW
    sync = method_seconds(b=b, alpha=alpha, dims=dims, hw=hw)[method]
    return {"sync_s": sync, "stale_s": sync, "critical_s": 0.0}


def pick_dense_strategy(cfg, shape, dims: MeshDims, hbm_bytes: float = 16e9,
                        param_dtype_bytes: int = 2) -> str:
    """Choose tp(+SP) vs dp(ZeRO-3 over every axis) for dense params.

    Per-chip wire napkin (per layer):
      tp+sp: ~12 seq-scattered activation units = 12·T_repl·D·w·(m-1)/m
      dp:    ~3 passes x full layer params      = 3·P_L·w
    MoE and decode need the model axis (EP / cache sharding) -> tp.
    """
    if cfg.n_experts or shape.kind == "decode" or dims.model <= 1:
        return "tp"
    chips = dims.chips
    if shape.global_batch % chips != 0 and \
            shape.global_batch % (dims.data * dims.model) != 0:
        return "tp"
    t_repl = shape.tokens / max(dims.replicas, 1)
    m = dims.model
    tp_unit = t_repl * cfg.d_model * param_dtype_bytes * (m - 1) / m
    layers = cfg.n_layers + (cfg.enc_layers if cfg.is_encdec else 0)
    p_layer = max((cfg.param_count() - cfg.vocab_size * cfg.d_model *
                   (1 if cfg.tie_embeddings else 2)) / max(layers, 1), 1)
    tp_coll = 12 * tp_unit
    dp_coll = 3 * p_layer * param_dtype_bytes
    return "dp" if dp_coll < tp_coll else "tp"
