"""The Parallax plan: logical-axis → mesh resolution and the per-parameter
communication plan (C1 hybrid communication, generalized per DESIGN.md §2).

Every parameter gets a ``ParamPlan`` naming its exchange *method*:

  allreduce    dense, replicated over data/pod (+TP over model where the
               logical axes say so); gradients ring-all-reduced by XLA.
               == paper's MPI/NCCL path, cost 2(N-1)b/N.
  fsdp         dense, additionally sharded over data (ZeRO-3); pull =
               all-gather before use, push = reduce-scatter.  == paper's PS
               path applied to a dense parameter, cost 2b.
  ps           sparse (embedding rows): row-sharded over model ("server
               shards"); pull = psum of deduped row-buffer (2αb), push =
               owner-local scatter-add + shard psum over data.  == paper's PS
               path for sparse parameters.
  mpi_gatherv  sparse baseline: all-gather of per-replica (ids, rows) +
               local densify, cost 2(N-1)αb.  == paper's AllGatherv path.

The method is chosen by core/cost_model.py from the Table-3 transfer model;
``RunConfig.comm_mode`` can force the paper's BASE (ps) / MPI (mpi) baselines.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.compat import Mesh, NamedSharding, P
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.models.layers import ParamSpec


# ---------------------------------------------------------------------------
# logical axis rules
# ---------------------------------------------------------------------------

def default_rules(mesh: Optional[Mesh], shape_kind: str, batch: int,
                  dense_strategy: str = "tp") -> dict:
    """logical axis name -> mesh axes (tuple) or None."""
    if mesh is None:
        return {}
    names = mesh.axis_names
    has_pod = "pod" in names
    if dense_strategy == "dp" and shape_kind != "decode":
        # §Perf iteration B: the model axis joins data parallelism; params
        # fully sharded (ZeRO-3) and gathered per layer. No TP, no SP.
        batch_axes = ("pod", "data", "model") if has_pod else ("data", "model")
        ba = list(batch_axes)
        while ba and batch % math.prod(mesh.shape[a] for a in ba) != 0:
            ba.pop(0)
        rules = {k: None for k in (
            "seq_sp", "vocab", "embed", "q_heads", "kv_heads", "heads_hd",
            "mlp", "experts", "moe_mlp", "layers", "state", "lstm_hidden",
            "conv")}
        rules["batch"] = tuple(ba) if ba else None
        rules["kv_seq"] = ("model",)
        return rules
    batch_axes = ("pod", "data") if has_pod else ("data",)
    # batch must divide the data(+pod) axes; drop axes until it does
    ba = list(batch_axes)
    while ba and batch % math.prod(mesh.shape[a] for a in ba) != 0:
        ba.pop(0)
    rules = {
        "batch": tuple(ba) if ba else None,
        "seq_sp": ("model",),          # sequence-parallel residual stream
        "vocab": ("model",),           # PS server shards (row-sharded)
        "embed": None,
        "q_heads": ("model",),
        "kv_heads": None,              # replicated: TP > n_kv  (DESIGN.md)
        "heads_hd": ("model",),        # flattened q_heads*head_dim rows
        "mlp": ("model",),
        "experts": ("model",),
        "moe_mlp": None,               # expert d_ff when experts are sharded
        "kv_seq": ("model",),          # decode cache sequence dim
        "layers": None,
        "state": None,
        "lstm_hidden": ("model",),
        "conv": None,
    }
    if shape_kind == "decode" and (not ba):
        # tiny-batch decode (long_500k): spread the cache over every axis
        rules["kv_seq"] = tuple(a for a in ("pod", "data", "model") if a in names)
    return rules


@dataclass
class MeshRules:
    mesh: Optional[Mesh]
    rules: dict

    def axis_size(self, logical: str) -> int:
        if self.mesh is None:
            return 1
        ax = self.rules.get(logical)
        if ax is None:
            return 1
        return math.prod(self.mesh.shape[a] for a in ax)

    def pspec(self, axes: tuple, shape: Optional[tuple] = None) -> P:
        """Resolve logical axes to a PartitionSpec with divisibility checks."""
        if self.mesh is None:
            return P()
        used: set[str] = set()
        out = []
        for i, name in enumerate(axes):
            entry = None
            if name is not None:
                cand = self.rules.get(name)
                if cand:
                    cand = tuple(a for a in cand if a not in used)
                    if cand:
                        size = math.prod(self.mesh.shape[a] for a in cand)
                        if shape is None or shape[i] % size == 0:
                            # single axes stay unwrapped: old JAX compares
                            # P(('model',)) != P('model') (no canonicalization)
                            entry = cand[0] if len(cand) == 1 else cand
                            used.update(cand)
            out.append(entry)
        return P(*out)

    def sharding(self, axes: tuple, shape: Optional[tuple] = None) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.pspec(axes, shape))


# ---------------------------------------------------------------------------
# per-parameter plan
# ---------------------------------------------------------------------------

def plan_leaves(tree: Any) -> list:
    """Flatten a ParamPlan tree in leaf order (the order bucket indices and
    gradient leaves share)."""
    return jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, ParamPlan))


@dataclass
class ParamPlan:
    name: str
    method: str                        # allreduce | fsdp | ps | mpi_gatherv
    pspec: P
    opt_pspec: P                       # optimizer-state sharding (ZeRO-1/3)
    wire_dtype: Any
    sparse: bool
    bytes: int
    capacity: int = 0                  # sparse tables: dedupe-buffer rows
    stale: bool = False                # bounded-staleness push mode: this
                                       # table applies s-step-old exchanged
                                       # gradients (jitter fallback)
    est_cost: dict = field(default_factory=dict)


@dataclass
class Plan:
    model_cfg: ModelConfig
    run_cfg: RunConfig
    shape_cfg: ShapeConfig
    mesh: Optional[Mesh]
    rules: MeshRules
    params: Any = None                 # tree of ParamPlan (aligned with specs)
    alpha: float = 1.0                 # estimated sparse-access ratio
    capacity: int = 0                  # binding sparse-exchange row capacity
    zero_stage: int = 0
    embed_method: str = "ps"           # the "embed" table's exchange method
    bucket_plan: Any = None            # core/buckets.py BucketPlan (None =
                                       # per-tensor dense collectives)
    fused_apply: bool = False          # optimizer reads the bucket
                                       # buffers directly (fused m/v/EMA
                                       # layout; optim/optimizer.py)
    table_tiles: dict = field(default_factory=dict)  # name -> (gather_block,
                                       # scatter_block) Pallas lane tiles from
                                       # the kernel autotune cache (0 = the
                                       # fixed full-row block)
    # ---- per-parameter planning (one record per sparse table) ----
    table_methods: dict = field(default_factory=dict)   # name -> method
    table_capacity: dict = field(default_factory=dict)  # name -> buffer rows
    table_wire: dict = field(default_factory=dict)      # name -> jnp dtype
    table_alpha: dict = field(default_factory=dict)     # name -> priced α
                                       # (the activated fraction the Table-3
                                       # argmin ran at — recorded so a
                                       # checkpoint manifest can reproduce
                                       # the method choice on restore)
    grown_tables: tuple = ()           # tables whose capacity the overflow
                                       # rule grew in this plan's census
    stale_tables: tuple = ()           # tables running the bounded-staleness
                                       # push (jitter fallback; empty = all
                                       # synchronous)
    table_serve: dict = field(default_factory=dict)  # decode-shape plans
                                       # only: name -> serve-mesh pricing
                                       # (cost_model.serve_table_pricing —
                                       # pull bytes/seconds per decode step
                                       # and per-token exchange seconds)

    # ---- totals for Table-1 style census ----
    def census(self) -> dict:
        dense = sparse = 0
        for p in jax.tree.leaves(self.params, is_leaf=lambda x: isinstance(x, ParamPlan)):
            if p.sparse:
                sparse += p.bytes
            else:
                dense += p.bytes
        return {"dense_bytes": dense, "sparse_bytes": sparse, "alpha": self.alpha}

    def methods(self) -> dict:
        out: dict[str, int] = {}
        for p in jax.tree.leaves(self.params, is_leaf=lambda x: isinstance(x, ParamPlan)):
            out[p.method] = out.get(p.method, 0) + 1
        return out

    def tables(self) -> dict:
        """Per-sparse-table plan summary (JSON-friendly) — one entry per
        table: its exchange method, buffer capacity, wire dtype, and the α
        the cost model priced it at. The summary round-trips through the
        checkpoint manifest (``Trainer`` saves it in ``extra['plan']``) and
        is enough to re-derive the same plan on restore: capacities and
        grown flags override the census, α reproduces the method argmin."""
        return {t: {
            "method": m,
            "capacity": self.table_capacity.get(t, self.capacity),
            "wire_dtype": jnp.dtype(self.table_wire[t]).name
            if t in self.table_wire else None,
            "grown": t in self.grown_tables,
            "alpha": self.table_alpha.get(t),
            "stale": t in self.stale_tables,
            # decode-shape plans carry serve-mesh pricing (per-step pull
            # bytes/seconds + per-token exchange seconds at the decode batch)
            "serve": self.table_serve.get(t),
        } for t, m in self.table_methods.items()}

    def exchange_contract(self) -> dict:
        """Everything ``analysis/contract.py`` needs to derive the expected
        collective set of a compiled step from this plan alone: the
        per-bucket dense collectives (kind + element count, in issue
        order), the overlap mode, and each sparse table's method/capacity/
        wire so the checker knows which row-buffer collectives to expect.
        ``n_leaves`` is the gradient leaf count — the overlap=False pin
        rides one element per leaf on every bucket psum."""
        leaves = jax.tree.leaves(
            self.params, is_leaf=lambda x: isinstance(x, ParamPlan))
        n_leaves = len(leaves)
        bp = self.bucket_plan
        return {
            "n_leaves": n_leaves,
            "methods": self.methods(),
            "bucketed": bp is not None,
            "overlap": bool(bp.overlap) if bp is not None else False,
            "replicas": bp.replicas if bp is not None else 1,
            "buckets": (bp.expected_collectives(n_leaves)
                        if bp is not None else []),
            "n_sparse_push": bp.n_sparse_push if bp is not None else 0,
            "tables": {t: {
                "method": m,
                "capacity": self.table_capacity.get(t, self.capacity),
                "wire_dtype": jnp.dtype(self.table_wire[t]).name
                if t in self.table_wire else None,
                "stale": t in self.stale_tables,
            } for t, m in self.table_methods.items()},
        }


def _drifted(old_cap: int, new_cap: int, factor: float) -> bool:
    hi = max(old_cap, new_cap)
    lo = max(min(old_cap, new_cap), 1)
    return old_cap != new_cap and hi / lo >= factor


def plan_diff(old: Plan, new: Plan, capacity_drift: float = 1.5) -> dict:
    """Structural diff between two Plans for the replan loop.

    ``changed`` is True when any parameter's exchange method flips, any
    pspec/opt_pspec differs (state must reshard), any parameter's wire dtype
    moves (the jitted step must re-trace), any table's capacity drifts by
    more than ``capacity_drift``x in either direction, the overflow rule
    grew a table's capacity (growth is never deadbanded — sustained overflow
    means rows are being silently zeroed under the live plan), or the plans
    price *different world sizes* (``mesh_changed`` — the elastic remesh
    path: the cost model's α·messages term depends on N, so a plan diffed
    across meshes always warrants a rebuild even if every method held).
    """
    leaf = lambda x: isinstance(x, ParamPlan)
    olds = {p.name: p for p in jax.tree.leaves(old.params, is_leaf=leaf)}
    flips, wire_flips, pspecs_changed = [], [], False
    for p in jax.tree.leaves(new.params, is_leaf=leaf):
        q = olds.get(p.name)
        if q is None:
            pspecs_changed = True
            continue
        if p.method != q.method:
            flips.append((p.name, q.method, p.method))
        if jnp.dtype(p.wire_dtype) != jnp.dtype(q.wire_dtype):
            wire_flips.append((p.name, jnp.dtype(q.wire_dtype).name,
                               jnp.dtype(p.wire_dtype).name))
        if tuple(p.pspec) != tuple(q.pspec) or \
                tuple(p.opt_pspec) != tuple(q.opt_pspec):
            pspecs_changed = True
    capacity_drifted = _drifted(old.capacity, new.capacity, capacity_drift)
    for t, cap in new.table_capacity.items():
        if t in old.table_capacity:
            capacity_drifted |= _drifted(old.table_capacity[t], cap,
                                         capacity_drift)
    capacity_grown = any(
        new.table_capacity.get(t, 0) > old.table_capacity.get(t, 0)
        for t in new.grown_tables)
    mesh_shape = lambda p: dict(p.mesh.shape) if p.mesh is not None else None
    mesh_changed = mesh_shape(old) != mesh_shape(new)
    # sync <-> stale transitions (the jitter fallback): the train step's
    # update rule for the flipped table changes, so the jit must re-trace
    stale_flips = [
        (t, t in old.stale_tables, t in new.stale_tables)
        for t in sorted(set(old.stale_tables) ^ set(new.stale_tables))]
    return {
        "changed": bool(flips) or bool(wire_flips) or pspecs_changed
                   or capacity_drifted or capacity_grown or mesh_changed
                   or bool(stale_flips),
        "mesh_changed": mesh_changed,
        "mesh": (mesh_shape(old), mesh_shape(new)),
        "rebuilt": False,             # set by the caller that acts on the diff
        "flips": flips,
        "wire_flips": wire_flips,
        "stale_flips": stale_flips,
        "pspecs_changed": pspecs_changed,
        "capacity_drifted": capacity_drifted,
        "capacity_grown": capacity_grown,
        "capacity": (old.capacity, new.capacity),
        "table_capacity": (dict(old.table_capacity),
                           dict(new.table_capacity)),
        "table_methods": (dict(old.table_methods), dict(new.table_methods)),
        "alpha": (old.alpha, new.alpha),
        "embed_method": (old.embed_method, new.embed_method),
        "buckets": (len(old.bucket_plan.buckets) if old.bucket_plan else 0,
                    len(new.bucket_plan.buckets) if new.bucket_plan else 0),
    }


def _fsdp_axes(mesh: Mesh, dense_strategy: str = "tp") -> tuple:
    axes = ("data", "model") if dense_strategy == "dp" else ("data",)
    return tuple(a for a in axes if a in mesh.axis_names)


def add_fsdp(pspec: P, shape: tuple, mesh: Mesh,
             dense_strategy: str = "tp") -> P:
    """ZeRO-3: additionally shard the largest free dim over the data axis."""
    fax = _fsdp_axes(mesh, dense_strategy)
    if not fax:
        return pspec
    size = math.prod(mesh.shape[a] for a in fax)
    entries = list(pspec) + [None] * (len(shape) - len(pspec))
    used = {a for e in entries if e for a in ((e,) if isinstance(e, str) else e)}
    if any(a in used for a in fax):
        return pspec
    # pick the largest unsharded, divisible dim
    best, best_dim = None, -1
    for i, (e, d) in enumerate(zip(entries, shape)):
        if e is None and d % size == 0 and d > best_dim:
            best, best_dim = i, d
    if best is None:
        return pspec
    entries[best] = fax if len(fax) > 1 else fax[0]
    return P(*entries)


def per_device_bytes(specs: Any, rules: MeshRules, plans: Any, dtype_bytes: int = 2,
                     opt_bytes: int = 8) -> float:
    """Rough params+optimizer per-chip bytes under the plan (for escalation)."""
    total = 0.0
    for spec, plan in zip(
        jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec)),
        jax.tree.leaves(plans, is_leaf=lambda x: isinstance(x, ParamPlan)),
    ):
        n = math.prod(spec.shape)
        shards = _pspec_shards(plan.pspec, rules.mesh)
        opt_shards = _pspec_shards(plan.opt_pspec, rules.mesh)
        total += n * dtype_bytes / shards + n * opt_bytes / opt_shards
    return total


def _pspec_shards(pspec: P, mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    s = 1
    for e in pspec:
        if e is None:
            continue
        for a in (e,) if isinstance(e, str) else e:
            s *= mesh.shape[a]
    return s
