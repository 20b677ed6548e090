"""Automatic graph transformation (paper §5) — the Parallax API.

Planning is a pipeline of pure stages so it can be re-entered at runtime
with *observed* (not estimated) workload parameters:

``estimate_census``  workload-model census (uniform/Zipf analytic α).
``choose_methods``   census -> Plan via the Table-3 cost model (incl. ZeRO
                     escalation under the per-chip memory budget).
``analyze``          the one-shot composition of the two (census optional —
                     pass an observed census to replan without rebuilding
                     the model).
``build_step``       the shared state/sharding/jit assembly used by both
                     ``get_runner`` and ``runtime.trainer.Trainer``.
``make_train_step`` / ``make_decode_step``
              build the distributed jit-ready step functions with
              in/out shardings derived from the plan. The correctness
              contract (paper §3.1): the distributed step computes exactly
              what the single-device step computes at equal global batch —
              asserted by tests/test_transform.py.
``get_runner`` the user-facing two-line API (paper Table 2 analogue);
              ``Runner.replan(census)`` hot-swaps the jitted step onto a
              plan recomputed from a measured census (paper §5's profile →
              re-optimize loop).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.compat import Mesh, NamedSharding, P
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core import buckets, cost_model, sparsity
from repro.core.plan import (MeshRules, ParamPlan, Plan, add_fsdp,
                             default_rules, per_device_bytes, plan_diff,
                             plan_leaves, _pspec_shards)
from repro.core.runtime import Runtime
from repro.models.layers import ParamSpec
from repro.models.model import Model, build_model
from repro.optim.optimizer import (Optimizer, TrainState, fuse_state,
                                   is_fused, make_optimizer, unfuse_state)
from repro.utils.hlo import FORWARD, OPTIMIZER
from repro.utils.tree import named_leaves, path_name as tree_path_name


def _mesh_dims(mesh: Optional[Mesh], rules: MeshRules) -> cost_model.MeshDims:
    if mesh is None:
        return cost_model.MeshDims()
    get = lambda a: mesh.shape.get(a, 1) if hasattr(mesh.shape, "get") else \
        (mesh.shape[a] if a in mesh.axis_names else 1)
    return cost_model.MeshDims(
        model=get("model") if "model" in mesh.axis_names else 1,
        data=get("data") if "data" in mesh.axis_names else 1,
        pod=get("pod") if "pod" in mesh.axis_names else 1,
        hosts=cost_model.mesh_hosts(mesh),
    )


def estimate_census(model: Model, rt: Runtime) -> sparsity.Census:
    """Stage 1: the build-time workload-model census (estimated α)."""
    dims = _mesh_dims(rt.mesh, rt.rules)
    return sparsity.run_census(model.specs(), rt.model_cfg, rt.shape_cfg,
                               rt.run_cfg, dims.replicas)


def analyze(model: Model, rt: Runtime,
            memory_budget: Optional[float] = None,
            census: Optional[sparsity.Census] = None,
            stale_tables: tuple = ()) -> Plan:
    """Census + cost model -> Plan (the paper's analysis phase).

    Pass ``census`` (e.g. an observed one from a SparsityProfile) to replan
    from measured sparsity; by default the workload-model estimate is used.
    ``stale_tables`` names sparse tables running the bounded-staleness push
    (the jitter fallback) — stamped onto the plan so the train step builds
    the stale update rule for exactly those tables. ``memory_budget``
    defaults to 90% of the chip's HBM.
    """
    if census is None:
        census = estimate_census(model, rt)
    return choose_methods(model, rt, census, memory_budget,
                          stale_tables=stale_tables)


def choose_methods(model: Model, rt: Runtime, census: sparsity.Census,
                   memory_budget: Optional[float] = None,
                   stale_tables: tuple = ()) -> Plan:
    """Stage 2: pure census -> Plan (Table-3 argmin + memory escalation)."""
    specs = model.specs()
    dims = _mesh_dims(rt.mesh, rt.rules)
    comm_mode = rt.run_cfg.comm_mode
    hw = cost_model.resolve_hw(rt.run_cfg)
    if memory_budget is None:
        memory_budget = 0.9 * hw.hbm_bytes

    can_shard_rows = rt.rules.axis_size("vocab") > 1
    strategy = getattr(rt, "resolved_strategy", rt.run_cfg.dense_strategy)
    table_methods: dict[str, str] = {}
    table_capacity: dict[str, int] = {}
    table_wire: dict[str, Any] = {}
    table_alpha: dict[str, float] = {}
    table_serve: dict[str, dict] = {}
    serving = rt.shape_cfg.kind == "decode"
    # bounded-staleness eligibility: only tables with their own sparse
    # exchange can defer their apply (dense-routed tables ride the
    # synchronous buckets by construction), and only when the machinery is
    # on at all (max_staleness > 0 allocates the state buffer)
    stale_requested = set(stale_tables) \
        if getattr(rt.run_cfg, "max_staleness", 0) > 0 else set()
    stale_stamped: set[str] = set()

    def _wire_for(name: str):
        """OPSW wire dtype for one parameter: the census's profiled hint
        (magnitude-census wire_dtype_hints) when present, else the global
        knob. Hints only matter when OPSW casting is on at all."""
        hint = census.wire_dtypes.get(name)
        if hint is not None and rt.run_cfg.opsw:
            return jnp.dtype(hint)
        return rt.wire_dtype

    def plan_leaf(name: str, spec: ParamSpec) -> ParamPlan:
        b = math.prod(spec.shape) * jnp.dtype(rt.param_dtype).itemsize
        # per-parameter pricing: each sparse table argmins at its *own*
        # activated fraction, so a Zipf vocab table and a near-dense
        # secondary table legitimately land on different methods
        alpha = census.alpha_for(name) if spec.sparse else census.alpha
        method, costs = cost_model.choose_method(
            b=b, sparse=spec.sparse, alpha=alpha, dims=dims,
            comm_mode=comm_mode, can_shard_rows=can_shard_rows, hw=hw)
        pspec = rt.rules.pspec(spec.axes, spec.shape)
        capacity = 0
        wire = _wire_for(name)
        if spec.sparse:
            capacity = census.capacity_for(name)
            if method in ("allreduce", "dense") and rt.mesh is not None \
                    and rt.run_cfg.capacity_mode == "capped":
                # near-dense tables routed to the dense path dedupe once over
                # the *global* batch (core/embedding.py lookup sizes its
                # buffer by ids.size there), so the per-replica Zipf estimate
                # misprices them — often undersized by ~N_replicas. Size
                # exactly: a global dedupe can never exceed global tokens or
                # the table's rows, and at that bound it never drops.
                capacity = min(rt.shape_cfg.tokens, spec.shape[0])
            table_methods[name] = method if rt.mesh is not None else "dense"
            table_capacity[name] = capacity
            table_wire[name] = wire
            table_alpha[name] = float(alpha)
            if serving:
                # serve-mesh pricing at decode batch shapes: the per-step
                # pull wire and per-token exchange seconds this table costs
                # the engine under its chosen method (one token per
                # sequence per decode step)
                table_serve[name] = cost_model.serve_table_pricing(
                    b=b, alpha=float(alpha), method=table_methods[name],
                    dims=dims, batch_tokens=rt.shape_cfg.global_batch,
                    hw=hw)
            if method in ("mpi_gatherv", "allreduce"):
                # table replicated (paper's MPI baseline / dense-AR pick)
                pspec = P(*([None] * len(spec.shape)))
        stale = bool(spec.sparse and name in stale_requested
                     and method in ("ps", "ps_gather", "mpi_gatherv"))
        if stale:
            stale_stamped.add(name)
        if method == "fsdp" and rt.mesh is not None:
            pspec = add_fsdp(pspec, spec.shape, rt.mesh, strategy)
        opt_pspec = pspec
        if rt.run_cfg.zero_stage >= 1 and rt.mesh is not None and not spec.sparse:
            opt_pspec = add_fsdp(pspec, spec.shape, rt.mesh, strategy)
        return ParamPlan(name=name, method=method, pspec=pspec,
                         opt_pspec=opt_pspec, wire_dtype=wire,
                         sparse=spec.sparse, bytes=int(b), capacity=capacity,
                         stale=stale, est_cost=costs)

    plans = jax.tree_util.tree_map_with_path(
        lambda path, s: plan_leaf(tree_path_name(path), s),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))

    # the "embed" table binds the legacy scalar; any sparse table otherwise
    embed_method = table_methods.get(
        "embed", next(iter(table_methods.values()), "dense"))
    plan = Plan(model_cfg=rt.model_cfg, run_cfg=rt.run_cfg,
                shape_cfg=rt.shape_cfg, mesh=rt.mesh, rules=rt.rules,
                params=plans, alpha=census.alpha, capacity=census.capacity,
                zero_stage=rt.run_cfg.zero_stage, embed_method=embed_method,
                table_methods=table_methods, table_capacity=table_capacity,
                table_wire=table_wire, table_alpha=table_alpha,
                table_serve=table_serve,
                grown_tables=tuple(sorted(
                    n for n, t in census.tables.items() if t.grown)),
                stale_tables=tuple(sorted(stale_stamped)))

    # ---- memory escalation: replicate -> ZeRO-1 -> ZeRO-3 (auto-PS) ----
    if rt.mesh is not None:
        for stage in (rt.run_cfg.zero_stage, 1, 3):
            bytes_est = per_device_bytes(specs, rt.rules, plan.params)
            if bytes_est <= memory_budget:
                break
            plan = _escalate(plan, specs, rt, stage if stage else 1)
        plan.zero_stage = max(plan.zero_stage, 0)
    # bucket the dense exchange (after escalation: fsdp flips veto it);
    # replans re-enter here, so the assignment always tracks the live plan
    buckets.plan_buckets(plan, rt)
    return plan


def _escalate(plan: Plan, specs, rt: Runtime, stage: int) -> Plan:
    """Raise the ZeRO stage: shard optimizer state (1) then params (3)."""
    strategy = getattr(rt, "resolved_strategy", rt.run_cfg.dense_strategy)

    def esc(spec: ParamSpec, p: ParamPlan) -> ParamPlan:
        if spec.sparse:
            return p
        new = p
        opt = add_fsdp(p.pspec, spec.shape, rt.mesh, strategy)
        new = replace(new, opt_pspec=opt)
        if stage >= 3 and p.method == "allreduce":
            new = replace(new, method="fsdp",
                          pspec=add_fsdp(p.pspec, spec.shape, rt.mesh, strategy),
                          opt_pspec=add_fsdp(p.pspec, spec.shape, rt.mesh,
                                             strategy))
        return new

    new_params = jax.tree.map(
        esc, specs, plan.params,
        is_leaf=lambda x: isinstance(x, (ParamSpec, ParamPlan)))
    plan.params = new_params
    plan.zero_stage = stage
    return plan


# ---------------------------------------------------------------------------
# shardings for state / batch
# ---------------------------------------------------------------------------

def _ns(mesh, pspec):
    return NamedSharding(mesh, pspec)


def param_shardings(plan: Plan):
    if plan.mesh is None:
        return None
    return jax.tree.map(lambda p: _ns(plan.mesh, p.pspec), plan.params,
                        is_leaf=lambda x: isinstance(x, ParamPlan))


def opt_shardings(plan: Plan):
    if plan.mesh is None:
        return None
    return jax.tree.map(lambda p: _ns(plan.mesh, p.opt_pspec), plan.params,
                        is_leaf=lambda x: isinstance(x, ParamPlan))


def state_shardings(plan: Plan, state_like: TrainState):
    """TrainState shardings (moments follow opt_pspec; ema follows param).

    Fused bucket-apply states (optim/optimizer.py ``fuse_state``) hold each
    moment as {"bucket": [f32 buffers, flat or, for a one-member bucket,
    in the leaf's shape], "leaf": per-param tree with None at bucketed
    positions}: the buffers are post-psum replicated values (fused apply
    needs zero_stage 0), so they shard as P() at any rank; the surviving
    unbucketed leaves keep their planned pspecs, and the None placeholders
    mirror over to the sharding tree (empty subtrees carry no sharding).
    """
    if plan.mesh is None:
        return None
    ps = param_shardings(plan)
    os = opt_shardings(plan)
    rep = _ns(plan.mesh, P())

    def moment(live, per_leaf):
        if live is None:
            return None
        if isinstance(live, dict) and set(live) == {"bucket", "leaf"}:
            shl, shdef = jax.tree_util.tree_flatten(per_leaf)
            for b in plan.bucket_plan.buckets:
                for i in b.idx:
                    shl[i] = None
            leaf = jax.tree_util.tree_unflatten(shdef, shl)
            return {"bucket": [rep] * len(live["bucket"]), "leaf": leaf}
        return per_leaf

    def stale_sh(stale_like):
        # staleness buffers: each table's "g" mirrors the table's param
        # sharding (it is a gradient-shaped buffer), "age" is a replicated
        # scalar — post-exchange grads are replica-identical, so the buffer
        # never needs its own collective
        if stale_like is None:
            return None
        by_name = {p.name: p.pspec for p in plan_leaves(plan.params)}
        return {n: {"g": _ns(plan.mesh, by_name[n]), "age": rep}
                for n in stale_like}

    return TrainState(
        step=rep,
        params=ps,
        m=moment(state_like.m, os),
        v=moment(state_like.v, os),
        ema=moment(state_like.ema, ps),
        stale=stale_sh(getattr(state_like, "stale", None)),
    )


def batch_shardings(plan: Plan, batch_specs: dict):
    if plan.mesh is None:
        return None
    ba = plan.rules.rules.get("batch")
    out = {}
    for k, v in batch_specs.items():
        spec = [ba] + [None] * (len(v.shape) - 1) if len(v.shape) else []
        out[k] = _ns(plan.mesh, P(*spec))
    return out


# ---------------------------------------------------------------------------
# bounded-staleness buffers (the jitter fallback's train-state leg)
# ---------------------------------------------------------------------------

def stale_buffer_tables(plan: Plan, rt: Runtime) -> tuple:
    """Tables that carry a staleness buffer in the train state: every
    sparse table with its own sparse exchange, whenever the machinery is on
    (``max_staleness > 0``). Deliberately independent of which tables are
    currently *flipped* stale — the buffer pytree stays structurally
    constant across sync<->stale flips, so checkpoints, sharding templates,
    and donation never churn with the jitter state."""
    if getattr(rt.run_cfg, "max_staleness", 0) <= 0:
        return ()
    return tuple(sorted(
        p.name for p in plan_leaves(plan.params)
        if p.sparse and p.method in ("ps", "ps_gather", "mpi_gatherv")))


def ensure_stale_buffers(state: TrainState, plan: Plan,
                         rt: Runtime) -> TrainState:
    """Attach (or drop) the staleness buffer pytree for a plan: zero f32
    grad buffers + int32 ages for every eligible table. Existing buffers
    whose shapes still match carry across (a replan/remesh mid-stale-window
    must not silently discard a buffered gradient); shape changes and
    de-listed tables re-zero."""
    names = stale_buffer_tables(plan, rt)
    old = getattr(state, "stale", None)
    if not names:
        return state._replace(stale=None) if old is not None else state
    by_idx = {p.name: i for i, p in enumerate(plan_leaves(plan.params))}
    pleaves = jax.tree_util.tree_leaves(state.params)
    old = old or {}
    new = {}
    for n in names:
        shape = tuple(pleaves[by_idx[n]].shape)
        o = old.get(n)
        if o is not None and tuple(np.shape(o["g"])) == shape:
            new[n] = o
        else:
            new[n] = {"g": jnp.zeros(shape, jnp.float32),
                      "age": jnp.zeros((), jnp.int32)}
    return state._replace(stale=new)


def _make_staleness_rule(plan: Plan, rt: Runtime) -> Callable:
    """The per-table gradient rewrite between exchange and optimizer:

      stale table:  apply the *buffered* (previous step's) exchanged
                    gradient, buffer the fresh one — the exchange itself
                    still runs every step, so every replica buffers the
                    same aggregate and the state stays replica-consistent;
      sync table:   apply fresh + buffered, zero the buffer — ordinary
                    steps add an exact zero, and the first step after a
                    stale->sync flip automatically drains the last buffered
                    gradient (no separate drain step to schedule).

    Emits ``staleness_age`` (max applied age over stale tables) and
    ``staleness_violation`` (sum of relu(age - max_staleness)) — the
    in-graph bound the acceptance contract asserts on."""
    stale_set = frozenset(getattr(plan, "stale_tables", ()))
    smax = int(getattr(rt.run_cfg, "max_staleness", 0))
    sparse_idx = {p.name: i for i, p in enumerate(plan_leaves(plan.params))
                  if p.sparse}

    def apply_rule(stale, grads, metrics):
        if stale is None:
            return None, grads, metrics
        gleaves, gtree = jax.tree_util.tree_flatten(grads)
        new_stale, ages = {}, []
        for name, buf in stale.items():
            i = sparse_idx[name]
            g = gleaves[i]
            if name in stale_set:
                age = buf["age"] + 1
                ages.append(age)
                gleaves[i] = buf["g"].astype(g.dtype)
                new_stale[name] = {"g": g.astype(jnp.float32),
                                   "age": jnp.zeros((), jnp.int32)}
            else:
                gleaves[i] = (g.astype(jnp.float32)
                              + buf["g"]).astype(g.dtype)
                new_stale[name] = {"g": jnp.zeros_like(buf["g"]),
                                   "age": jnp.zeros((), jnp.int32)}
        if ages:
            age_max = ages[0]
            for a in ages[1:]:
                age_max = jnp.maximum(age_max, a)
            metrics["staleness_age"] = age_max
            metrics["staleness_violation"] = sum(
                jnp.maximum(a - smax, 0) for a in ages)
        return (new_stale,
                jax.tree_util.tree_unflatten(gtree, gleaves), metrics)

    return apply_rule


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def make_train_step(model: Model, optimizer: Optimizer, rt: Runtime,
                    plan: Plan) -> Callable:
    """(state, batch) -> (state, metrics); grads flow through the plan.

    With a bucket plan, loss+grad run inside core/buckets.py's manual
    exchange region: dense gradients arrive pre-aggregated over a few fused
    collectives (already at the wire dtype — the OPSW cast lives in the
    exchange), and the optimizer consumes them per-tensor as always — or,
    when the plan stamps ``fused_apply``, bucket-natively: the exchange also
    hands back the post-psum bucket buffers and ``optimizer.update_fused``
    applies straight from them against the fused state layout.
    """
    stale_rule = _make_staleness_rule(plan, rt)
    heartbeat = bool(getattr(rt.run_cfg, "heartbeat", False))
    if plan.bucket_plan is not None:
        if getattr(plan, "fused_apply", False) \
                and optimizer.update_fused is None:
            plan.fused_apply = False      # e.g. sgd: per-param only
        value_and_grad = buckets.make_bucketed_value_and_grad(model, rt, plan)
        if plan.fused_apply:
            bp = plan.bucket_plan

            def train_step_fused(state: TrainState, batch: dict):
                (loss, metrics), grads, bufs = value_and_grad(
                    state.params, batch)
                metrics = dict(metrics)
                new_stale, grads, metrics = stale_rule(
                    getattr(state, "stale", None), grads, metrics)
                with jax.named_scope(OPTIMIZER):
                    new_state, opt_metrics = optimizer.update_fused(
                        state, grads, bufs, bp)
                new_state = new_state._replace(stale=new_stale)
                metrics.update(opt_metrics)
                metrics["loss"] = loss
                return new_state, metrics

            return train_step_fused
    else:
        def loss_fn(params, batch):
            with jax.named_scope(FORWARD):
                return model.loss_fn(params, batch)

        def value_and_grad(params, batch):
            out, grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            # OPSW: dense grads ride collectives at each parameter's planned
            # wire dtype (profiled per-bucket magnitude census can pin
            # outlier-prone parameters to f32). In global semantics the
            # aggregation psum is XLA-inserted at the dtype the gradient
            # tensors carry — so cast before the constraint boundary.
            if rt.run_cfg.opsw:
                grads = jax.tree.map(
                    lambda g, p: g.astype(p.wire_dtype)
                    if g.dtype == jnp.float32 else g, grads, plan.params)
            return out, grads

    unbucketed_hb = heartbeat and plan.bucket_plan is None

    def train_step(state: TrainState, batch: dict):
        hb = None
        if unbucketed_hb:
            # no manual region to one-hot-encode in: the global-semantics
            # heartbeat vector is already per-slot, echo it as metrics
            batch = dict(batch)
            hb = batch.pop("_heartbeat", None)
        (loss, metrics), grads = value_and_grad(state.params, batch)
        metrics = dict(metrics)
        new_stale, grads, metrics = stale_rule(
            getattr(state, "stale", None), grads, metrics)
        with jax.named_scope(OPTIMIZER):
            new_state, opt_metrics = optimizer.update(state, grads)
        new_state = new_state._replace(stale=new_stale)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        if hb is not None:
            for j in range(hb.shape[0]):
                metrics[f"heartbeat{j}"] = hb[j]
        return new_state, metrics

    return train_step


def make_decode_step(model: Model, rt: Runtime, plan: Plan) -> Callable:
    def decode_step(params, cache, tokens, cache_len):
        logits, new_cache = model.decode_fn(params, cache, tokens, cache_len)
        return logits, new_cache
    return decode_step


def make_prefill_step(model: Model, rt: Runtime, plan: Plan) -> Callable:
    def prefill_step(params, batch):
        logits, cache, _ = model.prefill_fn(params, batch)
        return logits, cache
    return prefill_step


# ---------------------------------------------------------------------------
# serving steps (runtime/server.py) — batched prefill + slot-paged decode
# ---------------------------------------------------------------------------

def sample_tokens(logits, *, greedy: bool, temperature: float, key):
    """Device-side sampling: (B, V) logits -> (B,) int32 token ids.

    Greedy argmax or temperature-scaled categorical — inside the jitted
    step, so the decode loop never round-trips logits through the host.
    """
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = max(float(temperature), 1e-4)
    return jax.random.categorical(
        key, logits.astype(jnp.float32) / t, axis=-1).astype(jnp.int32)


def make_serve_prefill_step(model: Model, rt: Runtime, plan: Plan, *,
                            greedy: bool = True, temperature: float = 1.0
                            ) -> Callable:
    """Batched prefill for one admitted request: a single dispatch that

      1. runs the full forward over the (bucket-padded) prompt, collecting
         every layer's K/V (``model.prefill_cache_fn``),
      2. inserts those rows into the live decode cache at the request's slot
         (rows past the true length carry pad K/V — the per-slot length
         masks them out of every later attention),
      3. samples the first generated token from the last prompt position
         (device-side — the request's TTFT token), and
      4. sets the slot's length and pending-token state.

    jit this once per power-of-two prompt-length bucket: the padded token
    shape is the only shape that varies, so two prompts in the same bucket
    share one executable.
    """
    if model.prefill_cache_fn is None:
        raise ValueError(
            f"family {model.cfg.family!r} has no positional KV cache; "
            "batched prefill is undefined under padding (use the decode "
            "loop for recurrent families)")

    def prefill_step(params, cache, lens, tok, tokens, length, slot, key):
        # tokens (1, Lb) pad-right; length, slot scalars; cache the live
        # (n_layers, B, S, KV, hd) decode cache; lens (B,); tok (B, 1)
        if rt.mesh is not None:
            # batch-sharded lookups (ps shard_map) need the batch divisible
            # by the data axis: run the forward at the full decode width —
            # every row computes the same prompt, row 0 is consumed below
            tokens = jnp.broadcast_to(
                tokens, (lens.shape[0],) + tokens.shape[1:])
        logits, kv = model.prefill_cache_fn(params, tokens)
        logits = logits[:1]
        kv = jax.tree.map(
            lambda p: jax.lax.slice_in_dim(p, 0, 1, axis=1), kv)
        last = jax.lax.dynamic_slice_in_dim(
            logits, length - 1, 1, axis=1)[:, 0, :]          # (1, Vp)
        nxt = sample_tokens(last, greedy=greedy,
                            temperature=temperature, key=key)  # (1,)

        def insert(c, p):
            start = (jnp.zeros_like(slot), slot) + \
                (jnp.zeros_like(slot),) * (c.ndim - 2)
            return jax.lax.dynamic_update_slice(c, p.astype(c.dtype), start)

        new_cache = jax.tree.map(insert, cache, kv)
        new_lens = jax.lax.dynamic_update_slice(
            lens, length[None].astype(lens.dtype), (slot,))
        new_tok = jax.lax.dynamic_update_slice(
            tok, nxt[:, None], (slot, jnp.zeros_like(slot)))
        return new_cache, new_lens, new_tok, nxt

    return prefill_step


def make_serve_decode_step(model: Model, rt: Runtime, plan: Plan, *,
                           max_seq: int, greedy: bool = True,
                           temperature: float = 1.0) -> Callable:
    """One slot-paged decode step over the whole batch.

    Per-slot state lives on device: ``lens`` (B,) is each slot's position
    (threaded into ``model.decode_fn`` — per-row KV write + per-slot
    attention masking), ``tok`` (B,1) is each slot's pending token (fed
    straight from the previous step's device-side sample — no host argmax
    round-trip). ``active`` is the host's (B,) occupancy mask: inactive
    slots neither advance their length nor replace their token, so a
    completed-but-not-yet-reused slot idles in place until the next prefill
    overwrites it. Returns sampled tokens with inactive slots as -1 (the
    detokenizer's cross-slot sanity marker).
    """
    def decode_step(params, cache, lens, tok, active, key):
        logits, new_cache = model.decode_fn(params, cache, tok, lens)
        nxt = sample_tokens(logits[:, -1, :], greedy=greedy,
                            temperature=temperature, key=key)   # (B,)
        act = active & (lens > 0)
        new_tok = jnp.where(act[:, None], nxt[:, None], tok)
        new_lens = jnp.where(act, jnp.minimum(lens + 1, max_seq), lens)
        out_tok = jnp.where(act, nxt, -1)
        return new_cache, new_lens, new_tok, out_tok

    return decode_step


# ---------------------------------------------------------------------------
# step assembly (shared by get_runner / Trainer._build / replan)
# ---------------------------------------------------------------------------

def build_step(model: Model, optimizer: Optimizer, rt: Runtime, plan: Plan,
               state: Optional[TrainState] = None, *, seed: int = 0
               ) -> tuple[Callable, TrainState, Any]:
    """Assemble (jitted train step, state, shardings) for a plan.

    ``state=None``: fresh init from ``seed``. An existing ``state`` (device
    or host arrays — e.g. the elastic remesh/replan paths) is the sharding
    template itself (no throwaway init) and is device_put onto the plan's
    shardings — a no-op when the placement is already current, a reshard
    otherwise. Incoming state must be in the canonical per-param layout
    (callers unfuse before handing it over); when the plan stamps
    ``fused_apply`` the optimizer memory is re-laid out per bucket here.
    """
    if getattr(rt.run_cfg, "kernel_autotune", False):
        from repro.kernels import autotune
        autotune.ensure_for_plan(plan, rt, model.specs())
    step_fn = make_train_step(model, optimizer, rt, plan)
    if state is None:
        state = optimizer.init(model.init(jax.random.key(seed)))
    # staleness buffers live on the canonical per-param state: attach/carry/
    # drop them for THIS plan before any fused re-layout
    state = ensure_stale_buffers(state, plan, rt)
    if getattr(plan, "fused_apply", False):
        state = fuse_state(state, plan.bucket_plan)
    state_like = state
    if plan.mesh is not None:
        # every sharding below names the mesh explicitly, so the pjit path
        # needs no ambient mesh; use_mesh gives callers who didn't wrap the
        # builder the set_mesh placement semantics.
        with compat.use_mesh(plan.mesh):
            shardings = state_shardings(plan, state_like)
            state = jax.device_put(state, shardings)
            bs = batch_shardings(plan, model.input_specs())
            if getattr(rt.run_cfg, "heartbeat", False) and bs is not None:
                ba = plan.rules.rules.get("batch")
                bs["_heartbeat"] = _ns(plan.mesh, P(ba))
            step = jax.jit(step_fn, in_shardings=(shardings, bs),
                           out_shardings=(shardings, None), donate_argnums=0)
            if getattr(rt.run_cfg, "verify_contract", False):
                # debug gate: every build — fresh, replan, or remesh —
                # must compile to the plan's collective contract before a
                # single step runs (analysis/contract.py). The compile is
                # cached, so the first step reuses it.
                from repro.analysis.contract import verify_step_contract
                verify_step_contract(
                    plan, step.lower(state, _abstract_batch(model, rt))
                    .compile().as_text())
    else:
        shardings = None
        step = jax.jit(step_fn, donate_argnums=0)
    return step, state, shardings


def _abstract_batch(model: Model, rt: Runtime) -> dict:
    """Global-shape ShapeDtypeStructs for lowering a step without data."""
    specs = dict(model.input_specs())
    if getattr(rt.run_cfg, "heartbeat", False):
        specs["_heartbeat"] = jax.ShapeDtypeStruct((rt.replicas,),
                                                   jnp.float32)
    return specs


def apply_replan(model: Model, optimizer: Optimizer, rt: Runtime,
                 new_plan: Plan, state: TrainState, diff: dict
                 ) -> tuple[Callable, TrainState, Any]:
    """Hot-swap to ``new_plan``: rebuild the jitted step, reshard state.

    The one shared swap sequence under Runner.replan and
    Trainer.maybe_replan: state moves device-to-device when pspecs are
    unchanged and through a host round-trip when they moved (the
    version-portable elastic path). Marks ``diff['rebuilt']``.
    """
    old_plan = rt.plan
    if is_fused(state):
        # migrate fused optimizer memory through the canonical per-param
        # layout: the OLD plan's bucket layout unfuses it, the new plan's
        # (possibly regrouped) layout re-fuses inside build_step
        state = unfuse_state(
            state, old_plan.bucket_plan if old_plan is not None else None)
    rt.plan = new_plan            # model fns read the plan at trace time
    if diff["pspecs_changed"] and new_plan.mesh is not None:
        state = jax.tree.map(
            lambda a: None if a is None else np.asarray(jax.device_get(a)),
            state)
    step, state, shardings = build_step(model, optimizer, rt, new_plan,
                                        state)
    diff["rebuilt"] = True
    return step, state, shardings


# ---------------------------------------------------------------------------
# the two-line user API (paper Table 2)
# ---------------------------------------------------------------------------

@dataclass
class Runner:
    model: Model
    optimizer: Optimizer
    plan: Plan
    rt: Runtime
    train_step: Callable          # jitted
    state: TrainState
    shardings: Any = None         # TrainState of NamedShardings (None off-mesh)

    def run(self, batch) -> dict:
        self.state, metrics = self.train_step(self.state, batch)
        return metrics

    def replan(self, census: sparsity.Census, *, force: bool = False,
               capacity_drift: float = 1.5) -> dict:
        """Hot-swap the plan/step from a (typically observed) census.

        Recomputes the Plan through the same pure stages as build time. If
        nothing material changed (no method flip, no pspec change, capacity
        within ``capacity_drift``x) the live step is kept untouched unless
        ``force``. State reshards in place: device-to-device when only the
        jitted step changes, through a host round-trip when pspecs moved
        (the version-portable elastic path). Returns the plan diff.
        """
        new_plan = analyze(self.model, self.rt, census=census,
                           stale_tables=getattr(self.plan, "stale_tables",
                                                ()))
        diff = plan_diff(self.plan, new_plan, capacity_drift)
        if not (diff["changed"] or force):
            return diff
        self.plan = new_plan
        self.train_step, self.state, self.shardings = apply_replan(
            self.model, self.optimizer, self.rt, new_plan, self.state, diff)
        return diff

    def check_contract(self, *, strict_dtype: bool = False) -> list:
        """On-demand plan-contract check of the live step: lower/compile
        against abstract inputs and diff the collectives against the
        current plan (analysis/contract.py). Returns findings (empty =
        the compiled step implements the plan)."""
        from repro.analysis.contract import check_contract
        if self.plan.mesh is None:
            return []          # off-mesh: no collectives to contract
        with compat.use_mesh(self.plan.mesh):
            txt = self.train_step.lower(
                self.state, _abstract_batch(self.model, self.rt)) \
                .compile().as_text()
        return check_contract(self.plan, txt, strict_dtype=strict_dtype)


def get_runner(model_cfg: ModelConfig, shape_cfg: ShapeConfig,
               run_cfg: RunConfig = RunConfig(),
               mesh: Optional[Mesh] = None, seed: int = 0) -> Runner:
    """Transform a single-device model into a distributed runner."""
    rt = Runtime(model_cfg, run_cfg, shape_cfg, mesh=mesh)
    model = build_model(model_cfg, rt)
    plan = analyze(model, rt)
    rt.plan = plan
    optimizer = make_optimizer(rt)
    step, state, shardings = build_step(model, optimizer, rt, plan, seed=seed)
    return Runner(model=model, optimizer=optimizer, plan=plan, rt=rt,
                  train_step=step, state=state, shardings=shardings)
