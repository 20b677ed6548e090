"""Bucketed dense-gradient exchange (Horovod-style tensor fusion, in JAX).

The hybrid plan minimizes *bytes* on the wire, but under global semantics
XLA materializes one all-reduce per gradient tensor at its producing op —
so a model with n dense parameters pays n per-message latencies (the α in
α + β·b, see core/cost_model.py) however small the tensors are. GSPMD has
no "unreduced" value state, so no downstream concatenation can merge those
collectives; the only place the exchange can be fused is *before* XLA ever
sees a global gradient.

This module therefore traces loss+grad inside one full-manual ``shard_map``
over the mesh: inside, gradients are per-replica partials and aggregation
is written explicitly —

  * dense (method == allreduce) gradients are flattened into a few flat
    wire-dtype buffers of at most ``RunConfig.bucket_bytes`` each, grouped
    by (method, exchange dtype, pspec); each buffer rides ONE psum. A
    bucket with one member — every leaf larger than ``bucket_bytes`` sits
    alone — keeps that leaf's shape instead (``Bucket.shaped``): it has
    nothing to concatenate, and on the TPU the flat layout of a tiled 2-D
    leaf is a physical relayout, not a bitcast. Its wire buffer, its
    post-psum buffer and its fused optimizer state stay in the leaf's
    shape; the two-level schedule, which pads and scatters a flat buffer,
    leaves it flat, and the ``overlap=False`` pin flattens its wire buffer,
  * the loss and every scalar metric ride a single fused scalar psum,
  * the sparse push keeps its own schedule: the embedding custom_vjp runs
    its per-device body directly on the live named axes (EmbedCtx.manual).

Overlap (RunConfig.overlap, default on): buckets are assigned in
*reverse-topological* order — greedy first-fit over the reversed parameter
flatten order, so bucket 0 holds the last-forward parameters whose
gradients the backward pass produces FIRST — and each bucket's fused psum
is issued inside the backward graph itself, at the point its last member
gradient is produced. The mechanism is a ``jax.custom_vjp`` identity "tap"
around each bucket's parameters: the forward is the identity, and the
backward performs the bucket's flatten → scale → cast → psum → slice-back
exchange on the incoming cotangents before handing them on. That places
the collective at the gradient-readiness frontier of the autodiff graph,
so the scheduler can run it concurrently with the rest of the backward —
tests/test_perf_paths.py asserts the first bucket's all-reduce is
scheduled before the final gradient op. ``overlap=False`` pins every
bucket collective strictly after the full backward (a data-dependence
pin: one element of every gradient leaf rides each bucket's psum input
and is sliced off after) — the regression baseline. Both paths compute
bit-identical values: the exchange is an elementwise psum, so grouping
and issue order never change the math.

Multi-host meshes (MeshDims.hosts > 1, fitted inter-tier constants): a
bucket whose cost-model argmin prefers it rides a *two-level* schedule —
intra-host reduce-scatter, inter-host all-reduce of the 1/L shard,
intra-host all-gather — instead of one flat psum, provided the mesh
exposes the host tier as the leading "pod" batch axis.

Applicability (``bucketable``): pure data-parallel meshes — every mesh axis
that is not a batch axis has size 1, every dense parameter exchanges by
all-reduce, and the model opens no nested shard_map of its own (MoE EP
does). Anywhere else ``assign_buckets`` returns None and the planner keeps
the per-tensor global-semantics path. Correctness contract: the bucketed
step computes what the unbucketed step computes (same plan, same math;
summation order differs only within float tolerance) — tests/test_perf_paths
asserts the 3-step trajectory at f32 and the collective-count drop in HLO.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.compat import P, shard_map
from repro.core import cost_model, embedding
from repro.core.plan import ParamPlan, Plan, plan_leaves
from repro.core.runtime import manual_region
from repro.utils.hlo import EXCHANGE, FORWARD
from repro.utils.roofline import HW, device_hw


def _plan_leaves(plan: Plan) -> list[ParamPlan]:
    # the one flatten order bucket indices are defined against — shared with
    # the trainer's wire_dtype_hints param_names via core/plan.plan_leaves
    return plan_leaves(plan.params)


def _effective_pspec(pspec, mesh) -> tuple:
    """Pspec with size-1 mesh axes dropped — the *physical* layout. Two
    parameters whose pspecs differ only in size-1 axes shard identically,
    so their flattened gradients can share a fused buffer."""
    out = []
    for e in pspec:
        axes = (e,) if isinstance(e, str) else tuple(e or ())
        axes = tuple(a for a in axes if mesh.shape[a] > 1)
        out.append(axes[0] if len(axes) == 1 else (axes or None))
    return tuple(x for x in out if x is not None)


@dataclass(frozen=True)
class Bucket:
    key: tuple        # (method, wire dtype name, pspec entries) group key
    idx: tuple        # leaf positions in the flattened grads/plan tree —
                      # reverse-topological: bucket 0 holds the last-forward
                      # (first-backward) parameters
    sizes: tuple      # element count per member
    nbytes: int       # fused buffer wire bytes
    schedule: str = "ring"     # ring | two_level (cost_model argmin)

    @property
    def shaped(self) -> bool:
        """Does this bucket keep its member leaf's own shape end to end?
        A one-member ring bucket has nothing to concatenate, and on the TPU
        a flat layout of a tiled 2-D leaf is a physical relayout, not a
        bitcast — so its wire buffer, its post-psum buffer and its fused
        optimizer state all stay in the leaf's shape."""
        return len(self.idx) == 1 and self.schedule == "ring"


@dataclass
class BucketPlan:
    buckets: list
    batch_axes: tuple      # the manual/psum axes of the exchange
    replicas: int          # N: product of the batch axis sizes
    n_params: int          # bucketed gradient tensors
    wire_bytes: int        # sum of fused buffer bytes
    bucket_bytes: int      # the RunConfig knob that sized the buckets
    hw: Any = None         # the hardware model the planner priced against
    hosts: int = 1         # H: host groups among the replicas
    overlap: bool = True   # issue each bucket's psum at grad readiness
    n_sparse_push: int = 0  # gatherv tables with their own row-buffer push

    @property
    def dims(self) -> cost_model.MeshDims:
        return cost_model.MeshDims(data=self.replicas, hosts=self.hosts)

    def stats(self, hw=None) -> dict:
        """Exchange accounting for runtime/monitor.py — the cost-model view
        of what bucketing saved (per step, dense push only), priced with
        the same hardware model the planner's argmin used. Each bucket is
        priced at its chosen execution schedule; the unbucketed reference
        is one flat ring per member tensor."""
        hw = hw or self.hw or device_hw()
        dims = self.dims
        ring = 2.0 * (self.replicas - 1) / max(self.replicas, 1)
        tier = cost_model.span_tier(dims, hw)
        est = 0.0
        for b in self.buckets:
            secs = cost_model.dense_schedule_seconds(b.nbytes, dims, hw)
            est += secs.get(b.schedule, secs["ring"])
        return {
            "n_buckets": len(self.buckets),
            "n_params_bucketed": self.n_params,
            "n_collectives_dense": len(self.buckets),
            "n_collectives_unbucketed": self.n_params,
            "n_two_level": sum(1 for b in self.buckets
                               if b.schedule == "two_level"),
            # one-member ring buckets, exchanged and applied in their
            # member leaf's shape (Bucket.shaped)
            "n_shaped_buckets": sum(1 for b in self.buckets if b.shaped),
            "hosts": self.hosts,
            "overlap": self.overlap,
            # sparse row-buffer pushes issued at gradient readiness inside
            # the backward (overlap=False defers them post-backward)
            "n_overlapped_sparse": self.n_sparse_push if self.overlap else 0,
            "wire_bytes": self.wire_bytes,
            "bucket_bytes": self.bucket_bytes,
            "est_seconds": est,
            "est_seconds_unbucketed": cost_model.exchange_seconds(
                ring * self.wire_bytes, self.n_params, hw, tier=tier),
        }

    def expected_collectives(self, n_leaves: int = 0,
                             overlap: bool | None = None) -> list:
        """The dense-exchange collective contract per bucket, as
        (kind, element-count) pairs in issue order — what the compiled
        step's ENTRY schedule must contain for this plan. Element counts
        (not bytes) because the CPU dry-run upcasts bf16 wires to f32 in
        HLO while the counts survive unchanged.

        ``n_leaves``: total gradient leaves in the step — the
        ``overlap=False`` pin appends one element per leaf to every
        bucket's psum input (see ``_exchange_bucket``), so the observed
        collectives grow by exactly that much when overlap is off.
        ``overlap`` overrides the plan's own mode — the contract checker
        uses the flipped variant to recognize (and report) a step compiled
        under the wrong schedule instead of failing to match at all."""
        if overlap is None:
            overlap = self.overlap
        pin = 0 if overlap else n_leaves
        out = []
        for k, b in enumerate(self.buckets):
            elems = sum(b.sizes) + pin
            if b.schedule == "two_level":
                local = max(self.dims.local_replicas, 1)
                padded = elems + ((-elems) % local)
                colls = [("reduce-scatter", padded // local),
                         ("all-reduce", padded // local),
                         ("all-gather", padded)]
            else:
                colls = [("all-reduce", elems)]
            out.append({"bucket": k, "dtype": b.key[1],
                        "schedule": b.schedule, "collectives": colls})
        return out


def _exchange_dtype(rt, p: Optional[ParamPlan] = None) -> Any:
    """The dtype a dense gradient rides the wire at — mirrors the OPSW cast
    in the unbucketed step (f32 grads drop to the parameter's planned wire
    dtype; everything else ships as-is). Per-parameter: the magnitude-census
    hints can pin individual parameters to f32, and the bucket group key
    includes this dtype so buckets never mix wire precisions."""
    d = jnp.dtype(rt.param_dtype)
    if rt.run_cfg.opsw and d == jnp.dtype(jnp.float32):
        return jnp.dtype(p.wire_dtype) if p is not None else rt.wire_dtype
    return d


def bucketable(plan: Plan, rt) -> bool:
    """Can this plan's dense exchange run as a manual bucketed region?"""
    if plan.mesh is None or rt.run_cfg.bucket_bytes <= 0:
        return False
    if rt.shape_cfg.kind != "train":
        return False
    ba = tuple(rt.batch_axes)
    if not ba or rt.replicas <= 1:
        return False
    # the loss must trace collective-free per replica: no TP/SP/EP axis may
    # be live (the model would need manual collectives this module doesn't
    # write), and MoE opens a nested shard_map of its own.
    for a in plan.mesh.axis_names:
        if a not in ba and plan.mesh.shape[a] != 1:
            return False
    if rt.model_cfg.n_experts > 0:
        return False
    for p in _plan_leaves(plan):
        if not p.sparse and p.method != "allreduce":
            return False          # fsdp pull/push needs its own manual path
        if p.sparse and p.method not in ("allreduce", "mpi_gatherv", "dense"):
            return False          # ps variants need model-axis shards anyway
    return True


def assign_buckets(plan: Plan, rt) -> Optional[BucketPlan]:
    """Group dense all-reduce parameters into fused exchange buffers.

    Greedy first-fit in *reverse* tree-flatten order — reverse-topological
    by the backward pass: the last-forward parameters produce their
    gradients first, so bucket 0 fills (and its collective becomes
    issuable) earliest in the backward. A parameter joins the open bucket
    of its (method, exchange dtype, pspec) group until the bucket reaches
    ``RunConfig.bucket_bytes``, then a new one opens. Sparse parameters
    whose argmin picked a sparse method keep their own exchange. On
    multi-host meshes each bucket also gets its execution schedule
    (ring vs two-level) from the cost-model argmin.

    The tied-embedding coherence rule: under a manual region a gatherv'd
    table gradient would mix a replica-summed sparse part with a local
    dense part (the tied head matmul) — unscalable by one factor. The
    planner resolves it by flipping such tables to the dense bucket
    (pspec is already replicated for mpi_gatherv, so only the method moves).
    """
    if not bucketable(plan, rt):
        return None
    if rt.model_cfg.tie_embeddings and plan.embed_method == "mpi_gatherv":
        def untie(p: ParamPlan):
            if p.sparse and p.method == "mpi_gatherv":
                p.method = "allreduce"
                plan.table_methods[p.name] = "allreduce"
            return p
        jax.tree.map(untie, plan.params,
                     is_leaf=lambda x: isinstance(x, ParamPlan))
        plan.embed_method = "allreduce"

    groups: dict[tuple, list] = {}
    leaves = list(enumerate(_plan_leaves(plan)))
    for i, p in reversed(leaves):        # reverse-topological: see docstring
        if p.method != "allreduce":
            continue
        itemsize = jnp.dtype(_exchange_dtype(rt, p)).itemsize
        cap = max(int(rt.run_cfg.bucket_bytes), itemsize)
        n = p.bytes // jnp.dtype(rt.param_dtype).itemsize
        key = (p.method, jnp.dtype(_exchange_dtype(rt, p)).name,
               _effective_pspec(p.pspec, plan.mesh))
        open_buckets = groups.setdefault(key, [[]])
        if open_buckets[-1] and \
                sum(s for _, s, _ in open_buckets[-1]) * itemsize + \
                n * itemsize > cap:
            open_buckets.append([])
        open_buckets[-1].append((i, n, None))

    hw = cost_model.resolve_hw(rt.run_cfg)
    hosts = cost_model.mesh_hosts(plan.mesh)
    batch_axes = tuple(rt.batch_axes)
    dims = cost_model.MeshDims(data=rt.replicas, hosts=hosts)
    # the two-level schedule needs the host tier as an actual mesh axis to
    # split the psum on: the leading "pod" batch axis (the layout
    # make_production_mesh uses for multi-host worlds)
    can_two_level = (hw.hierarchical and hosts > 1 and len(batch_axes) >= 2
                     and batch_axes[0] == "pod")
    buckets = []
    for key, bs in groups.items():
        itemsize = jnp.dtype(key[1]).itemsize
        for members in bs:
            if not members:
                continue
            idx = tuple(i for i, _, _ in members)
            sizes = tuple(s for _, s, _ in members)
            nbytes = sum(sizes) * itemsize
            schedule = "ring"
            if can_two_level:
                schedule, _ = cost_model.choose_dense_schedule(
                    nbytes, dims, hw)
            buckets.append(Bucket(key=key, idx=idx, sizes=sizes,
                                  nbytes=nbytes, schedule=schedule))
    if not buckets:
        return None
    return BucketPlan(
        buckets=buckets, batch_axes=batch_axes,
        replicas=rt.replicas, n_params=sum(len(b.idx) for b in buckets),
        wire_bytes=sum(b.nbytes for b in buckets),
        bucket_bytes=int(rt.run_cfg.bucket_bytes),
        hw=hw, hosts=hosts,
        overlap=bool(getattr(rt.run_cfg, "overlap", True)),
        n_sparse_push=sum(1 for _, p in leaves
                          if p.sparse and p.method == "mpi_gatherv"))


def fused_apply_eligible(plan: Plan, rt) -> bool:
    """Can the optimizer apply run bucket-natively (optim/optimizer.py
    ``update_fused``)? Needs the bucketed exchange (flat post-psum buffers
    exist), an optimizer with a fused path, replicated optimizer state
    (zero_stage 0 — the flat buffer has no per-leaf dims to ZeRO-shard),
    and OPAU on (the fused global-norm is the partial-sum form)."""
    return bool(plan.bucket_plan is not None
                and getattr(rt.run_cfg, "fused_apply", True)
                and rt.run_cfg.optimizer in ("adamw", "momentum")
                and rt.run_cfg.zero_stage == 0
                and rt.run_cfg.opau)


def plan_buckets(plan: Plan, rt) -> None:
    """Planner hook: (re)compute the bucket assignment for a plan in place.
    Runs after memory escalation so method flips to fsdp veto bucketing;
    re-runs on every replan so the assignment tracks the live plan. Also
    stamps fused-apply eligibility — the optimizer-state layout is part of
    the plan, so replans/remeshes migrate fused state deliberately."""
    plan.bucket_plan = assign_buckets(plan, rt)
    plan.fused_apply = fused_apply_eligible(plan, rt)


# ---------------------------------------------------------------------------
# the fused exchange step
# ---------------------------------------------------------------------------

def _two_level_psum(buf, batch_axes: tuple, local: int):
    """Two-level dense exchange for one flat buffer: intra-host
    reduce-scatter, inter-host all-reduce of the 1/L shard, intra-host
    all-gather. ``batch_axes[0]`` is the host tier ("pod"); the remaining
    axes are the L (= ``local``) intra-host replicas. Elementwise-identical
    to one flat psum — only b/L bytes ever cross the slow tier."""
    inter, intra = batch_axes[0], tuple(batch_axes[1:])
    n = buf.shape[0]
    pad = (-n) % local
    if pad:
        buf = jnp.concatenate([buf, jnp.zeros((pad,), buf.dtype)])
    piece = jax.lax.psum_scatter(buf, intra, scatter_dimension=0, tiled=True)
    piece = jax.lax.psum(piece, inter)
    out = jax.lax.all_gather(piece, intra, axis=0, tiled=True)
    return out[:n] if pad else out


@jax.named_scope(EXCHANGE)
def _exchange_bucket(b: Bucket, gparts: list, scale: float, bp: BucketPlan,
                     census: bool, pin=None):
    """The fused exchange for ONE bucket: flatten → 1/N scale → census →
    wire-dtype cast → psum (ring or two-level) → slice back. ``gparts`` are
    the members' local gradient leaves; returns (exchanged leaves cast back
    to the member dtypes, (|g|inf, rms) census scalars or None, the
    post-psum wire buffer — the fused bucket-apply path feeds it to the
    optimizer directly, pin excluded).

    A shaped bucket (``Bucket.shaped``: one member, ring schedule) skips the
    flatten and the slice-back: it scales, casts and psums in the leaf's own
    shape, and its post-psum buffer is the exchanged leaf's shape. The
    ``overlap=False`` pin is appended to a flat buffer, so there even a
    shaped bucket flattens (the scheduling baseline, not the hot path).

    The census reads what rides the wire, pre-cast; downstream the scalars
    join the fused metrics psum so the host sees the replica-*mean* of the
    per-replica maxima — a profile signal for wire-dtype selection
    (sparsity.wire_dtype_hints), not an exact global max.

    ``pin`` (overlap=False): a small vector appended to the psum input and
    sliced off after — a true data dependence on values from every gradient
    leaf, so the scheduler cannot issue this collective before the full
    backward has drained. ``lax.optimization_barrier`` would be the
    idiomatic pin, but the CPU backend expands barriers away before
    scheduling, and the regression baseline must hold everywhere."""
    wdt = jnp.dtype(b.key[1])
    flat = not b.shaped or pin is not None
    parts = [g.astype(jnp.float32) * scale for g in gparts]
    if flat:
        parts = [x.reshape(-1) for x in parts]
    buf32 = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    stats = None
    if census:
        stats = (jnp.max(jnp.abs(buf32)),
                 jnp.sqrt(jnp.mean(jnp.square(buf32))))
    if pin is not None:
        buf32 = jnp.concatenate([buf32, pin])
    wire = buf32.astype(wdt)
    if b.schedule == "two_level":
        buf = _two_level_psum(wire, bp.batch_axes, bp.dims.local_replicas)
    else:
        buf = jax.lax.psum(wire, bp.batch_axes)   # ONE dense collective
    if not flat:
        return [buf.astype(gparts[0].dtype)], stats, buf
    out, off = [], 0
    for g, sz in zip(gparts, b.sizes):
        out.append(buf[off:off + sz].reshape(g.shape).astype(g.dtype))
        off += sz
    return out, stats, buf[:off]


def make_bucketed_value_and_grad(model, rt, plan: Plan) -> Callable:
    """(params, batch) -> ((loss, metrics), grads), grads pre-aggregated.

    A drop-in for jax.value_and_grad(loss_fn, has_aux=True) whose gradient
    collectives are the bucketed exchange. Inside the manual body gradients
    are grads of the *local* mean loss; since the global loss is the equal-
    weight mean of local losses, the true gradient is their pmean — applied
    as a 1/N pre-scale (mirroring the 1/T the unbucketed mean bakes in)
    followed by the fused psum. Sparse gatherv gradients arrive replica-
    summed from the embedding push and take only the 1/N.

    Overlap (bp.overlap): each bucket's exchange runs inside the backward
    graph as the bwd of an identity ``custom_vjp`` "tap" wrapped around the
    bucket's parameters — applied *inside* the differentiated function, so
    autodiff routes the bucket's cotangents through the exchange at the
    moment its last member gradient is produced. Each tap also takes a
    zeros((2,)) census token whose cotangent smuggles the backward-computed
    (|g|inf, rms) scalars out to the forward metrics. overlap=False pins
    every bucket collective strictly after the full backward with a data-
    dependence pin over all gradient leaves — the scheduling baseline;
    both paths are bit-identical (the exchange is an elementwise psum,
    issue order never changes the math, and the pin is sliced off).
    """
    bp: BucketPlan = plan.bucket_plan
    assert bp is not None and plan.mesh is not None
    leaf = lambda x: isinstance(x, ParamPlan)
    pspecs = jax.tree.map(lambda p: p.pspec, plan.params, is_leaf=leaf)
    bspecs = {
        k: P(*([bp.batch_axes] + [None] * (len(v.shape) - 1)))
        if len(v.shape) else P()
        for k, v in model.input_specs().items()
    }
    heartbeat = bool(getattr(rt.run_cfg, "heartbeat", False))
    if heartbeat:
        # one scalar per replica slot, sharded so each replica holds only
        # its own — the attribution channel rides the fused metrics psum
        bspecs["_heartbeat"] = P(bp.batch_axes)
    scale = 1.0 / bp.replicas
    bucketed = {i for b in bp.buckets for i in b.idx}
    grad_census = bool(getattr(rt.run_cfg, "wire_dtype_auto", False))
    # fused bucket-apply: the optimizer wants the post-psum flat buffers
    # themselves (optim/optimizer.py update_fused), so the step also
    # returns them — under overlap they leave the backward through the tap
    # tokens' cotangents (wire -> f32 is exact for every wire dtype). A
    # shaped bucket's buffer is its exchanged leaf itself: the wire dtype
    # is never wider than the parameter's (_exchange_dtype), so wire ->
    # param dtype -> f32 reads the same values as wire -> f32
    want_bufs = bool(getattr(plan, "fused_apply", False))
    # sparse tables that kept their own exchange: the row-buffer census
    # targets these (their grads never transit a bucket, so without this
    # they could never earn an f32 wire pin)
    sparse_tables = {i: p.name for i, p in enumerate(_plan_leaves(plan))
                     if p.sparse and i not in bucketed}
    # overlap=False defers each eligible gatherv table's push: the lookup
    # VJP returns the locally-densified gradient (no collectives in the
    # backward) and the exchange reruns here, post-backward, behind the
    # same data-dependence pin as the dense buckets — the sparse half of
    # the scheduling baseline. Eligible = the densify round-trip is exact
    # in the table's param/wire dtypes (Runtime.sparse_defer_exact).
    deferred = {}
    if not bp.overlap:
        deferred = {i: (p.name, rt.embed_ctx(p.name))
                    for i, p in enumerate(_plan_leaves(plan))
                    if p.sparse and p.method == "mpi_gatherv"
                    and i not in bucketed
                    and rt.sparse_defer_exact(p.name)}

    def _make_tap(b: Bucket):
        # the flat buffer rides the token's cotangent out of the backward;
        # a shaped bucket's buffer is its exchanged leaf
        carry = sum(b.sizes) if want_bufs and not b.shaped else 0
        @jax.custom_vjp
        def tap(leaves, token):
            return leaves
        def fwd(leaves, token):
            return leaves, None
        def bwd(_, cts):
            ex, stats, buf = _exchange_bucket(b, list(cts), scale, bp,
                                              grad_census)
            tok_ct = (jnp.stack(stats) if stats is not None
                      else jnp.zeros((2,), jnp.float32))
            if carry:
                tok_ct = jnp.concatenate([tok_ct, buf.astype(jnp.float32)])
            return tuple(ex), tok_ct
        tap.defvjp(fwd, bwd)
        return tap, 2 + carry

    taps_and_sizes = [_make_tap(b) for b in bp.buckets]
    taps = [t for t, _ in taps_and_sizes]
    token_sizes = [s for _, s in taps_and_sizes]

    def loss_fn(params, batch):
        with jax.named_scope(FORWARD):
            return model.loss_fn(params, batch)

    def loss_tapped(params, tokens, batch):
        # taps must wrap the parameters *inside* the differentiated
        # function — wrapping before value_and_grad would leave the tap
        # bwd (the whole exchange) outside the traced gradient path
        pleaves, ptree = jax.tree_util.tree_flatten(params)
        for k, b in enumerate(bp.buckets):
            tapped = taps[k](tuple(pleaves[i] for i in b.idx), tokens[k])
            for j, i in enumerate(b.idx):
                pleaves[i] = tapped[j]
        return loss_fn(jax.tree_util.tree_unflatten(ptree, pleaves), batch)

    def body(params, batch):
        batch = dict(batch)
        hb = batch.pop("_heartbeat", None)
        bufs = []
        if bp.overlap:
            tokens = tuple(jnp.zeros((n,), jnp.float32)
                           for n in token_sizes)
            with manual_region():
                (loss, metrics), (grads, tgrads) = jax.value_and_grad(
                    loss_tapped, argnums=(0, 1), has_aux=True)(
                        params, tokens, batch)
            metrics = dict(metrics)
            gleaves, gtree = jax.tree_util.tree_flatten(grads)
            out = list(gleaves)       # bucketed leaves already exchanged
            if want_bufs:
                bufs = [out[b.idx[0]] if b.shaped else tgrads[k][2:]
                        for k, b in enumerate(bp.buckets)]
            if grad_census:
                for k in range(len(bp.buckets)):
                    metrics[f"gbucket{k}_gmax"] = tgrads[k][0]
                    metrics[f"gbucket{k}_grms"] = tgrads[k][1]
        else:
            with manual_region():
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
            metrics = dict(metrics)
            gleaves, gtree = jax.tree_util.tree_flatten(grads)
            out = list(gleaves)
            # pin every bucket collective strictly after the full
            # backward — the deterministic contrast the overlap scheduling
            # regression tests against. One element of EVERY gradient leaf
            # rides each bucket's psum input (sliced off after): a data
            # dependence the compiler cannot drop, unlike an
            # optimization_barrier (expanded away pre-scheduling on CPU).
            pin = jnp.stack([g.reshape(-1)[0].astype(jnp.float32)
                             for g in gleaves])
            for k, b in enumerate(bp.buckets):
                ex, stats, buf = _exchange_bucket(
                    b, [gleaves[i] for i in b.idx], scale, bp, grad_census,
                    pin=pin)
                for j, i in enumerate(b.idx):
                    out[i] = ex[j]
                if want_bufs:
                    bufs.append(ex[0] if b.shaped
                                else buf.astype(jnp.float32))
                if stats is not None:
                    metrics[f"gbucket{k}_gmax"] = stats[0]
                    metrics[f"gbucket{k}_grms"] = stats[1]
            # deferred sparse push: rerun each eligible gatherv exchange
            # here, behind the same pin, from the locally-densified grad
            # and the dedupe buffer the forward smuggled out via metrics
            for i, (name, ectx) in deferred.items():
                uids = metrics.pop(f"{name}_uids")
                gleaves[i] = embedding.deferred_push(
                    gleaves[i], uids, ectx, pin=pin)
        with jax.named_scope(EXCHANGE):
            for i, g in enumerate(gleaves):
                if i in bucketed:
                    continue
                # sparse push already exchanged inside the lookup's VJP
                # (replica-summed); only the loss-mean 1/N remains
                g32 = g.astype(jnp.float32) * scale
                if grad_census and i in sparse_tables and g32.ndim >= 2:
                    # sparse row-buffer magnitude census: |g|inf and rms
                    # over the rows the push actually touched (zero rows
                    # excluded — the replica-sum inflates max and rms by
                    # the same factor, so the peak-to-rms pin ratio is
                    # unaffected)
                    name = sparse_tables[i]
                    rows = jnp.any(g32 != 0.0, axis=tuple(range(1, g32.ndim)))
                    width = g32.size // g32.shape[0]
                    nnz = jnp.maximum(jnp.sum(rows.astype(jnp.float32)), 1.0)
                    metrics[f"{name}_gmax"] = jnp.max(jnp.abs(g32))
                    metrics[f"{name}_grms"] = jnp.sqrt(
                        jnp.sum(jnp.square(g32)) / (nnz * width))
                out[i] = g32.astype(g.dtype)
        grads_out = jax.tree_util.tree_unflatten(gtree, out)

        if hb is not None:
            # per-host straggler attribution (runtime/monitor.py): each
            # replica one-hot-encodes its own heartbeat scalar at N× so the
            # replica-*mean* the fused psum computes decodes back to slot
            # j's raw value — the channel adds D scalars to the existing
            # reduction, zero extra collectives
            slot = jnp.zeros((), jnp.int32)
            for a in bp.batch_axes:
                slot = slot * plan.mesh.shape[a] + jax.lax.axis_index(a)
            for j in range(bp.replicas):
                metrics[f"heartbeat{j}"] = hb[0] * jnp.where(
                    slot == j, float(bp.replicas), 0.0)
        # fused scalar reduction: loss + every scalar metric, one psum;
        # rank>=1 metric leaves (none today) pmean individually — returning
        # them raw through out_specs=P() would silently pass one device's
        # local value off as the global metric
        with jax.named_scope(EXCHANGE):
            mleaves, mtree = jax.tree_util.tree_flatten(metrics)
            scalar_pos = [j for j, x in enumerate(mleaves)
                          if jnp.ndim(x) == 0]
            vec = jnp.stack([loss.astype(jnp.float32)] +
                            [mleaves[j].astype(jnp.float32)
                             for j in scalar_pos])
            vec = jax.lax.psum(vec, bp.batch_axes) * scale
            loss_out = vec[0]
            for k, j in enumerate(scalar_pos):
                mleaves[j] = vec[1 + k]
            for j, x in enumerate(mleaves):
                if jnp.ndim(x) > 0:
                    mleaves[j] = jax.lax.psum(
                        x.astype(jnp.float32), bp.batch_axes) * scale
        metrics_out = jax.tree_util.tree_unflatten(mtree, mleaves)
        if want_bufs:
            # post-psum buffers are replica-identical; they leave the
            # manual region replicated for the fused optimizer apply
            return loss_out, metrics_out, grads_out, tuple(bufs)
        return loss_out, metrics_out, grads_out

    out_specs = (P(), P(), pspecs)
    if want_bufs:
        out_specs = out_specs + (tuple(P() for _ in bp.buckets),)
    fn = shard_map(body, mesh=plan.mesh, in_specs=(pspecs, bspecs),
                   out_specs=out_specs, check_vma=False)

    def value_and_grad_fn(params, batch):
        if want_bufs:
            loss, metrics, grads, bufs = fn(params, batch)
            return (loss, metrics), grads, list(bufs)
        loss, metrics, grads = fn(params, batch)
        return (loss, metrics), grads

    return value_and_grad_fn
