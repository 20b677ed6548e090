"""Fault-tolerant training driver.

Responsibilities beyond the jitted step:
  * deterministic resume — the data pipeline is step-addressed, so restoring
    (state, step) from a checkpoint reproduces the exact remaining stream;
  * checkpoint/restart — async sharded checkpoints every N steps; on any
    step failure the driver restores the last committed checkpoint and
    continues (bounded retries);
  * elastic re-mesh — ``Trainer.remesh(new_mesh)`` rebuilds the plan/step on
    a different mesh and reshards the live state through the elastic
    checkpoint path (the node-failure story: drop the bad host's slice,
    re-mesh, resume);
  * automatic straggler response — with ``remesh_on_straggle`` the monitor's
    sustained-outlier escalation drives the loop itself: commit a
    checkpoint, shrink the data axis by one slice
    (``launch/mesh.shrink_mesh``), re-run ``analyze()`` so every method /
    capacity / bucket is re-priced for the smaller world (the cost model's
    α·messages term changes with N), and resume on the live state with
    trajectory continuity. ``remesh_cooldown`` steps must pass before the
    monitor may escalate again, and ``min_data_parallel`` floors the
    shrink. With ``RunConfig.heartbeat`` the eviction is *attributed*: each
    data slice's step-time scalar rides the fused metrics psum, the monitor
    EMAs them per slot, and the shrink drops the named slice (by process
    index on a real multi-host mesh) instead of the last by convention;
  * mesh re-growth — ``Trainer.readmit()`` re-inserts the evicted slice at
    its original grid position (``launch/mesh.grow_mesh``) through the same
    checkpoint → ``analyze()`` → rebuild path, arming a probation window:
    if the re-admitted slice re-straggles, it is re-evicted immediately,
    bypassing the full escalation and the cooldown;
  * bounded-staleness sparse fallback — with ``stale_on_jitter`` and
    ``RunConfig.max_staleness > 0``, sustained jitter *below* the eviction
    threshold flips the sparse tables to stale pushes (the step applies the
    previous step's exchanged gradient; dense buckets stay synchronous) and
    flips back, with an automatic drain, once the jitter drains;
  * adaptive replanning — with ``replan_every > 0`` the driver feeds the
    in-graph sparsity census (``embed_unique`` metrics) into a
    ``SparsityProfile`` EMA and periodically re-runs the planner on the
    *observed* census (paper §5's profile → re-optimize loop). When the
    cost model flips a method or the capacity drifts past
    ``replan_drift``x, the jitted step is rebuilt and the live state
    reshards in place — device-side when pspecs are unchanged, through the
    remesh host path otherwise;
  * straggler detection via runtime/monitor.py.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.checkpoint.ckpt import (AsyncCheckpointer, latest_step,
                                   restore_checkpoint)
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core.plan import plan_diff, plan_leaves
from repro.core.runtime import Runtime
from repro.core.sparsity import (SparsityProfile, observed_census,
                                 wire_dtype_hints)
from repro.core.transform import (analyze, apply_replan, build_step,
                                  estimate_census, stale_buffer_tables,
                                  state_shardings)
from repro.data.pipeline import Dataset
from repro.launch.mesh import grow_mesh, shrink_mesh
from repro.models.model import build_model
from repro.optim.optimizer import (fuse_state, is_fused, make_optimizer,
                                   unfuse_state)
from repro.runtime import monitor as spans
from repro.runtime.monitor import StepMonitor

log = logging.getLogger("repro.trainer")


def _spanned(name: str):
    """Run a Trainer method inside the monitor's host span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def method(self, *args, **kwargs):
            with self.monitor.span(name):
                return fn(self, *args, **kwargs)
        return method
    return wrap


def _bucket_signature(plan) -> tuple:
    """The identity of a plan's bucket layout: per-bucket member indices and
    wire dtype, in order. Index-keyed gbucket EMAs are only comparable
    between plans with equal signatures."""
    if plan.bucket_plan is None:
        return ()
    return tuple((b.idx, b.key[1]) for b in plan.bucket_plan.buckets)


@dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep_ckpts: int = 3
    max_retries: int = 3
    log_every: int = 10
    metrics_host_every: int = 1
    # ---- profile -> replan loop (0 disables) ----
    replan_every: int = 0          # consider replanning every N steps
    replan_warmup: int = 2         # min profiled steps before first replan
    replan_drift: float = 1.5      # capacity drift factor that triggers it
    profile_decay: float = 0.9     # EMA decay of the sparsity profile
    # ---- elastic straggler response (auto-remesh) ----
    remesh_on_straggle: bool = False  # act on the monitor's escalation
    remesh_cooldown: int = 50      # steps before the monitor may re-escalate
    min_data_parallel: int = 1     # never shrink the data axis below this
    # ---- straggler attribution + probationary re-admission ----
    attribution: bool = True       # evict the heartbeat-attributed slice
                                   # (falls back to last-slice convention)
    probation_steps: int = 100     # probation window after readmit()
    probation_sustained: int = 2   # outlier heartbeats on probation that
                                   # re-evict without a full escalation
    # ---- bounded-staleness sparse fallback (jitter below eviction) ----
    stale_on_jitter: bool = False  # flip sparse tables to stale pushes on
                                   # sustained jitter (needs
                                   # RunConfig.max_staleness > 0)


class Trainer:
    def __init__(self, model_cfg: ModelConfig, shape_cfg: ShapeConfig,
                 run_cfg: RunConfig, tcfg: TrainerConfig,
                 dataset: Dataset, mesh=None):
        self.model_cfg, self.shape_cfg = model_cfg, shape_cfg
        self.run_cfg, self.tcfg = run_cfg, tcfg
        self.dataset = dataset
        self.monitor = StepMonitor(cooldown=tcfg.remesh_cooldown)
        self.ckpt = AsyncCheckpointer(tcfg.ckpt_dir, tcfg.keep_ckpts) \
            if tcfg.ckpt_dir else None
        self.step = 0
        self.profile = SparsityProfile(decay=tcfg.profile_decay)
        # per-slot heartbeat override hook: (step, n_slots) -> float[n] step
        # seconds. Single-controller default writes the measured step time
        # into every slot; multi-host shims (and the chaos bench) use this
        # to carry genuinely per-host timings.
        self.heartbeat_fn: Optional[Callable] = None
        self._evicted: list = []       # LIFO of evicted slices (readmit)
        self._stale_tables: tuple = ()  # live bounded-staleness table set
        log.debug("jax %s compat=%s", jax.__version__, compat.capabilities())
        self._build(mesh)

    # ------------------------------------------------------------------
    def _build(self, mesh, state=None, carry_plan=None):
        """(Re)build plan + jitted step; ``state`` (host or device arrays)
        is resharded onto the new plan instead of re-initializing.

        ``carry_plan`` (the plan live before an elastic rebuild) carries
        the *observed* workload knowledge across the mesh change: the new
        plan is derived from the profile's observed census with sticky
        growth against the old plan, not from the bare build-time estimate
        — otherwise a remesh silently reverts overflow-grown capacities
        and profiled method/wire choices (the same bug class the restore
        path fixes via the manifest plan record)."""
        self.mesh = mesh
        self.rt = Runtime(self.model_cfg, self.run_cfg, self.shape_cfg,
                          mesh=mesh)
        self.model = build_model(self.model_cfg, self.rt)
        census = None
        if carry_plan is not None and self.profile.ready():
            census = self._observed_census(carry_plan)
        self.plan = analyze(self.model, self.rt, census=census,
                            stale_tables=self._stale_tables)
        self.rt.plan = self.plan
        self.optimizer = make_optimizer(self.rt)
        self.train_step, self.state, self.shardings = build_step(
            self.model, self.optimizer, self.rt, self.plan, state,
            seed=self.run_cfg.seed)
        self._note_plan_costs()

    def _note_plan_costs(self):
        self.monitor.note_exchange(
            self.plan.bucket_plan.stats() if self.plan.bucket_plan else None)

    def _canonical_state(self):
        """The live state in the canonical per-param layout. Checkpoints,
        restore templates, and the remesh host round-trip never see the
        fused bucket layout — it is a per-plan memory layout, rebuilt by
        build_step, not portable state."""
        if is_fused(self.state):
            return unfuse_state(self.state, self.plan.bucket_plan)
        return self.state

    # ------------------------------------------------------------------
    def _wire_pins(self, plan) -> dict:
        """Dense parameters whose planned wire dtype differs from the
        global knob — i.e. the profiled wire_dtype_auto pins. Part of the
        manifest plan record: Plan.tables() only covers sparse tables, so
        without this a restored run would silently revert an outlier-prone
        bucket's f32 pin to the bf16 default."""
        base = jnp.dtype(self.rt.wire_dtype)
        return {p.name: jnp.dtype(p.wire_dtype).name
                for p in plan_leaves(plan.params)
                if not p.sparse and jnp.dtype(p.wire_dtype) != base}

    def _ckpt_extra(self) -> dict:
        """Manifest ``extra`` for every checkpoint this trainer writes: the
        dataset cursor plus the live plan's per-table summary — capacities,
        methods, wire dtypes, and the priced α — and the dense wire-dtype
        pins, so a restore can rebuild the *saved* plan instead of silently
        re-deriving the build-time estimate (which loses overflow-grown
        capacities and profiled method/wire flips, corrupting the resumed
        trajectory)."""
        extra = {"dataset_step": self.step, "plan": self.plan.tables()}
        pins = self._wire_pins(self.plan)
        if pins:
            extra["wire_pins"] = pins
        if self.mesh is not None:
            extra["mesh"] = dict(self.mesh.shape)
        return extra

    def maybe_restore(self):
        if self.ckpt is None:
            return
        last = latest_step(self.tcfg.ckpt_dir)
        if last is None:
            return
        # checkpoints hold the canonical per-param layout: restore into a
        # canonical template (with matching shardings), re-fuse afterwards
        template = self._canonical_state()
        shardings = state_shardings(self.plan, template) \
            if self.mesh is not None else None
        self.state, self.step, extra = restore_checkpoint(
            self.tcfg.ckpt_dir, template, shardings=shardings)
        saved = (extra or {}).get("plan")
        pins = (extra or {}).get("wire_pins", {})
        if (saved and saved != self.plan.tables()) or \
                pins != self._wire_pins(self.plan):
            self._adopt_saved_plan(saved or {}, pins)
        elif getattr(self.plan, "fused_apply", False):
            self.state = fuse_state(self.state, self.plan.bucket_plan)
            if self.shardings is not None:
                self.state = jax.device_put(self.state, self.shardings)
        # recovery latency must not read as a straggler, and the in-flight
        # timing sample (if any) now spans a restore, not a step
        self.monitor.note_recovery()
        log.info("restored checkpoint at step %d", self.step)

    @_spanned(spans.REBUILD)
    def _adopt_saved_plan(self, saved: dict, wire_pins: Optional[dict] = None):
        """Re-analyze + rebuild the jitted step against a checkpoint's plan
        record. The saved per-table α reproduces the Table-3 method argmin,
        the saved capacities/grown flags override the build-time census,
        and ``wire_pins`` re-applies profiled dense wire-dtype choices —
        without this, restoring a checkpoint written after a
        capacity-growth replan rebuilds the *estimate's* smaller buffers
        and the restored run immediately re-overflows (and a method or
        wire flip recorded at save time would silently revert)."""
        census = estimate_census(self.model, self.rt)
        if wire_pins:
            census.wire_dtypes.update(wire_pins)
        for name, ent in saved.items():
            t = census.tables.get(name)
            if t is None:
                continue
            alpha = ent.get("alpha")
            census.tables[name] = dataclasses.replace(
                t,
                alpha=float(alpha) if alpha is not None else t.alpha,
                capacity=int(ent.get("capacity", t.capacity)),
                grown=bool(ent.get("grown", False)))
            if ent.get("wire_dtype"):
                census.wire_dtypes[name] = ent["wire_dtype"]
        if census.tables:
            census.capacity = max(
                census.capacity,
                max(t.capacity for t in census.tables.values()))
        # the checkpoint's stale flags are the authority on which tables run
        # the bounded-staleness push — a run saved mid-stale-window resumes
        # stale (and vice versa), instead of silently flipping on restore
        self._stale_tables = tuple(sorted(
            n for n, e in saved.items() if e.get("stale")))
        self.monitor._stale_on = bool(self._stale_tables)  # no flip counted
        new_plan = analyze(self.model, self.rt, census=census,
                           stale_tables=self._stale_tables)
        diff = plan_diff(self.plan, new_plan)
        log.info("restore adopted the checkpoint's plan record: "
                 "capacities %s -> %s, flips=%s", diff["table_capacity"][0],
                 diff["table_capacity"][1], diff["flips"])
        self.plan = new_plan
        self.train_step, self.state, self.shardings = apply_replan(
            self.model, self.optimizer, self.rt, new_plan, self.state, diff)
        self._note_plan_costs()

    def _observed_census(self, live_plan):
        """The census the replan loop runs on: the profile's per-table
        observed uniques/overflow folded over the build-time estimate, with
        sticky growth against ``live_plan`` and (under wire_dtype_auto)
        per-parameter wire hints from the magnitude census. Shared by
        ``maybe_replan`` and the elastic rebuild — reads ``self.model`` /
        ``self.rt``, so on a remesh it prices against the *new* world."""
        base = estimate_census(self.model, self.rt)
        live = {n: (live_plan.table_capacity.get(n, 0),
                    n in live_plan.grown_tables)
                for n in live_plan.table_methods}
        census = observed_census(self.profile, base,
                                 self.model_cfg.vocab_size, self.run_cfg,
                                 live=live)
        if self.run_cfg.wire_dtype_auto and live_plan.bucket_plan is not None:
            names = [p.name for p in plan_leaves(live_plan.params)]
            census.wire_dtypes = wire_dtype_hints(
                self.profile, live_plan.bucket_plan, names,
                outlier_ratio=self.run_cfg.wire_outlier_ratio,
                default=self.run_cfg.wire_dtype,
                # tables that kept their own sparse exchange emit a
                # name-keyed row-buffer census instead of riding a bucket
                sparse_tables=[n for n, m in live_plan.table_methods.items()
                               if m != "allreduce"])
        return census

    @_spanned(spans.REBUILD)
    def remesh(self, new_mesh):
        """Elastic re-mesh: reshard live state onto a new mesh (e.g. after
        dropping a failed host slice). The rebuild derives shardings from
        the restored values themselves — no throwaway ``model.init`` — and
        carries the observed census across the mesh change (grown
        capacities and profiled choices survive; only the world-size terms
        re-price)."""
        host_state = jax.tree.map(
            lambda a: None if a is None else np.asarray(jax.device_get(a)),
            self._canonical_state())
        old_sig = _bucket_signature(self.plan)
        self._build(new_mesh, state=host_state, carry_plan=self.plan)
        if _bucket_signature(self.plan) != old_sig:
            # bucket magnitude EMAs are index-keyed; a regrouped layout on
            # the new mesh makes the old samples mis-attributed
            self.profile.reset_grad_census()

    def _auto_remesh(self) -> Optional[dict]:
        """Act on the monitor's straggler escalation: commit a checkpoint,
        evict the slow data slice, and resume on the live state.

        With heartbeat attribution (``RunConfig.heartbeat`` +
        ``TrainerConfig.attribution``) the monitor *names* the slow slice —
        per-host step scalars ride the fused metrics psum and the per-slot
        EMAs single out the outlier — so the eviction drops that slice; on
        a genuinely multi-process mesh the attributed slice resolves to its
        owning process and the shrink goes through
        ``shrink_mesh(drop_process_index=...)``. Without attribution the
        last data slice is dropped by convention. The evicted slice
        (devices + grid position) is recorded so ``readmit()`` can grow the
        mesh back at the same position. The rebuild re-runs ``analyze()``
        against the smaller world, so methods, capacities, and buckets are
        re-priced at the new N (a ps↔allreduce flip across the remesh is
        legitimate and handled). Returns the plan diff across the remesh,
        or None when the mesh cannot shrink.
        """
        if self.mesh is None or "data" not in self.mesh.axis_names:
            self.monitor.note_recovery()
            return None
        devs = np.asarray(self.mesh.devices)
        ax = self.mesh.axis_names.index("data")
        slot = self.monitor.straggler_slice() if self.tcfg.attribution \
            else None
        if slot is not None and tuple(self.rt.batch_axes) == ("data",) \
                and 0 <= int(slot) < devs.shape[ax]:
            drop = int(slot)      # heartbeat slots ARE data grid indices
        else:
            slot = None
            drop = devs.shape[ax] - 1      # by-convention fallback
        kw = {"drop_axis_index": drop}
        if slot is not None and \
                len({getattr(d, "process_index", 0) for d in devs.flat}) > 1:
            procs = {getattr(d, "process_index", 0)
                     for d in np.take(devs, drop, axis=ax).flat}
            if len(procs) == 1:
                kw = {"drop_process_index": procs.pop()}
        new_mesh = shrink_mesh(self.mesh, axis="data",
                               min_axis_size=self.tcfg.min_data_parallel,
                               **kw)
        if new_mesh is None:
            log.warning(
                "straggler escalation at step %d but the mesh cannot "
                "shrink (data axis at or below min_data_parallel=%d) — "
                "re-arming the monitor", self.step,
                self.tcfg.min_data_parallel)
            self.monitor.note_recovery()   # re-arm instead of re-firing
            self.monitor._probation_trip = None
            return None
        evicted = {"devices": np.take(devs, drop, axis=ax),
                   "index": drop, "slot": slot, "step": self.step}
        if self.ckpt is not None:
            # synchronous commit before touching placement: a crash during
            # the reshard recovers from this step, not an older one. A
            # checkpoint failure (including a stored async one re-raised by
            # the wait) must not abort the recovery itself — the live-state
            # remesh does not depend on it
            try:
                self.ckpt.save_sync(self.step, self._canonical_state(),
                                    extra=self._ckpt_extra())
            except Exception as e:
                log.exception("pre-remesh checkpoint failed; continuing "
                              "with the live-state remesh")
                self.monitor.note_ckpt_error(e)
        old_plan, old_shape = self.plan, dict(self.mesh.shape)
        self.remesh(new_mesh)
        self._evicted.append(evicted)
        diff = plan_diff(old_plan, self.plan)
        self.monitor.note_remesh()
        log.warning(
            "auto-remesh at step %d: mesh %s -> %s (%s data slice %d), "
            "flips=%s, capacities %s -> %s", self.step, old_shape,
            dict(new_mesh.shape),
            "heartbeat-attributed" if slot is not None else "by-convention",
            drop, diff["flips"], diff["table_capacity"][0],
            diff["table_capacity"][1])
        return diff

    def readmit(self) -> Optional[dict]:
        """Re-admit the most recently evicted slice on probation.

        The grow mirrors the shrink through the same safety protocol:
        commit a checkpoint, re-insert the evicted devices at their
        original grid position (``launch/mesh.grow_mesh``), and rebuild
        plan + step through the observed-census elastic path — grown
        capacities and profiled choices survive, only the world-size terms
        re-price. The monitor's escalation window and cooldown origin reset
        (``note_regrow``) and a probation window arms on the re-admitted
        slice: if its heartbeats re-straggle for ``probation_sustained``
        beats within ``probation_steps``, the next eviction fires
        immediately — no second full escalation, no cooldown wait. Returns
        the plan diff, or None when there is nothing to re-admit (or the
        devices are no longer addressable)."""
        if not self._evicted or self.mesh is None:
            return None
        ev = self._evicted[-1]
        try:
            new_mesh = grow_mesh(self.mesh, ev["devices"],
                                 insert_axis_index=ev["index"], axis="data")
        except ValueError as e:
            log.warning("readmit at step %d impossible: %s", self.step, e)
            return None
        self._evicted.pop()
        if self.ckpt is not None:
            try:
                self.ckpt.save_sync(self.step, self._canonical_state(),
                                    extra=self._ckpt_extra())
            except Exception as e:
                log.exception("pre-readmit checkpoint failed; continuing "
                              "with the live-state re-grow")
                self.monitor.note_ckpt_error(e)
        old_plan, old_shape = self.plan, dict(self.mesh.shape)
        self.remesh(new_mesh)
        diff = plan_diff(old_plan, self.plan)
        self.monitor.note_regrow(
            slot=ev["index"], probation_steps=self.tcfg.probation_steps,
            probation_sustained=self.tcfg.probation_sustained)
        log.warning(
            "readmit at step %d: mesh %s -> %s (slice %d back on probation "
            "for %d steps), flips=%s", self.step, old_shape,
            dict(new_mesh.shape), ev["index"], self.tcfg.probation_steps,
            diff["flips"])
        return diff

    @_spanned(spans.REBUILD)
    def _flip_stale(self, on: bool) -> Optional[dict]:
        """Flip the stale-eligible sparse tables to (or back from) the
        bounded-staleness push and hot-swap the jitted step. The staleness
        buffers themselves are plan-independent state (transform.py
        ``ensure_stale_buffers``): only ``Plan.stale_tables`` and the
        compiled step change, and the first synchronous step after a
        flip-back drains the last buffered gradient as part of its own
        update. Returns the plan diff, or None when nothing flips."""
        target = stale_buffer_tables(self.plan, self.rt) if on else ()
        if tuple(target) == tuple(getattr(self.plan, "stale_tables", ())):
            return None
        census = self._observed_census(self.plan) if self.profile.ready() \
            else None
        new_plan = analyze(self.model, self.rt, census=census,
                           stale_tables=tuple(target))
        diff = plan_diff(self.plan, new_plan)
        if not diff["changed"]:
            return None
        self._stale_tables = tuple(new_plan.stale_tables)
        self.plan = new_plan
        self.train_step, self.state, self.shardings = apply_replan(
            self.model, self.optimizer, self.rt, new_plan, self.state, diff)
        self.monitor.note_stale_flip(bool(new_plan.stale_tables))
        self._note_plan_costs()
        log.warning(
            "stale flip at step %d (jitter %.2f): tables %s now %s "
            "(max_staleness=%d)", self.step, self.monitor.jitter_ratio,
            list(new_plan.stale_tables) or diff["stale_flips"],
            "bounded-stale" if new_plan.stale_tables else "synchronous",
            getattr(self.run_cfg, "max_staleness", 0))
        return diff

    # ------------------------------------------------------------------
    @_spanned(spans.REBUILD)
    def maybe_replan(self) -> Optional[dict]:
        """Re-run the planner on the observed census; hot-swap on change.

        Per-parameter: the census carries one record per sparse table
        (measured unique rows, overflow EMA, overflow-grown capacity) plus
        profiled wire-dtype hints from the dense-gradient magnitude census,
        so each table / bucket group can move independently. Returns the
        plan diff when a replan was evaluated, None when the profile has no
        data yet. Reuses the remesh reshard path only when pspecs actually
        moved; otherwise state stays put and just the jitted step is
        rebuilt against the new plan.
        """
        if not self.profile.ready(self.tcfg.replan_warmup):
            return None
        census = self._observed_census(self.plan)
        new_plan = analyze(self.model, self.rt, census=census,
                           stale_tables=self._stale_tables)
        diff = plan_diff(self.plan, new_plan, self.tcfg.replan_drift)
        self.monitor.note_alpha(census.alpha)
        if not diff["changed"]:
            return diff
        log.info(
            "replan at step %d: alpha %.4f -> %.4f, capacity %d -> %d "
            "(tables %s -> %s%s), flips=%s, wire_flips=%s, "
            "pspecs_changed=%s", self.step, diff["alpha"][0],
            diff["alpha"][1], diff["capacity"][0], diff["capacity"][1],
            diff["table_capacity"][0], diff["table_capacity"][1],
            ", overflow-grown" if diff["capacity_grown"] else "",
            diff["flips"], diff["wire_flips"], diff["pspecs_changed"])
        old_sig = _bucket_signature(self.plan)
        self.plan = new_plan
        self.train_step, self.state, self.shardings = apply_replan(
            self.model, self.optimizer, self.rt, new_plan, self.state, diff)
        if _bucket_signature(new_plan) != old_sig:
            # bucket metrics are index-keyed: a regrouped layout makes the
            # old per-bucket magnitude EMAs mis-attributed — start fresh
            self.profile.reset_grad_census()
        self.monitor.note_replan()
        self._note_plan_costs()
        return diff

    # ------------------------------------------------------------------
    def _heartbeat_batch(self, batch: dict) -> dict:
        """Inject the per-slot heartbeat vector the step carries through
        the fused metrics psum (one f32 scalar per data slice). Single
        controller: every slot gets this process's last measured step time,
        so attribution reads flat unless ``heartbeat_fn`` (a multi-host
        shim, or the chaos bench) supplies genuinely per-slot timings."""
        if not getattr(self.run_cfg, "heartbeat", False) \
                or self.mesh is None:
            return batch
        n = max(self.rt.replicas, 1)
        if self.heartbeat_fn is not None:
            hb = np.asarray(self.heartbeat_fn(self.step, n),
                            np.float32).reshape(n)
        else:
            t = self.monitor.times[-1] if self.monitor.times else 0.0
            hb = np.full((n,), float(t), np.float32)
        batch = dict(batch)
        batch["_heartbeat"] = hb
        return batch

    def run(self, on_metrics: Optional[Callable[[int, dict], None]] = None):
        tokens_per_step = self.shape_cfg.tokens
        mon = self.monitor
        retries = 0
        while self.step < self.tcfg.total_steps:
            with mon.step_span(self.step):
                with mon.span(spans.INPUT):
                    batch = self._heartbeat_batch(
                        self.dataset.batch(self.step))
                try:
                    # the live mesh is the context of every step: after a
                    # remesh it differs from whatever mesh the caller
                    # entered, and the step's shard_maps must trace against
                    # the mesh they name
                    with (compat.use_mesh(self.mesh) if self.mesh is not None
                          else contextlib.nullcontext()), \
                            mon.span(spans.DISPATCH):
                        self.state, metrics = self.train_step(self.state,
                                                              batch)
                    if (self.step + 1) % self.tcfg.metrics_host_every == 0:
                        with mon.span(spans.HOST_SYNC):
                            metrics = {k: float(v) for k, v in metrics.items()
                                       if getattr(v, "ndim", 0) == 0}
                        # decode the heartbeat slots out of the fused
                        # metrics psum into the attribution state (and out
                        # of the user-visible metrics — stats carries the
                        # EMAs)
                        beats = {int(k[9:]): metrics.pop(k)
                                 for k in list(metrics)
                                 if k.startswith("heartbeat")
                                 and k[9:].isdigit()}
                        if beats:
                            mon.note_heartbeats(beats)
                        self.profile.update(metrics)
                        # overflow is visible host-side every profiled step,
                        # not just when (or if) the growth replan fires;
                        # restricted to real sparse tables (the MoE router
                        # also emits a *_dropped scalar that is not buffer
                        # overflow)
                        mon.note_overflow(
                            self.profile.dropped(self.plan.table_methods))
                    retries = 0
                except Exception:  # failure path: restore + retry
                    retries += 1
                    log.exception("step %d failed (retry %d/%d)",
                                  self.step, retries, self.tcfg.max_retries)
                    if retries > self.tcfg.max_retries or self.ckpt is None:
                        raise
                    try:
                        self.ckpt.wait()
                    except Exception:
                        log.exception("in-flight checkpoint also failed")
                    if latest_step(self.tcfg.ckpt_dir) is None:
                        # no committed checkpoint to fall back on — and the
                        # failed call may already have consumed the donated
                        # state buffers, so retrying on self.state would
                        # feed the step poisoned memory. Rebuild from
                        # scratch.
                        log.warning("no committed checkpoint: "
                                    "reinitializing state from seed %d at "
                                    "step 0", self.run_cfg.seed)
                        self.train_step, self.state, self.shardings = \
                            build_step(self.model, self.optimizer, self.rt,
                                       self.plan, None,
                                       seed=self.run_cfg.seed)
                        self.step = 0
                        mon.note_recovery()
                    else:
                        self.maybe_restore()
                    continue
                stats = mon.stop(tokens=tokens_per_step)
                self.step += 1
                self._after_step(stats)
                if on_metrics is not None:
                    with mon.span(spans.CALLBACK):
                        on_metrics(self.step, {**metrics, **stats})
                elif self.step % self.tcfg.log_every == 0:
                    log.info("step %d loss %.4f %.0f tok/s", self.step,
                             metrics.get("loss", float("nan")),
                             stats["tokens_per_s"])
        if self.ckpt is not None:
            with mon.span(spans.CHECKPOINT):
                self.ckpt.save(self.step, self._canonical_state(),
                               extra=self._ckpt_extra())
                self.ckpt.wait()
        return self.state

    def _after_step(self, stats: dict) -> None:
        """The host's work between a step and its metrics callback: the
        replan loop, the checkpoint mirror and periodic save, and the
        monitor's escalations. Notes what it did in ``stats``."""
        mon = self.monitor
        if self.tcfg.replan_every and \
                self.step % self.tcfg.replan_every == 0:
            self.maybe_replan()
            # this step's stats must reflect a replan it triggered
            stats["replans"] = mon.replans
            if mon.observed_alpha is not None:
                stats["observed_alpha"] = mon.observed_alpha
        if self.ckpt is not None:
            # mirror the background-writer state each step (before the
            # save below can consume it): a pending failure keeps
            # re-noting until consumed; once the writer is clean again
            # and no new failure is noted, the signal self-heals
            mon.note_ckpt_error(self.ckpt.error)
            mon.note_ckpt_retries(self.ckpt.total_retries)
        if self.ckpt is not None and self.step % self.tcfg.ckpt_every == 0:
            # a failed *previous* background write re-raises out of
            # save()'s internal wait(); periodic checkpointing is not
            # worth aborting a healthy run — surface it and try again
            # next period (the final end-of-run save still raises)
            try:
                with mon.span(spans.CHECKPOINT):
                    self.ckpt.save(self.step, self._canonical_state(),
                                   extra=self._ckpt_extra())
            except Exception as e:
                log.exception("checkpoint at step %d failed", self.step)
                mon.note_ckpt_error(e)
        if mon.remesh_suggested and self.tcfg.remesh_on_straggle:
            if self._auto_remesh() is not None:
                stats["remeshes"] = mon.remeshes
                if self.mesh is not None:
                    stats["mesh"] = dict(self.mesh.shape)
        elif mon.straggler_suspected:
            log.warning("sustained step-time regression at step %d — "
                        "straggler suspected; consider remesh() or "
                        "remesh_on_straggle=True", self.step)
        elif self.tcfg.stale_on_jitter and \
                getattr(self.run_cfg, "max_staleness", 0) > 0:
            # the jitter fallback sits strictly below eviction: only
            # consulted when no straggler escalation is in flight
            if mon.stale_suggested:
                flipped = self._flip_stale(True)
            elif mon.stale_recovered:
                flipped = self._flip_stale(False)
            else:
                flipped = None
            if flipped is not None:
                stats["stale_flips"] = mon.stale_flips
                stats["stale_mode"] = mon._stale_on
