"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (lib/registry.py). One process drives every
chip the cell uses; with no TPU, or fewer chips than the cell asks for, it
exits 2 and prints no result.

A run:

1. set-up: draws the weights on the device from the seed in one jitted
   call (the configuration's ``make_init``), builds the program's
   ``Trainer`` as its launcher does, with the benchmark's traffic as its
   dataset and those weights as its state, and drives it through its first
   steps. Steps 1 to 3 are the checked steps: their losses, the first
   gradient's per-leaf norms (read from the first Adam moment) and the
   per-leaf change of the parameters after step 3 are kept. Two more steps,
   each waited for, finish the warm-up and time a step. Every program the
   window runs is compiled here.
2. window: ``Trainer.run`` in chunks of ``CHUNK`` steps until ``--seconds``
   have passed, with ``AHEAD_SECONDS`` of steps dispatched ahead of the one
   waited for, so that a host stall shorter than that leaves the chip fed.
   The losses are read once the window has closed. When the time is up
   nothing more is sent, and the window ends when every step sent is done.
   ``--trace 1`` profiles a window of at most ``TRACE_SECONDS`` instead.
3. check: the trainer is freed, the plain reference runs the same three
   steps from the same weights on the same batches, and the numbers of
   lib/check.py are held to the cell's limits.

With ``--trace 0`` the run prints the end-to-end metrics that
``BENCHMARK.json`` lists for the cell, out of tokens_per_s, mfu and
setup_s; with ``--trace 1``, its per-layer metrics, each from its reader
under metrics/.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from lib import check, trace as tracelib  # noqa: E402
from lib.hlo import collectives  # noqa: E402
from lib.lstm_ref import seed32  # noqa: E402
from lib.peaks import peaks_for  # noqa: E402
from lib.registry import Registry, load_module  # noqa: E402

CHECKED_STEPS = 3
WARM_STEPS = 5          # checked steps included
CHUNK = 50              # steps per Trainer.run call in the window
AHEAD_SECONDS = 4.0     # steps dispatched ahead of the one waited for;
                        # the runtime may hold fewer in flight
TRACE_SECONDS = 2.0     # longest traced window, before the last wait
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(Exception):
    pass


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if require_tpu:
        peaks_for(devs[0].device_kind)   # an unknown chip is an error
    return devs[:chips]


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def build(cell: dict, cfg: dict, mix: dict, seed: int, cmod, traffic):
    """The program's Trainer, built as ``repro.launch.train.main`` builds
    it, with ``traffic`` as its dataset and the seeded weights as its
    state. -> (trainer, key seed)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import RunConfig, ShapeConfig, get_config
    from repro.core.runtime import Runtime
    from repro.launch.mesh import make_mesh
    from repro.models.model import build_model
    from repro.optim.optimizer import make_optimizer
    from repro.runtime.trainer import Trainer, TrainerConfig

    key_seed = seed32(seed)
    mcfg = dataclasses.replace(get_config(cfg["arch"]), **cfg["model"])
    run_cfg = RunConfig(**cfg["run_config"], seed=key_seed)
    shape = ShapeConfig(cell["name"], mix["seq_len"], mix["global_batch"],
                        "train")
    mesh = make_mesh(tuple(cell["mesh"]), ("data", "model")) \
        if cell.get("mesh") else None
    init = cmod.make_init(cfg["model"], jnp.dtype(cfg["run_config"]
                                                  ["param_dtype"]))

    class SeededTrainer(Trainer):
        """Takes its first state from the benchmark's seeded weights."""
        params = None

        def _build(self, mesh, state=None, carry_plan=None):
            if state is None and self.params is not None:
                rt = Runtime(self.model_cfg, self.run_cfg, self.shape_cfg,
                             mesh=mesh)
                want = jax.eval_shape(build_model(self.model_cfg, rt).init,
                                      jax.random.key(0))
                got = jax.eval_shape(lambda p: p, self.params)
                if jax.tree.structure(want) != jax.tree.structure(got) or \
                        jax.tree.leaves(want) != jax.tree.leaves(got):
                    raise ValueError("the configuration's parameters do not "
                                     "match the program's model")
                state = make_optimizer(rt).init(self.params)
                SeededTrainer.params = None
            super()._build(mesh, state, carry_plan)

    SeededTrainer.params = init(jax.random.key(key_seed))
    tcfg = TrainerConfig(total_steps=0, log_every=10 ** 9)
    trainer = SeededTrainer(mcfg, shape, run_cfg, tcfg, traffic, mesh=mesh)
    return trainer, key_seed


def _mesh_ctx(trainer):
    from repro import compat
    return compat.use_mesh(trainer.mesh) if trainer.mesh is not None \
        else contextlib.nullcontext()


def moment_norms_fn(trainer):
    """A jitted ``state -> per-leaf norms of the first Adam moment``."""
    import jax
    import jax.numpy as jnp
    from repro.optim.optimizer import unfuse_state
    bp = trainer.plan.bucket_plan

    @jax.jit
    def norms(state):
        m = unfuse_state(state, bp).m
        return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(m)]

    return norms


def moment_norms(trainer) -> list:
    """Per-leaf norm of the gradient the optimizer took at step 1: its
    first Adam moment is (1 - b1) times that gradient."""
    with _mesh_ctx(trainer):
        out = moment_norms_fn(trainer)(trainer.state)
    return [float(x) / (1.0 - 0.9) for x in out]


def change_norms(trainer, cmod, cfg, key_seed) -> list:
    """Per-leaf norm of the parameters' change since the seeded weights,
    which the configuration's ``make_change_norms`` draws again block by
    block beside the state."""
    import jax
    import jax.numpy as jnp
    norms = cmod.make_change_norms(cfg["model"], jnp.dtype(
        cfg["run_config"]["param_dtype"]))
    with _mesh_ctx(trainer):
        out = norms(trainer.state.params, jax.random.key(key_seed))
    return [float(x) for x in out]


def setup_steps(trainer, cmod, cfg, key_seed) -> dict:
    """Steps 1..CHECKED_STEPS through ``Trainer.run``; their readings."""
    losses = []

    def keep(step, m):
        if step <= CHECKED_STEPS:
            losses.append(m["loss"])

    trainer.tcfg.total_steps = 1
    trainer.run(on_metrics=keep)
    grads = moment_norms(trainer)
    trainer.tcfg.total_steps = CHECKED_STEPS
    trainer.run(on_metrics=keep)
    change = change_norms(trainer, cmod, cfg, key_seed)
    return {"losses": losses, "grad_norms": grads, "change_norms": change}


def warm_steps(trainer) -> float:
    """Steps CHECKED_STEPS + 1..WARM_STEPS, each waited for. -> the last
    one's host seconds."""
    import jax
    last, times = [time.perf_counter()], []

    def keep(step, m):
        now = time.perf_counter()
        times.append(now - last[0])
        last[0] = now

    trainer.tcfg.total_steps = WARM_STEPS
    trainer.run(on_metrics=keep)
    jax.block_until_ready(trainer.state)
    return times[-1]


def window(trainer, seconds: float, step_s: float) -> dict:
    """``Trainer.run`` in chunks until ``seconds`` have passed, the loss
    of the step ``AHEAD_SECONDS`` (by ``step_s``) behind the newest waited
    for. -> losses, host gaps between dispatches, steps, the window's
    seconds and the seconds of its last wait."""
    import jax
    from jax.profiler import TraceAnnotation
    ahead = max(1, math.ceil(AHEAD_SECONDS / step_s))
    gaps, losses, pending, done = [], [], collections.deque(), [False]
    every = trainer.tcfg.metrics_host_every
    trainer.tcfg.metrics_host_every = sys.maxsize   # no per-step sync
    t0 = last = time.perf_counter()

    def on_step(step, m):
        nonlocal last
        with TraceAnnotation("bench.callback"):
            losses.append(m["loss"])
            pending.append(m["loss"])
            while len(pending) > ahead:
                pending.popleft().block_until_ready()
            now = time.perf_counter()
            gaps.append(now - last)
            last = now
            if now - t0 >= seconds:
                trainer.tcfg.total_steps = trainer.step
                done[0] = True

    with TraceAnnotation("bench.window"):
        while not done[0]:
            trainer.tcfg.total_steps = trainer.step + CHUNK
            with TraceAnnotation("bench.run_chunk"):
                trainer.run(on_metrics=on_step)
        t_close = time.perf_counter()
        jax.block_until_ready((trainer.state, losses))
    t_end = time.perf_counter()
    trainer.tcfg.metrics_host_every = every
    return {"gaps": gaps, "losses": [float(x) for x in jax.device_get(losses)],
            "steps": len(losses), "seconds": t_end - t0, "ahead": ahead,
            "drain_s": t_end - t_close}


def peak_bytes(devs) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(args, reg: Registry, require_tpu: bool = True,
             hook=None) -> tuple:
    """-> (result dict, check lines). Raises ``NoChip`` before any work
    when the chips are missing. ``hook(trainer)``, when given, runs on the
    built trainer before its first step (the tests plant faults with it)."""
    cell = reg.cell(args.workload)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    cmod = reg.config_module(cell["config"])
    devs = _devices(cell["chips"], require_tpu)

    import jax
    sys.path.insert(0, os.path.join(reg.root, "src"))
    if require_tpu:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    compile_s = [0.0]

    def on_duration(event, secs, *a, **k):
        if event in COMPILE_EVENTS:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    traffic_mod = load_module(os.path.join(reg.dir, "traffic",
                                           "generator.py"), "traffic")
    traffic = traffic_mod.Traffic(mix, cfg["model"]["vocab_size"],
                                  args.seed, cfg["model"].get("is_encdec",
                                                              False))
    trainer, key_seed = build(cell, cfg, mix, args.seed, cmod, traffic)
    if hook is not None:
        hook(trainer)
    prog = setup_steps(trainer, cmod, cfg, key_seed)
    step_s = warm_steps(trainer)
    _log(f"set-up compile_s {compile_s[0]:.3f}; peak_bytes_in_use after "
         f"set-up {peak_bytes(devs)}")

    tdir = os.path.join(reg.dir, ".trace", cell["name"])
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    compiled_before = compile_s[0]
    traffic.seconds.clear()
    if args.trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
    setup_s = time.perf_counter() - T_START
    win = window(trainer, seconds, step_s)
    if args.trace:
        jax.profiler.stop_trace()
    input_s = list(traffic.seconds)
    iv = win["gaps"]
    longest = sorted(range(len(iv)), key=lambda i: -iv[i])[:5]
    _log(f"window {win['seconds']:.4f} s, {win['steps']} steps, "
         f"{win['ahead']} ahead (warm step {step_s * 1e3:.4f} ms), last wait "
         f"{win['drain_s']:.4f} s; longest host gaps between dispatches "
         f"(step, ms): "
         f"{[(i + 1, round(iv[i] * 1e3, 3)) for i in sorted(longest)]}; "
         f"median {statistics.median(iv) * 1e3:.4f} ms")
    lw = win["losses"]
    _log(f"window losses: first {lw[0]:.4f} last {lw[-1]:.4f} max "
         f"{max(lw):.4f}; not finite {sum(not math.isfinite(x) for x in lw)}")
    if compile_s[0] != compiled_before:
        _log(f"WARNING: {compile_s[0] - compiled_before:.3f} s of "
             "compilation inside the window")
    peak = peak_bytes(devs)

    coll = None
    if args.trace and cell["chips"] > 1:
        with _mesh_ctx(trainer):
            text = trainer.train_step.lower(
                trainer.state, traffic.draw(0)).compile().as_text()
        coll = collectives(text)
    trainer.train_step = None
    del trainer
    gc.collect()

    pk = peaks_for(devs[0].device_kind) if require_tpu \
        else peaks_for("TPU v5 lite")
    tokens = traffic.target_tokens
    flops_tok = cmod.model_flops_per_token(cfg["model"], mix)
    chips = cell["chips"]
    rate = win["steps"] * tokens / win["seconds"]
    metrics = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        devices, host = tracelib.read_xplane(tdir)
        red = tracelib.reduce(devices, host,
                              tracelib.find_span(host, "bench.window"))
        shutil.rmtree(tdir, ignore_errors=True)
        flop_s = flops_tok * tokens / chips / pk["bf16_flops"]
        byte_s = cmod.least_step_bytes(cfg["model"], cfg["run_config"]) / chips \
            / pk["hbm_bytes_per_s"]
        _log(f"least step time {max(flop_s, byte_s) * 1e3:.4f} ms, bound by "
             f"{'FLOPs' if flop_s >= byte_s else 'HBM bytes'} "
             f"(FLOPs {flop_s * 1e3:.4f} ms, bytes {byte_s * 1e3:.4f} ms); "
             f"{len(devices)} device planes, {win['steps']} traced steps")
        rec = {"trace": red, "trace_steps": win["steps"], "input_s": input_s,
               "peak_bytes": peak, "collectives": coll,
               "least_step_s": max(flop_s, byte_s)}
        for m in reg.metrics(cell["name"], "per_layer"):
            value = reg.metric_module(m["name"]).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
    else:
        e2e = {"tokens_per_s": rate,
               "mfu": 100.0 * flops_tok * rate / (chips * pk["bf16_flops"]),
               "setup_s": setup_s}
        for m in reg.metrics(cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    batches = [traffic.draw(s) for s in range(CHECKED_STEPS)]
    with jax.default_device(devs[0]):
        ref = cmod.reference(cfg["model"], cfg["run_config"], key_seed,
                             batches)
    numbers = check.compare(prog, ref, cell.get("loss_steps", 0))
    correct, compared = check.judge(numbers, cell["limits"])
    lines = [f"reference {time.perf_counter() - t_ref:.1f} s; program "
             f"losses {prog['losses']} reference losses {ref['losses']}"]
    lines += [f"{k} {v['value']:.6g} limit {v['limit']:.6g}"
              for k, v in compared.items()]
    result = {"correct": correct, "attempted": win["steps"], "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = compared
    return result, lines


def main(argv=None) -> int:
    args = _parse(argv)
    try:
        reg = Registry(os.path.dirname(BENCH_DIR))
        result, lines = run_cell(args, reg)
    except NoChip as e:
        _log(f"bench/run.py: {e}")
        return 2
    for line in lines:
        _log(line)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
