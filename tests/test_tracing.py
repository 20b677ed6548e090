"""The program's own tracing: the step's named scopes read back as phases
from its compiled HLO (utils/hlo.py), the trainer's host spans on the
profiler's clock and in the monitor's records, and the compile counter
(runtime/monitor.py)."""
import glob
import os
import re

import jax
import pytest

from conftest import distributed_run
from repro.configs import RunConfig, ShapeConfig, get_config, reduced
from repro.data import SyntheticLM
from repro.runtime import monitor as spans
from repro.runtime.monitor import StepMonitor
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.utils import hlo

VOCAB, D_MODEL = 512, 64


@pytest.mark.parametrize("op_name, phase", [
    ("jit(train_step)/jvp(forward)/dot_general", "forward"),
    ("jit(train_step)/transpose(jvp(forward))/lstm/while/body/mul",
     "backward"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/transpose(jvp(forward))/exchange/all_gather",
     "exchange"),
    ("jit(train_step_fused)/shard_map/exchange/psum", "exchange"),
    # an exchange inside the optimizer, an optimizer named nowhere else
    ("jit(f)/optimizer/exchange/psum", "exchange"),
    ("jit(train_step)/shard_map/broadcast", None),
    # a scope name is a whole path component, not a substring
    ("jit(forwarder)/jvp(forward_scan)/mul", None),
    ("jit(train_step)/jvp(forward)/transpose", "forward"),
])
def test_step_phase_rules(op_name, phase):
    assert hlo.step_phase(op_name) == phase


SYNTHETIC_HLO = """\
HloModule jit_train_step, entry_computation_layout={()->()}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %sqrt.1 = f32[8]{0} sqrt(%param_0), metadata={op_name="jit(train_step)/optimizer/sqrt"}
  ROOT %divide.1 = f32[8]{0} divide(%param_0, %sqrt.1), metadata={op_name="jit(train_step)/optimizer/div"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %convert.2 = f32[8]{0} convert(%param_0.1)
}

ENTRY %main.11 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="params"}
  %constant.0 = f32[] constant(0)
  %broadcast.1 = f32[8]{0} broadcast(%constant.0), dimensions={}, metadata={op_name="jit(train_step)/shard_map/broadcast"}
  %dot.3 = f32[8]{0} dot(%p.1, %p.1), metadata={op_name="jit(train_step)/jvp(forward)/dot_general"}
  %mul.4 = f32[8]{0} multiply(%dot.3, %broadcast.1), metadata={op_name="jit(train_step)/transpose(jvp(forward))/mul"}
  %all-reduce.5 = f32[8]{0} all-reduce(%mul.4), replica_groups={{0,1}}, metadata={op_name="jit(train_step)/shard_map/exchange/psum"}
  %fusion.6 = f32[8]{0} fusion(%all-reduce.5), kind=kLoop, calls=%fused_computation.1
  %copy.7 = f32[8]{0} copy(%fusion.6)
  %fusion.8 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.2
  %add.9 = f32[8]{0} add(%copy.7, %fusion.8)
  ROOT %tuple.10 = (f32[8]{0}) tuple(%p.1)
}
"""


def test_step_phases_read_metadata_calls_operands_and_users():
    """An instruction's own op_name decides; a fusion without one takes its
    computation's root's phase, a copy its operand's, a buffer of zeros
    and a fusion whose root names none their first user's; one with no
    way to a phase is left out."""
    ph = hlo.step_phases(SYNTHETIC_HLO)
    assert ph["dot.3"] == "forward"
    assert ph["mul.4"] == "backward"
    assert ph["all-reduce.5"] == "exchange"
    assert ph["fusion.6"] == "optimizer"         # via its called root
    assert ph["copy.7"] == "optimizer"           # via its operand
    assert ph["add.9"] == "optimizer"            # its first operand's
    assert ph["broadcast.1"] == "backward"       # via its user
    assert ph["p.1"] == "forward"                # its first user's
    assert ph["fusion.8"] == "optimizer"         # its user's
    assert "tuple.10" not in ph


def _tiny_lm(total_steps=3, **run):
    cfg = reduced(get_config("parallax-lm"), vocab=VOCAB, d_model=D_MODEL,
                  layers=1)
    shape = ShapeConfig("tiny", seq_len=8, global_batch=4, kind="train")
    rc = RunConfig(remat="none", **run)
    ds = SyntheticLM(cfg.vocab_size, shape.seq_len, shape.global_batch)
    return Trainer(cfg, shape, rc, TrainerConfig(total_steps=total_steps),
                   ds)


def _entry(text: str) -> str:
    body = text[text.index("\nENTRY"):]
    return body[:body.index("\n}")]


def test_tiny_lm_step_has_forward_backward_and_optimizer_ops():
    t = _tiny_lm()
    text = t.train_step.lower(t.state, t.dataset.batch(0)).compile() \
        .as_text()
    ph = hlo.step_phases(text)
    entry = _entry(text)
    names = re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ", entry, re.M)
    found = {ph.get(n) for n in names}
    assert {"forward", "backward", "optimizer"} <= found, found
    # the AdamW update of each table: an entry fusion with the table's
    # shape among its results whose computation takes Adam's square root
    table = f"[{VOCAB},{D_MODEL}]"
    adam = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?) fusion\(.*"
                     r"calls=%([\w.\-]+)", line)
        if m and table in m.group(2):
            comp = text[text.index(f"\n%{m.group(3)} "):]
            if " sqrt(" in comp[:comp.index("\n}")]:
                adam.append(m.group(1))
    assert len(adam) >= 2, "no AdamW fusion over the tables"
    assert {ph.get(n) for n in adam} == {"optimizer"}, adam


BUCKET_CODE = """
import re
from repro.configs import get_config, reduced, RunConfig, ShapeConfig
from repro.core.transform import _abstract_batch, get_runner
from repro.utils import hlo

cfg = reduced(get_config("parallax-lm"), vocab=512, d_model=64, layers=1)
shape = ShapeConfig("tiny", seq_len=8, global_batch=16, kind="train")
mesh = make_mesh((4, 1), ("data", "model"))
with use_mesh(mesh):
    run = get_runner(cfg, shape, RunConfig(remat="none", comm_mode="hybrid"),
                     mesh=mesh)
    text = run.train_step.lower(
        run.state, _abstract_batch(run.model, run.rt)).compile().as_text()
ph = hlo.step_phases(text)
coll = {}
for line in text.splitlines():
    m = re.match(r"\\s*(?:ROOT\\s+)?%([\\w.\\-]+) = .*? "
                 r"(all-reduce|all-gather|reduce-scatter)(?:-start)?\\(", line)
    if m:
        coll[m.group(1)] = [m.group(2), ph.get(m.group(1))]
print("RESULT:" + json.dumps({"buckets": len(run.plan.bucket_plan.buckets),
                              "coll": coll}))
"""


@pytest.mark.distributed
def test_bucket_psum_classifies_as_exchange():
    """On a 4x1 data mesh the bucketed step's collectives — the bucket
    psums, the fused scalar psum and the row all-gathers — are each
    ``exchange``."""
    res = distributed_run(BUCKET_CODE, devices=4, timeout=600)
    assert res["buckets"] >= 1, res
    kinds = {k for k, _ in res["coll"].values()}
    assert "all-reduce" in kinds, res
    assert {p for _, p in res["coll"].values()} == {"exchange"}, res


def _trace_events(trace_dir: str) -> list:
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("train."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def test_trainer_spans_sit_in_the_trace_and_the_monitor(tmp_path):
    """A profiled ``Trainer.run`` of 3 steps: one ``train.step`` per step
    with ``step_num`` 0-2, each holding its ``train.input``,
    ``train.dispatch``, ``train.host_sync`` and ``train.callback``; the
    monitor's records of the same spans agree with the trace."""
    t = _tiny_lm(total_steps=3)
    with jax.profiler.trace(str(tmp_path)):
        t.run(on_metrics=lambda s, m: None)
    evs = _trace_events(str(tmp_path))
    steps = sorted((e for e in evs if e[0] == spans.STEP),
                   key=lambda e: e[1])
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2]
    for name, lo, hi, stats in steps:
        inner = {n for n, s, e, _ in evs if lo <= s and e <= hi}
        assert {spans.INPUT, spans.DISPATCH, spans.HOST_SYNC,
                spans.CALLBACK} <= inner, inner
        rec = t.monitor.step_spans(stats["step_num"])
        assert set(rec) == {spans.STEP, spans.INPUT, spans.DISPATCH,
                            spans.HOST_SYNC, spans.CALLBACK}
        for sub, s, e, _ in evs:
            if lo <= s and e <= hi:
                # the monitor's clock runs inside the annotation's
                assert rec[sub] <= (e - s) * 1e-9 + 1e-6
                assert rec[sub] >= (e - s) * 1e-9 - 1e-3
    tot = t.monitor.span_stats()
    assert tot[spans.STEP]["count"] == 3 == tot[spans.INPUT]["count"]
    assert tot[spans.STEP]["max_s"] <= tot[spans.STEP]["sum_s"]
    assert t.monitor.step_spans(3) is None            # never ran


def test_step_spans_keep_only_the_last_steps(monkeypatch):
    """The per-step records are a ring of ``SPAN_STEPS``: nothing grows
    with the number of steps."""
    mon = StepMonitor()
    for step in range(spans.SPAN_STEPS + 3):
        with mon.step_span(step), mon.span(spans.INPUT):
            pass
        mon.stop()
    assert mon.step_spans(2) is None
    assert mon.step_spans(3) is not None
    assert set(mon.step_spans(spans.SPAN_STEPS + 2)) == {spans.STEP,
                                                         spans.INPUT}
    assert len(mon._recent) == spans.SPAN_STEPS
    with mon.span(spans.CHECKPOINT):                  # outside any step
        pass
    assert spans.CHECKPOINT not in mon.step_spans(spans.SPAN_STEPS + 2)
    assert mon.span_stats()[spans.CHECKPOINT]["count"] == 1


def test_compile_counter_reads_zero_when_steady_and_counts_a_rebuild():
    t = _tiny_lm(total_steps=2)
    t.run()
    before = t.monitor.compiles
    seen = []
    t.tcfg.total_steps = 5
    t.run(on_metrics=lambda s, m: seen.append(m["compiles"]))
    assert t.monitor.compiles == before and set(seen) == {before}
    t.remesh(None)                            # rebuilds the jitted step
    assert t.monitor.span_stats()[spans.REBUILD]["count"] == 1
    t.tcfg.total_steps = 6
    t.run(on_metrics=lambda s, m: seen.append(m["compiles"]))
    assert seen[-1] >= before + 1
