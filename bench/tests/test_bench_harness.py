"""The harness finds everything by name, and refuses a machine without a
chip."""
import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, BENCH)
sys.path.insert(0, TESTS)
import run  # noqa: E402
from benchtree import make_tree  # noqa: E402
from lib.registry import Registry  # noqa: E402


def test_every_name_in_the_benchmark_resolves_to_files():
    reg = Registry()
    for w in reg.spec["workloads"]:
        cell = reg.cell(w["name"])
        assert cell["chips"] in (1, 4) and "limits" in cell
        assert reg.config(cell["config"])["arch"]
        assert reg.traffic(cell["traffic"])["global_batch"] > 0
        cmod = reg.config_module(cell["config"])
        assert callable(cmod.model_flops_per_token)
        for kind in ("end_to_end", "per_layer"):
            assert reg.metrics(w["name"], kind)
    for m in reg.spec["per_layer"]:
        assert callable(reg.metric_module(m["name"]).read)


def test_a_cell_config_and_metric_added_as_files_are_found(tmp_path):
    root = make_tree(str(tmp_path))
    with open(os.path.join(root, "bench", "metrics", "traced_steps.py"),
              "w") as f:
        f.write("def read(rec):\n    return rec.get('trace_steps')\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["per_layer"].append({
        "name": "traced_steps", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "device", "moves": "tokens_per_s",
        "workloads": ["tiny-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    reg = Registry(root)
    cell = reg.cell("tiny-cell")
    assert cell["config"] == "tiny_parallax_lm" and cell["mesh"] is None
    assert reg.config("tiny_parallax_lm")["model"]["d_model"] == 64
    assert reg.traffic(cell["traffic"])["seq_len"] == 8
    names = [m["name"] for m in reg.metrics("tiny-cell", "per_layer")]
    assert "traced_steps" in names and "exchange_mb_per_step" not in names
    assert "traced_steps" not in [
        m["name"] for m in reg.metrics("lm-1chip-zipf", "per_layer")]
    assert reg.metric_module("traced_steps").read({"trace_steps": 7}) == 7
    with pytest.raises(KeyError):
        reg.cell("no-such-cell")
    with pytest.raises(ValueError):
        reg.traffic("../BENCHMARK")


def test_refuses_a_machine_without_a_tpu(capsys):
    rc = run.main(["--workload", "lm-1chip-zipf", "--seed", str(2 ** 33),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "no TPU" in out.err


def test_refuses_to_run_from_the_benchmark_files_alone(tmp_path):
    make_tree(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lm-1chip-zipf",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
