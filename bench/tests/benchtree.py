"""A copy of the benchmark in a temporary directory with one cell added the
way a later change adds one: a configuration, a traffic mix, a workload
file and their entries in ``BENCHMARK.json``, and nothing else edited."""
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TINY = {"vocab_size": 512, "d_model": 64, "d_ff": 128}


def make_tree(root: str, base: str = "parallax_lm", mesh=None,
              cell: str = "tiny-cell") -> str:
    """-> the copy's root. The added configuration is ``base`` at ``TINY``
    widths; the added cell uses it at seq 8 and 8 rows per chip."""
    dst = os.path.join(root, "bench")
    if not os.path.isdir(dst):
        shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
            "__pycache__", ".trace", "tests"))
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(dst, "configs", base + ".json")) as f:
        cfg = json.load(f)
    cfg["model"].update(TINY)
    name = "tiny_" + base
    with open(os.path.join(dst, "configs", name + ".json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(dst, "configs", base + ".py"),
                os.path.join(dst, "configs", name + ".py"))
    chips = 4 if mesh else 1
    with open(os.path.join(dst, "traffic", f"tiny{chips}.json"), "w") as f:
        json.dump({"seq_len": 8, "global_batch": 8 * chips, "zipf_a": 1.3,
                   "src_zipf_a": 1.3}, f)
    with open(os.path.join(dst, "workloads", cell + ".json"), "w") as f:
        json.dump({"mesh": mesh, "limits": {
            "loss_gap": 0.01, "grad_gap": 0.05, "change_gap": 0.1}}, f)
    if not any(c["name"] == name for c in spec["configs"]):
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": name,
                              "traffic": f"tiny{chips}", "chips": chips,
                              "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
