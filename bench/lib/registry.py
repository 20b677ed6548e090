"""Finds a cell, its configuration, its traffic mix and its metrics by the
names ``BENCHMARK.json`` gives them. Adding a cell, a configuration, a
traffic mix or a per-layer metric means adding files and entries; nothing
here names one.

Layout under the benchmark directory:

  configs/<config>.json   sizes and run settings, as run
  configs/<config>.py     count functions and the plain reference
  traffic/<traffic>.json  parameters of the one traffic generator
  workloads/<cell>.json   the cell's mesh
  metrics/<metric>.py     a reader of the traced run's record
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_module(path: str, tag: str):
    """Import a file of the benchmark by its path, under a private name."""
    mod_name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_", tag)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """``BENCHMARK.json`` at ``root`` and the benchmark's files under
    ``root/bench``."""

    def __init__(self, root: str = REPO_ROOT, bench_dir: str = "bench"):
        self.root = root
        self.dir = os.path.join(root, bench_dir)
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _path(self, kind: str, name: str, ext: str) -> str:
        return os.path.join(self.dir, kind, _checked(name) + ext)

    def _json(self, kind: str, name: str) -> dict:
        with open(self._path(kind, name, ".json")) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        """The ``workloads`` entry of ``name`` merged with its file."""
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return {**self._json("workloads", name), **w}
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return {**json.load(f), "name": name}
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def config_module(self, name: str):
        return load_module(self._path("configs", name, ".py"),
                           f"config_{name}")

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def metrics(self, cell: str, kind: str) -> list:
        """Entries of ``end_to_end`` or ``per_layer`` that ``cell`` reports:
        those with no ``workloads`` key and those that list it."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def metric_module(self, name: str):
        return load_module(self._path("metrics", name, ".py"),
                           f"metric_{name}")
