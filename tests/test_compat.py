"""repro.compat: mesh construction and the installed-JAX report on the one
supported JAX (0.9.0), and the gate that keeps its spellings in compat."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.compat import version


# ---------------------------------------------------------------------------
# installed-JAX report
# ---------------------------------------------------------------------------

def test_version_tuple_parses_current_jax():
    vt = compat.jax_version_tuple()
    assert len(vt) == 3 and all(isinstance(x, int) for x in vt)
    assert vt >= compat.MIN_SUPPORTED
    assert compat.supported()


def test_version_tuple_strips_dev_suffixes(monkeypatch):
    monkeypatch.setattr(jax, "__version__", "0.7.2.dev20250101")
    assert version.jax_version_tuple() == (0, 7, 2)
    monkeypatch.setattr(jax, "__version__", "0.4.37rc1")
    assert version.jax_version_tuple() == (0, 4, 37)
    monkeypatch.setattr(jax, "__version__", "1.0")
    assert version.jax_version_tuple() == (1, 0, 0)


def test_probes_match_installed_jax():
    # the spellings compat re-exports exist on the installed JAX
    assert compat.AxisType is jax.sharding.AxisType
    assert hasattr(jax, "set_mesh") and hasattr(jax, "shard_map")
    caps = compat.capabilities()
    assert caps["jax_version"] == jax.__version__
    assert caps["min_supported"] == list(compat.MIN_SUPPORTED)
    assert caps["supported"] == compat.supported()


# ---------------------------------------------------------------------------
# mesh construction on the current JAX (single device -> (1,) meshes only;
# multi-device construction is covered by every `distributed` test)
# ---------------------------------------------------------------------------

def test_make_mesh_current_jax():
    mesh = compat.make_mesh((1,), ("data",))
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == 1


def test_make_mesh_accepts_axis_types_kwarg():
    mesh = compat.make_mesh((1,), ("data",),
                            axis_types=(compat.AxisType.Auto,))
    assert mesh.axis_names == ("data",)


def test_use_mesh_is_reentrant_context():
    mesh = compat.make_mesh((1,), ("data",))
    with compat.use_mesh(mesh) as m:
        assert m is mesh
        with compat.use_mesh(mesh):
            pass


def test_shard_map_runs_on_current_jax():
    mesh = compat.make_mesh((1,), ("data",))
    fn = compat.shard_map(lambda x: x * 2, mesh=mesh,
                          in_specs=compat.P("data"),
                          out_specs=compat.P("data"), check_vma=False)
    np.testing.assert_array_equal(np.asarray(fn(jnp.arange(4.0))),
                                  np.arange(4.0) * 2)


# ---------------------------------------------------------------------------
# cost_analysis normalization (a dict, empty where XLA reports none)
# ---------------------------------------------------------------------------

def test_cost_analysis_normalized_shapes():
    class _C:
        def __init__(self, ret):
            self._ret = ret

        def cost_analysis(self):
            return self._ret

    assert compat.cost_analysis(_C({"flops": 3.0})) == {"flops": 3.0}
    assert compat.cost_analysis(_C(None)) == {}
    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    assert compat.cost_analysis(compiled).get("flops", 0) > 0


# ---------------------------------------------------------------------------
# the import-hygiene gate from the issue: no direct AxisType imports outside
# the compat package
# ---------------------------------------------------------------------------

def test_no_direct_version_dependent_jax_api_outside_compat():
    """Every spelling that differs across the supported JAX range must stay
    inside repro/compat — in code AND comments, so stale guidance can't
    creep back either."""
    import os
    import re
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    forbidden = [
        re.compile(r"from\s+jax\.sharding\s+import\s[^\n]*\bAxisType\b"),
        re.compile(r"jax\.sharding\.AxisType"),
        re.compile(r"jax\.set_mesh"),
        re.compile(r"jax\.shard_map"),
        re.compile(r"from\s+jax\.experimental\.shard_map\s+import"),
        re.compile(r"pltpu\.(?:TPU)?CompilerParams"),
        re.compile(r"\w+\.cost_analysis\(\)"),   # use compat.cost_analysis
    ]
    this_file = os.path.abspath(__file__)
    compat_dir = os.path.join(root, "src", "repro", "compat") + os.sep
    offenders = []
    for top in ("src", "tests", "benchmarks", "tools"):
        for dirpath, _, names in os.walk(os.path.join(root, top)):
            for name in names:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                if path == this_file or path.startswith(compat_dir):
                    continue
                text = open(path).read()
                for pat in forbidden:
                    for m in pat.finditer(text):
                        line = text[:m.start()].count("\n") + 1
                        offenders.append(f"{path}:{line}: {m.group(0)}")
    assert not offenders, \
        "version-dependent JAX API outside repro/compat:\n" \
        + "\n".join(offenders)


def test_check_env_smoke():
    """tools/check_env.py prints one json line and exits 0 — the one-line
    environment-drift diagnosis."""
    import json
    import os
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_env.py")
    proc = subprocess.run([sys.executable, tool, "--json"],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    assert report["jax"]["jax_version"] == jax.__version__
    assert "hypothesis" in report["optional_deps"]
    assert report["ok"] is True
