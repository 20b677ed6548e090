#!/usr/bin/env python
"""One-line environment drift diagnosis.

    PYTHONPATH=src python tools/check_env.py [--json]

Prints the JAX version against the one supported JAX, device count, and
optional-dependency presence, then a PASS/WARN verdict — so a broken
environment shows up as one readable line instead of 16 cryptic test
failures. tests/test_compat.py::test_check_env_smoke runs this on every
suite invocation.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

OPTIONAL_DEPS = ("hypothesis",)


def collect() -> dict:
    import jax
    from repro import compat

    report = {
        "python": sys.version.split()[0],
        "jax": compat.capabilities(),
        "jaxlib": getattr(__import__("jaxlib"), "__version__", "?"),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
        "devices": [str(d) for d in jax.devices()[:8]],
        "remesh": _remesh_eligibility(),
        "attribution": _attribution_eligibility(),
        "topology": _host_topology(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "optional_deps": {
            name: importlib.util.find_spec(name) is not None
            for name in OPTIONAL_DEPS
        },
        "embed_impl_pallas": _probe_pallas(),
        "kernel_autotune": _autotune_status(),
        "fused_apply": _fused_apply_eligibility(),
        "serving": _serve_eligibility(),
        "analysis": _analysis_status(),
    }
    report["ok"] = bool(report["jax"]["supported"])
    return report


def _analysis_status() -> dict:
    """Static-analysis availability and the repo's current lint status
    (analysis/lint.py): the CI `lint` job fails on any finding, so a
    non-zero count here predicts that failure locally."""
    try:
        from repro.analysis.lint import lint_repo
        findings = lint_repo()
        return {"available": True, "lint_findings": len(findings),
                "clean": not findings,
                "kinds": sorted({f.kind for f in findings})}
    except Exception as e:
        return {"available": False, "clean": False,
                "error": f"{type(e).__name__}: {e}"}


def _autotune_status() -> dict:
    """Embedding-kernel autotune cache state (kernels/autotune.py): cold
    means the first --kernel-autotune run on this machine pays the measured
    sweep (or keeps fixed tiles if REPRO_AUTOTUNE_NO_MEASURE=1)."""
    from repro.kernels import autotune
    st = autotune.cache_status()
    st["measurement_allowed"] = autotune.measurement_allowed()
    return st


def _fused_apply_eligibility() -> dict:
    """Would the default config get the fused bucket-apply here? Mirrors
    core/buckets.fused_apply_eligible: needs a bucketed dense exchange
    (bucket_bytes > 0, a data axis), an optimizer with a bucket-native
    update (adamw | momentum), zero_stage 0, and opau."""
    from repro.configs.base import RunConfig
    cfg = RunConfig()
    reasons = []
    if not cfg.fused_apply:
        reasons.append("fused_apply disabled")
    if cfg.optimizer not in ("adamw", "momentum"):
        reasons.append(f"optimizer {cfg.optimizer} has no fused update")
    if cfg.zero_stage != 0:
        reasons.append(f"zero_stage {cfg.zero_stage} shards the moments")
    if not cfg.opau:
        reasons.append("opau off (no aggregated update)")
    if not cfg.bucket_bytes:
        reasons.append("bucket_bytes 0 (per-tensor exchange)")
    return {"eligible": not reasons, "blockers": reasons,
            "optimizer": cfg.optimizer,
            "requires": "bucketed dense exchange on a data-parallel mesh"}


def _serve_eligibility() -> dict:
    """What the rebuilt serving engine (runtime/server.py) gets on this
    host: which families can take the batched-prefill path (a positional
    KV cache — recurrent carries fall back to ToyServer), how many
    prefill executables a default-sized engine would trace (one per
    power-of-two length bucket), and whether sampling runs on device."""
    from repro.runtime.server import MIN_BUCKET, ServerConfig, \
        prefill_buckets
    scfg = ServerConfig()
    buckets = prefill_buckets(scfg.max_seq, MIN_BUCKET)
    return {
        "paged_families": ["dense", "moe", "vlm"],
        "toy_fallback_families": ["lstm", "ssm", "hybrid", "encdec"],
        "max_seq": scfg.max_seq,
        "prefill_buckets": buckets,
        "prefill_executables": len(buckets),
        "sampling": ("device argmax" if scfg.greedy
                     else f"device categorical @T={scfg.temperature}"),
        "detokenize_thread": True,   # engine always runs host work off-path
    }


def _remesh_eligibility() -> dict:
    """Can the elastic auto-remesh path (Trainer.remesh_on_straggle /
    launch/mesh.shrink_mesh) actually shrink a data axis here? It needs at
    least 2 devices on that axis — a 1-device host can exercise the
    escalation policy but never the shrink itself."""
    import jax
    n = jax.device_count()
    return {
        "devices": n,
        "hosts": jax.process_count(),
        "max_data_parallel": n,               # all-data mesh upper bound
        "can_shrink_data_axis": n >= 2,
    }


def _attribution_eligibility() -> dict:
    """Can straggler *attribution* (RunConfig.heartbeat -> the monitor's
    per-slice EMAs -> an attributed eviction) do real work here? Per-slice
    heartbeats need >= 2 data slices to compare against each other, and the
    attributed shrink only resolves to shrink_mesh(drop_process_index=...)
    when each data slice is wholly owned by one process — on a
    single-process host the eviction still drops the attributed *grid*
    slice, and the bounded-staleness fallback (RunConfig.max_staleness +
    --stale-on-jitter) is available regardless."""
    import jax
    from repro.launch.mesh import make_mesh, slice_for_process
    n = jax.device_count()
    hosts = jax.process_count()
    per_process_slices = None
    if hosts > 1 and n % hosts == 0:
        # would every process map to one whole data slice on the natural
        # (hosts, n // hosts) mesh? (the drop_process_index fast path)
        mesh = make_mesh((hosts, n // hosts), ("data", "model"))
        owned = [slice_for_process(mesh, p) for p in range(hosts)]
        per_process_slices = all(s is not None for s in owned)
    return {
        "heartbeats_comparable": n >= 2,      # >= 2 slices to EMA against
        "process_eviction": bool(per_process_slices),
        "grid_eviction": n >= 2,              # single-controller fallback
        "probation_readmit": n >= 2,          # grow needs a slice to return
        "stale_fallback": True,               # plan-level, mesh-independent
    }


def _host_topology() -> dict:
    """Detected host topology — the H and L of the two-level exchange
    schedule (core/cost_model.py). Hierarchical pricing additionally needs
    fitted inter-host α/β constants (tools/profile_collectives.py fit →
    RunConfig.hw_profile); the default roofline HW is single-tier."""
    import jax
    from repro.utils.roofline import HW
    per_host: dict[int, int] = {}
    for d in jax.devices():
        p = getattr(d, "process_index", 0)
        per_host[p] = per_host.get(p, 0) + 1
    sizes = sorted(set(per_host.values()))
    return {
        "hosts": len(per_host),
        "local_devices_per_host": sizes,
        "uniform": len(sizes) <= 1,
        "hierarchical_hw": HW.hierarchical,
    }


def _probe_pallas() -> dict:
    """Can RunConfig.embed_impl='pallas' serve the sparse hot path here?
    On the CPU backend the kernels run in interpret mode — available but
    slow."""
    try:
        import numpy as np
        from repro.kernels import ops
        out = ops.embed_gather(np.zeros((8, 4), np.float32),
                               np.zeros((4,), np.int32))
        return {"available": bool(np.asarray(out).shape == (4, 4)),
                "interpret_mode": ops.interpret_mode()}
    except Exception as e:  # pallas import / lowering failure
        return {"available": False, "error": f"{type(e).__name__}: {e}"}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="machine-readable single-line report")
    args = ap.parse_args()
    report = collect()
    if args.json:
        print(json.dumps(report))
        return 0 if report["ok"] else 1

    j = report["jax"]
    print(f"python {report['python']}  jax {j['jax_version']}  "
          f"jaxlib {report['jaxlib']}  backend={report['backend']}  "
          f"devices={report['device_count']} "
          f"(local={report['local_device_count']}, "
          f"hosts={report['process_count']})")
    print(f"compat: supported(>= "
          f"{'.'.join(map(str, j['min_supported']))})={j['supported']}")
    missing = [k for k, v in report["optional_deps"].items() if not v]
    present = [k for k, v in report["optional_deps"].items() if v]
    print("optional deps: "
          + "  ".join([f"{k}=yes" for k in present]
                      + [f"{k}=no (tests fall back to tests/_prop.py shim)"
                         for k in missing]))
    pal = report["embed_impl_pallas"]
    if pal.get("available"):
        mode = "interpret mode (CPU)" if pal.get("interpret_mode") \
            else "compiled (Mosaic)"
        print(f"embed_impl=pallas: available, {mode}")
    else:
        print("embed_impl=pallas: UNAVAILABLE "
              f"({pal.get('error', 'unknown')}) — use embed_impl=jnp")
    at = report["kernel_autotune"]
    print(f"kernel autotune: cache {at['state']} "
          f"({at['entries']} entries, {at['backend_entries']} for this "
          f"backend) at {at['path']}  "
          f"measurement={'allowed' if at['measurement_allowed'] else 'OFF'}")
    fa = report["fused_apply"]
    if fa["eligible"]:
        print(f"fused apply: eligible (optimizer={fa['optimizer']}; "
              f"needs {fa['requires']})")
    else:
        print("fused apply: NOT eligible — " + "; ".join(fa["blockers"]))
    topo = report["topology"]
    tier = "fitted (two-level pricing active on multi-host meshes)" \
        if topo["hierarchical_hw"] else \
        "unset — run tools/profile_collectives.py fit for two-level pricing"
    print(f"topology: hosts={topo['hosts']} "
          f"local_devices={topo['local_devices_per_host']} "
          f"uniform={topo['uniform']}  inter α/β: {tier}")
    rm = report["remesh"]
    print(f"elastic remesh: data axis can shrink="
          f"{rm['can_shrink_data_axis']} "
          f"(devices={rm['devices']}, hosts={rm['hosts']}; "
          f"remesh_on_straggle drops one data slice per escalation)")
    at = report["attribution"]
    evict = "by process" if at["process_eviction"] else \
        "by grid slice" if at["grid_eviction"] else "n/a (1 device)"
    print(f"straggler attribution: heartbeats comparable="
          f"{at['heartbeats_comparable']}  eviction resolves {evict}  "
          f"probation/readmit={at['probation_readmit']}  "
          f"stale fallback=always (plan-level)")
    an = report["analysis"]
    if an.get("available"):
        status = "clean" if an["clean"] else \
            f"{an['lint_findings']} finding(s) {an['kinds']}"
        print(f"static analysis: spmd lint {status}; plan-contract checker "
              "available (RunConfig.verify_contract, tools/spmd_lint.py)")
    else:
        print("static analysis: UNAVAILABLE "
              f"({an.get('error', 'unknown')})")
    sv = report["serving"]
    print(f"serving: paged engine for {'/'.join(sv['paged_families'])} "
          f"({sv['prefill_executables']} prefill buckets "
          f"{sv['prefill_buckets']} at max_seq={sv['max_seq']}), "
          f"sampling={sv['sampling']}, detokenize thread="
          f"{'on' if sv['detokenize_thread'] else 'off'}; "
          f"{'/'.join(sv['toy_fallback_families'])} -> ToyServer")
    print("PASS" if report["ok"] else
          "WARN: JAX older than the supported version — tier-1 results are "
          "not meaningful")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
