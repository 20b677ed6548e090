"""repro.compat — the sharding/mesh names, spelled for JAX 0.9.0.

The single import point for anything whose spelling has changed across JAX
generations. Library code, launchers, benchmarks, and the test subprocess
snippets all use these names; ``jax.sharding.AxisType`` / ``jax.set_mesh`` /
``jax.shard_map`` must never be imported directly outside this package
(enforced by tests/test_compat.py).

    from repro.compat import make_mesh, use_mesh, shard_map
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        ...
"""
from repro.compat.version import (MIN_SUPPORTED, capabilities,
                                  jax_version_tuple, supported)
from repro.compat.shardmesh import (AxisType, Mesh, NamedSharding, P,
                                    PartitionSpec, cost_analysis, make_mesh,
                                    shard_map, use_mesh)

__all__ = [
    "MIN_SUPPORTED", "capabilities", "jax_version_tuple", "supported",
    "AxisType", "Mesh", "NamedSharding", "P", "PartitionSpec",
    "cost_analysis", "make_mesh", "shard_map", "use_mesh",
]
