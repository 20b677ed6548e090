"""The one supported JAX and a report of what is installed.

The repo targets exactly JAX 0.9.0 (``MIN_SUPPORTED``): its spellings are
the ones ``repro.compat`` re-exports, and nothing here branches on another
version. ``supported()`` lets tools/check_env.py flag an install that is
older than that.
"""
from __future__ import annotations

import jax

MIN_SUPPORTED = (0, 9, 0)


def jax_version_tuple() -> tuple:
    """(major, minor, patch) ints; dev/rc suffixes stripped."""
    parts = []
    for tok in jax.__version__.split(".")[:3]:
        digits = ""
        for ch in tok:
            if not ch.isdigit():
                break
            digits += ch
        if not digits:
            break
        parts.append(int(digits))
    while len(parts) < 3:
        parts.append(0)
    return tuple(parts)


def supported() -> bool:
    return jax_version_tuple() >= MIN_SUPPORTED


def capabilities() -> dict:
    """Installed-JAX report (tools/check_env.py, debugging)."""
    return {
        "jax_version": jax.__version__,
        "jax_version_tuple": list(jax_version_tuple()),
        "min_supported": list(MIN_SUPPORTED),
        "supported": supported(),
    }
