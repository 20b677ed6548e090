"""Pallas names the kernels import from one place.

Kernels take ``CompilerParams`` from here instead of from pltpu directly,
so a later JAX that renames it changes this file only.
"""
from __future__ import annotations

from jax.experimental import pallas as pl  # noqa: F401  (re-export)
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (re-export)

CompilerParams = pltpu.CompilerParams
