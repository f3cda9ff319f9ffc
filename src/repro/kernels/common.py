"""Shared kernel plumbing.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling).  The
tests run on the CPU (``JAX_PLATFORMS=cpu``), where `interpret_default()`
puts every kernel into Pallas interpret mode; on any other backend the
kernels are lowered by Mosaic.  The chip is exercised by
``chip_smoke.py`` at the repository root.
"""
from __future__ import annotations

import jax


def interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b
