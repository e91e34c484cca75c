"""Shared kernel utilities: compiler params and the interpret-mode gate."""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def tpu_compiler_params(dimension_semantics):
    """``pltpu.CompilerParams(dimension_semantics=...)``."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics))


def interpret_default() -> bool:
    """Kernels compile for the chip on a TPU backend and run in Pallas
    interpret mode everywhere else (same source).  On a TPU this is
    always False: nothing can switch the chip path to the emulator."""
    return jax.default_backend() != "tpu"
