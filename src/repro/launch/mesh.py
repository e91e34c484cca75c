"""Production mesh construction (assignment-mandated shape).

A function, not a module-level constant: importing this module never touches
jax device state.  Single pod: (data=16, model=16) = 256 chips (v5e-256).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis carries
only the cross-pod gradient all-reduce (or acts as the pipeline-stage axis
when pipeline parallelism is enabled) because inter-pod links are the
scarcest bandwidth — the paper's "routing" objective (Tab. 1 RT) maps to
keeping traffic off that axis.

``make_mesh`` is the one place meshes are built: every axis is an Auto
axis, so GSPMD propagates shardings through the un-annotated model code.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serving_mesh(tp: int) -> jax.sharding.Mesh:
    """1-axis ``("model",)`` mesh over the first ``tp`` devices — the
    tensor-parallel serving mesh (``launch/serve.py --mesh``).  Use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to simulate
    N devices on CPU."""
    devs = jax.devices()
    if tp < 1:
        raise ValueError(f"mesh size must be >= 1, got {tp}")
    if tp > len(devs):
        raise ValueError(
            f"mesh size {tp} exceeds visible devices ({len(devs)}); set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp} to "
            f"simulate")
    return make_mesh((tp,), ("model",), devices=devs[:tp])


def make_host_mesh(shape=None, axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        model = 1
        for cand in (4, 2, 1):
            if n % cand == 0:
                model = cand
                break
        shape = (n // model, model)
    return make_mesh(shape, axes)
