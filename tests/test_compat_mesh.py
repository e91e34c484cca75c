"""Mesh construction through ``launch/mesh.make_mesh``.

``make_mesh`` is the one place meshes are built (lint-enforced); these
tests pin its contract on degenerate 1-device meshes: Auto axis types are
always requested, and a mapped region over such a mesh round-trips values.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import mesh as mesh_mod


# ------------------------------------------------------------- shard_map

def test_shard_map_1device_runs():
    """A degenerate 1-device mapped identity round-trips values exactly."""
    m = mesh_mod.make_mesh((1,), ("model",))
    x = jnp.arange(12.0).reshape(3, 4)
    out = jax.shard_map(lambda t: t * 2.0, mesh=m, in_specs=(P(),),
                        out_specs=P())(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2.0)


def test_shard_map_check_vma_kwarg_both_values():
    """check_vma=False is what sharded serving uses for collective
    outputs; both values run on a make_mesh mesh."""
    m = mesh_mod.make_mesh((1,), ("model",))
    x = jnp.ones((2, 2))
    for flag in (True, False):
        out = jax.shard_map(lambda t: t + 1.0, mesh=m, in_specs=(P(),),
                            out_specs=P(), check_vma=flag)(x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x) + 1.0)


# ------------------------------------------------------------- make_mesh

def test_make_mesh_1device():
    m = mesh_mod.make_mesh((1,), ("model",))
    assert m.shape == {"model": 1}
    assert m.axis_names == ("model",)
    assert m.axis_types == (jax.sharding.AxisType.Auto,)


def test_make_mesh_axis_types_branch(monkeypatch):
    """The axis_types kwarg (one Auto per axis) and devices flow through
    to ``jax.make_mesh``."""
    seen = {}

    def fake_make_mesh(shape, axes, **kwargs):
        seen.update(shape=shape, axes=axes, kwargs=kwargs)
        return "mesh-sentinel"

    monkeypatch.setattr(jax, "make_mesh", fake_make_mesh)
    devs = jax.devices()[:1]
    assert mesh_mod.make_mesh((1,), ("model",),
                              devices=devs) == "mesh-sentinel"
    assert seen["shape"] == (1,) and seen["axes"] == ("model",)
    assert seen["kwargs"]["axis_types"] == (jax.sharding.AxisType.Auto,)
    assert seen["kwargs"]["devices"] == devs


# ------------------------------------------------------ make_serving_mesh

def test_make_serving_mesh_degenerate():
    m = mesh_mod.make_serving_mesh(1)
    assert m.shape == {"model": 1}


def test_make_serving_mesh_bounds():
    with pytest.raises(ValueError, match=">= 1"):
        mesh_mod.make_serving_mesh(0)
    with pytest.raises(ValueError, match="exceeds visible devices"):
        mesh_mod.make_serving_mesh(len(jax.devices()) + 1)
