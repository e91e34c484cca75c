"""Unit + property tests for repro.core — the transformation toolbox."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import (
    TABLE1, TABLE2, Level, Objective, PipelineModel, Roofline, TilePlanner,
    TransformClass, TPU_V5E, by_class, cross_input_interleave,
    dequantize_block, flatten_grid, fuse_phases, interleaved_accumulate,
    lane_utilization, machine_balance, quantize_block, recommend,
    tiled_accumulate, vector_pad,
)
from repro.core.memory import QuantizedBlock


# ---------------------------------------------------------------- taxonomy
def test_table1_covers_all_three_classes():
    for cls in TransformClass:
        assert len(by_class(cls)) >= 3, cls


def test_table1_count_matches_paper():
    # 7 pipelining + 4 scaling + 4 memory transformations
    assert len(TABLE1) == 15


def test_every_objective_has_a_recommendation():
    for obj in Objective:
        assert recommend(obj), f"no transformation targets {obj}"


def test_transformations_name_repo_entrypoints():
    for t in TABLE1.values():
        assert t.tpu_mechanism and t.fpga_mechanism
        assert t.repo_entrypoints, t.name


# ---------------------------------------------------------- pipeline model
def test_pipeline_model_eq1():
    pm = PipelineModel(latency=100, initiation_interval=2, n=51)
    assert pm.cycles() == 100 + 2 * 50


def test_pipeline_sequential_composition():
    a = PipelineModel(10, 1, 100)
    b = PipelineModel(20, 2, 100)
    c = a.then(b)
    assert c.latency == 30 and c.initiation_interval == 2


def test_folding_cuts_iterations():
    pm = PipelineModel(10, 1, 1000).folded(8)
    assert pm.n == 125


# --------------------------------------------------- accumulation interleave
@settings(max_examples=30, deadline=None)
@given(st.integers(3, 400), st.integers(1, 16))
def test_interleaved_accumulate_matches_sum(n, lanes):
    xs = jnp.asarray(np.random.default_rng(n).normal(size=n), jnp.float32)
    got = interleaved_accumulate(xs, lanes=lanes)
    np.testing.assert_allclose(got, xs.sum(), rtol=1e-5, atol=1e-5)


def test_interleaved_accumulate_max():
    xs = jnp.asarray(np.random.default_rng(0).normal(size=777), jnp.float32)
    got = interleaved_accumulate(xs, lanes=8, op=jnp.maximum, init=-jnp.inf)
    assert got == xs.max()


def test_tiled_accumulate():
    def terms(idx):
        return jnp.sin(idx.astype(jnp.float32))[:, None] * jnp.ones((1, 3))

    got = tiled_accumulate(terms, n=64, tile=8, out_shape=(3,))
    want = jnp.sin(jnp.arange(64.0))[:, None].sum(0) * jnp.ones(3)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_cross_input_interleave_is_vmapped_iteration():
    def step(x):
        return 0.5 * x + 1.0

    states = jnp.arange(8.0)
    got = cross_input_interleave(step, states, n_steps=10)
    want = states
    for _ in range(10):
        want = 0.5 * want + 1.0
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_fuse_phases_equals_composition():
    phases = [jnp.sin, jnp.cos, jnp.tanh]
    x = jnp.linspace(-1, 1, 17)
    np.testing.assert_allclose(
        fuse_phases(phases)(x), jnp.tanh(jnp.cos(jnp.sin(x))), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(1, 7), min_size=1, max_size=4))
def test_flatten_grid_roundtrip(dims):
    total, unflatten = flatten_grid(dims)
    assert total == int(np.prod(dims))
    for flat in [0, total - 1, total // 2]:
        idx = [int(v) for v in unflatten(jnp.asarray(flat))]
        want = list(np.unravel_index(flat, dims))
        assert idx == want


# -------------------------------------------------------------- tile planner
@settings(max_examples=15, deadline=None)
@given(st.sampled_from([512, 1024, 4096, 8192]),
       st.sampled_from([512, 2048, 8192]),
       st.sampled_from([512, 1024, 8192]))
def test_tileplanner_respects_vmem_and_alignment(m, n, k):
    tp = TilePlanner()
    plan = tp.plan_matmul(m, n, k)
    assert plan.vmem_bytes <= tp.budget
    for b in (plan.bm, plan.bn, plan.bk):
        assert b % 128 == 0


def test_tileplanner_prefers_reuse():
    plan = TilePlanner().plan_matmul(8192, 8192, 8192)
    small = TilePlanner().plan_matmul(256, 256, 8192)
    assert plan.arithmetic_intensity >= small.arithmetic_intensity


@pytest.mark.parametrize("shape", [(8, 3360, 4096), (8, 4096, 3360),
                                   (320, 4096, 4096)])
def test_tileplanner_whole_dim_block_for_unaligned_dims(shape):
    """A dim no MXU-aligned tile divides (a tp=4 shard of d_ff 13440 is
    3360) gets one whole-dim block; aligned dims keep aligned tiles."""
    m, n, k = shape
    plan = TilePlanner().plan_matmul(m, n, k)
    for b, dim in zip((plan.bm, plan.bn, plan.bk), shape):
        assert not dim % min(b, dim)
        assert b % 128 == 0 or b == dim


def test_matmul_kernel_on_whole_dim_block():
    from repro.kernels import dispatch
    x = jax.random.normal(jax.random.key(0), (8, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (64, 336), jnp.float32)
    assert TilePlanner().plan_matmul(8, 336, 64).bn == 336
    with dispatch.stats_scope() as stats:
        got = dispatch.matmul(x, w, policy="kernels")
        assert stats() == {("matmul", "kernel"): 1}
    np.testing.assert_allclose(got, x @ w, rtol=1e-5, atol=1e-4)


def test_vector_pad_and_lane_utilization():
    assert vector_pad((100,), 4) == (128,)
    assert vector_pad((5, 100), 4) == (8, 128)
    assert vector_pad((5, 100), 2) == (16, 128)     # bf16 packs 2x
    assert 0 < lane_utilization((5, 100)) < 1
    assert lane_utilization((8, 128)) == 1.0


# ---------------------------------------------------------------- roofline
def test_roofline_terms_and_dominance():
    r = Roofline("t", chips=256, hlo_flops=197e12 * 256,
                 hlo_bytes=819e9 * 128, collective_bytes=50e9 * 512,
                 model_flops=197e12 * 256)
    assert abs(r.compute_s - 1.0) < 1e-9
    assert abs(r.memory_s - 0.5) < 1e-9
    assert abs(r.collective_s - 2.0) < 1e-9
    assert r.dominant == "collective"
    assert r.useful_flops_ratio == 1.0


def test_machine_balance_positive():
    assert machine_balance(TPU_V5E) > 100  # v5e is very compute-rich


# ----------------------------------------------------------- type demotion
@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3), st.sampled_from([1, 5, 127, 128, 300]),
       st.floats(0.01, 100.0))
def test_quantize_roundtrip_error_bound(ndim, last, scale):
    rng = np.random.default_rng(last)
    shape = tuple([2] * (ndim - 1) + [last])
    x = jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
    qb = quantize_block(x, block=128)
    back = dequantize_block(qb)
    # symmetric int8: error <= scale_per_block / 2 = amax/254
    err = np.abs(np.asarray(back - x))
    bound = np.abs(np.asarray(x)).max() / 127.0 + 1e-7
    assert err.max() <= bound


def test_quantized_block_is_pytree_with_static_block():
    qb = quantize_block(jnp.arange(256.0), block=64)
    leaves, treedef = jax.tree_util.tree_flatten(qb)
    assert len(leaves) == 2
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.block == 64


def test_quantize_shape_preserved():
    x = jnp.ones((3, 5, 257))
    qb = quantize_block(x)
    assert qb.q.shape == x.shape
    assert dequantize_block(qb).shape == x.shape
