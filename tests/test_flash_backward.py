"""Differential + routing tests for the fused flash-attention backward.

Three layers of evidence, mirroring the dispatch-differential discipline:

1. kernel-level — ``flash_attention_bwd`` (fused recompute Pallas kernels,
   interpret mode) against the dense reference VJP on fixed seeds, over
   {fp32, bf16} x causal/sliding-window, plus the lse residual itself;
2. model-level — gradients of ``layers.attention_blockwise`` through
   ``dispatch`` with policy "kernels" vs "reference" for every assigned
   arch's own attention geometry (GQA/MQA, window, qkv bias, M-RoPE);
3. route-level — a real train step with ``dispatch="kernels"`` inside a
   ``forbid_dense_scores()`` scope: the counters prove the fused backward
   fired and the tripwire proves no dense (S, S) lowering could have.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCHS
from repro.core.memory import DtypePolicy
from repro.kernels import dispatch
from repro.kernels.attention import flash_attention, flash_attention_bwd
from repro.kernels.attention import ref
from repro.models import layers
from repro.models.transformer import ExecOptions, Model, _attn_spec

KEY = jax.random.key(0)
B, S = 2, 8

DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
}
TOLS = {
    "float32": dict(rtol=5e-4, atol=5e-4),
    "bfloat16": dict(rtol=8e-2, atol=8e-2),
}
MASKS = {"causal": (True, 0), "window": (True, 12), "full": (False, 0)}


def _assert_close(got, want, dtype_name, msg=""):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               err_msg=msg, **TOLS[dtype_name])


def _fused_plan(s):
    return {"level": 3, "block_q": min(16, s), "block_kv": min(32, s)}


# ------------------------------------------------------------ kernel level
@pytest.mark.parametrize("mask_name", sorted(MASKS))
@pytest.mark.parametrize("dtype_name", sorted(DTYPES))
def test_fused_backward_matches_reference_vjp(dtype_name, mask_name):
    causal, window = MASKS[mask_name]
    dtype = DTYPES[dtype_name]
    b, h, s, hd = 2, 3, 64, 16
    ks = jax.random.split(KEY, 4)
    q, k, v = (jax.random.normal(kk, (b, h, s, hd), dtype) for kk in ks[:3])
    do = jax.random.normal(ks[3], (b, h, s, hd), jnp.float32)
    o, lse = flash_attention(q, k, v, causal=causal, window=window,
                             plan=_fused_plan(s), return_residuals=True)
    dq, dk, dv = flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                                     window=window, plan=_fused_plan(s))
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention_ref(q_, k_, v_, causal=causal,
                                             window=window), q, k, v)
    want = vjp(do)
    for got, ref_g, name in zip((dq, dk, dv), want, ("dq", "dk", "dv")):
        assert got.dtype == ref_g.dtype
        _assert_close(got, ref_g, dtype_name, f"{name} {mask_name}")


@pytest.mark.parametrize("mask_name", sorted(MASKS))
def test_forward_lse_residual_matches_reference(mask_name):
    causal, window = MASKS[mask_name]
    b, h, s, hd = 1, 2, 32, 16
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, hd), jnp.float32)
               for kk in ks)
    o, lse = flash_attention(q, k, v, causal=causal, window=window,
                             plan=_fused_plan(s), return_residuals=True)
    o_only = flash_attention(q, k, v, causal=causal, window=window,
                             plan=_fused_plan(s))
    _assert_close(o, o_only, "float32")       # residuals don't perturb o
    want = ref.attention_lse_ref(q, k, causal=causal, window=window)
    _assert_close(lse, want, "float32")


def test_backward_reference_level_matches_vjp_exactly():
    """plan level T1 (the stash schedule) IS the dense reference VJP.

    The level-1 backward runs inside ``jax.jit``, so it is compared with
    the same VJP under ``jax.jit``: XLA's fusion of the jitted graph
    reorders float32 sums (eager op-by-op dispatch differs in the last
    bit), and only a like-for-like compile can be held to bit equality."""
    b, h, s, hd = 1, 2, 16, 8
    ks = jax.random.split(jax.random.key(3), 4)
    q, k, v = (jax.random.normal(kk, (b, h, s, hd), jnp.float32)
               for kk in ks[:3])
    do = jax.random.normal(ks[3], (b, h, s, hd), jnp.float32)
    o, lse = flash_attention(q, k, v, plan=_fused_plan(s),
                             return_residuals=True)
    got = flash_attention_bwd(q, k, v, o, lse, do, plan={"level": 1})

    @jax.jit
    def ref_vjp(q_, k_, v_, do_):
        _, vjp = jax.vjp(ref.attention_ref, q_, k_, v_)
        return vjp(do_)

    for g, w in zip(got, ref_vjp(q, k, v, do)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------------- model level
def _positions(cfg):
    if cfg.mrope_sections:
        return jnp.broadcast_to(
            jnp.arange(S)[None, :, None],
            (B, S, len(cfg.mrope_sections))).astype(jnp.int32)
    return jnp.broadcast_to(jnp.arange(S)[None, :], (B, S)).astype(jnp.int32)


@pytest.mark.parametrize("dtype_name", sorted(DTYPES))
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_attention_grad_differential(arch, dtype_name):
    """d(loss)/d(params, x) of the arch's attention block agrees between
    the fused-kernel route and the reference route — the gradient twin of
    test_attention_differential, covering GQA grouping (the KV-head
    broadcast VJP reduces dK/dV over query-head groups) and windows."""
    cfg = ARCHS[arch].smoke()
    mixers = {m for m, _ in cfg.layer_kinds()}
    if not ({"attn", "swa"} & mixers):
        pytest.skip("attention-free arch")
    mixer = "swa" if "swa" in mixers else "attn"
    dt = DtypePolicy(compute=DTYPES[dtype_name])
    spec_k = _attn_spec(dataclasses.replace(cfg, dispatch="kernels"), mixer)
    spec_r = _attn_spec(dataclasses.replace(cfg, dispatch="reference"),
                        mixer)
    p = layers.attention_init(KEY, spec_r)
    x = (0.2 * jax.random.normal(jax.random.key(1), (B, S, cfg.d_model),
                                 jnp.float32)).astype(dt.compute)
    pos = _positions(cfg)
    cot = jax.random.normal(jax.random.key(2), (B, S, cfg.d_model),
                            jnp.float32)

    def make_loss(spec):
        def loss(p_, x_):
            out = layers.attention_blockwise(p_, spec, x_, pos, dt)
            return jnp.sum(out.astype(jnp.float32) * cot)
        return loss

    with dispatch.stats_scope() as stats_fn:
        gk = jax.grad(make_loss(spec_k), argnums=(0, 1))(p, x)
        stats = stats_fn()
    assert stats.get(("attention_bwd", "kernel"), 0) == 1, stats
    assert stats.get(("attention_bwd", "reference"), 0) == 0
    gr = jax.grad(make_loss(spec_r), argnums=(0, 1))(p, x)
    jax.tree.map(
        lambda got, want: _assert_close(got, want, dtype_name,
                                        f"{arch} grads"), gk, gr)


# ------------------------------------------------------------- route level
def _tiny_cfg(name="gemma-2b", **overrides):
    cfg = ARCHS[name].smoke()
    return dataclasses.replace(
        cfg, d_model=32, n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
        vocab_size=128, **overrides)


def test_train_step_takes_fused_backward_route():
    """A dispatch="kernels" train step routes the attention backward
    through the fused Pallas kernels — and, under forbid_dense_scores(),
    provably never materializes an (S, S) score tensor on that route."""
    from repro.optim.adamw import AdamWConfig
    from repro.train.steps import (TrainStepConfig, init_train_state,
                                   make_train_step)

    cfg = _tiny_cfg(dispatch="kernels")
    model = Model(cfg, dt=DtypePolicy(),
                  opts=ExecOptions(mode="run", block_q=8, block_kv=8,
                                   xent_chunks=2))
    ts = TrainStepConfig(opt=AdamWConfig(lr=1e-3))
    step = make_train_step(model, ts)
    params, opt = init_train_state(model, ts, jax.random.key(0))
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.ones((2, 8), jnp.int32)}
    with dispatch.stats_scope() as stats_fn, dispatch.forbid_dense_scores():
        _, _, metrics = jax.jit(step)(params, opt, batch)
        stats = stats_fn()
    assert np.isfinite(float(metrics["loss"]))
    assert stats.get(("attention", "kernel"), 0) > 0
    assert stats.get(("attention_bwd", "kernel"), 0) > 0
    assert stats.get(("attention_bwd", "reference"), 0) == 0


def test_forbid_dense_scores_trips_on_dense_lowerings():
    b, s, h, hd = 1, 8, 2, 8
    ks = jax.random.split(jax.random.key(5), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, hd), jnp.float32)
               for kk in ks)
    with dispatch.forbid_dense_scores():
        # blockwise reference and the fused kernel route both trace clean
        dispatch.attention(q, k, v, policy="reference")
        jax.grad(lambda q_: jnp.sum(
            dispatch.attention(q_, k, v, policy="kernels")))(q)
        with pytest.raises(AssertionError, match="dense"):
            dispatch.attention(q, k, v, impl="naive", policy="reference")
    # outside the scope the naive lowering is allowed again
    dispatch.attention(q, k, v, impl="naive", policy="reference")


def test_tuned_reference_plan_respected_under_auto(tmp_path, monkeypatch):
    """A tuned flash_attention_bwd entry that says "the dense VJP wins at
    this shape" (level 1) is honored on the backward route — unless the
    policy is an explicit "kernels", which forces the fused kernels."""
    from repro.tune import cache as tune_cache

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    cache = tune_cache.PlanCache(tmp_path / "plans.json")
    b, s, h, hd = 1, 16, 2, 8
    cache.put("flash_attention_bwd", (b, h, s, hd), jnp.float32,
              {"level": 1}, us=1.0)
    cache.save()
    tune_cache.preload()
    try:
        ks = jax.random.split(jax.random.key(6), 3)
        q, k, v = (jax.random.normal(kk, (b, s, h, hd), jnp.float32)
                   for kk in ks)

        def loss(q_):
            return jnp.sum(dispatch.attention(q_, k, v, policy="kernels"))

        with dispatch.stats_scope() as stats_fn:
            jax.grad(loss)(q)
            assert stats_fn().get(("attention_bwd", "kernel"), 0) == 1
        # force the auto decision path: module default "kernels" would
        # force fused, so emulate a TPU-style auto route via policy_scope
        monkeypatch.setattr(dispatch, "_kernels_by_default", lambda: True)
        with dispatch.stats_scope() as stats_fn:
            jax.grad(lambda q_: jnp.sum(
                dispatch.attention(q_, k, v, policy="auto")))(q)
            assert stats_fn().get(("attention_bwd", "reference"), 0) == 1
    finally:
        monkeypatch.delenv("REPRO_TUNE_CACHE")
        tune_cache.preload()
