"""Ragged multi-token prefill attention tests.

Mirrors the decode-kernel discipline (tests/test_paged_decode.py):

1. kernel differential — the Pallas ragged prefill kernel against the
   gather-and-mask reference, for every attention arch's own geometry
   (GQA groups, sliding windows) x {fp32, bf16} x chunk offsets covering
   the first chunk (empty history), mid-prompt, and the last chunk;
2. invariances — KV-tile geometry (pages_per_tile, incl. non-divisors of
   n_pages) is a pure performance knob; the last chunk's padded tail is
   hidden by causality;
3. route level — chunked prefill through the serve scheduler fires the
   ``(prefill_attention, kernel)`` counter under ``--dispatch kernels``
   and its logits match the dense reference forward (the acceptance
   probe for the op registered end-to-end through the registry).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCHS
from repro.core.memory import DtypePolicy
from repro.kernels import dispatch
from repro.models.transformer import ExecOptions, Model, paged_supported
from repro.tune import cache as tune_cache

DTYPES = {
    "float32": DtypePolicy(compute=jnp.float32),
    "bfloat16": DtypePolicy(),
}
TOLS = {
    "float32": dict(rtol=2e-4, atol=2e-4),
    "bfloat16": dict(rtol=5e-2, atol=5e-2),
}


def _assert_close(got, want, dtype_name, msg=""):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               err_msg=msg, **TOLS[dtype_name])


def _prefill_inputs(n_heads, n_kv_heads, hd, dtype, *, slots=3, chunk=8,
                    page=8, n_pages=4):
    pool = 1 + slots * n_pages
    ks = jax.random.split(jax.random.key(0), 3)
    q = (0.5 * jax.random.normal(ks[0], (slots, chunk, n_heads, hd),
                                 jnp.float32)).astype(dtype)
    kp = (0.5 * jax.random.normal(ks[1], (pool, n_kv_heads, page, hd),
                                  jnp.float32)).astype(dtype)
    vp = (0.5 * jax.random.normal(ks[2], (pool, n_kv_heads, page, hd),
                                  jnp.float32)).astype(dtype)
    rng = np.random.default_rng(0)
    table = jnp.asarray(
        1 + rng.permutation(pool - 1)[:slots * n_pages].reshape(
            slots, n_pages), jnp.int32)
    # first chunk (no history), mid-prompt, last chunk of the table
    starts = jnp.asarray([0, page, (n_pages - 1) * page], jnp.int32)
    return q, kp, vp, table, starts


@pytest.fixture
def empty_plan_cache(tmp_path, monkeypatch):
    """The repo cache may hold a (CPU-tuned) level-1 prefill plan, which
    would resolve the kernel route to the reference lowering under "auto"
    — the differential must drive the actual Pallas kernel."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "empty.json"))
    tune_cache.preload()
    yield
    monkeypatch.delenv("REPRO_TUNE_CACHE")
    tune_cache.preload()


# ---------------------------------------------------- kernel differential
@pytest.mark.parametrize("dtype_name", sorted(DTYPES))
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_prefill_attention_differential(arch, dtype_name, empty_plan_cache):
    """Kernel route == reference route for the arch's own attention
    geometry over chunk offsets (causal intra-chunk masking, GQA,
    windows)."""
    cfg = ARCHS[arch].smoke()
    mixers = {m for m, _ in cfg.layer_kinds()}
    if not ({"attn", "swa"} & mixers):
        pytest.skip("attention-free arch")
    window = cfg.window if "swa" in mixers else 0
    dt = DTYPES[dtype_name]
    q, kp, vp, table, starts = _prefill_inputs(
        cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dt.compute)
    with dispatch.stats_scope() as stats:
        got = dispatch.prefill_attention(q, kp, vp, table, starts,
                                         window=window, policy="kernels")
        want = dispatch.prefill_attention(q, kp, vp, table, starts,
                                          window=window, policy="reference")
        s = stats()
    assert got.dtype == want.dtype
    _assert_close(got, want, dtype_name)
    assert s[("prefill_attention", "kernel")] == 1
    assert s[("prefill_attention", "reference")] == 1


# Accuracy bound for the int8 KV path (see test_paged_decode.py for the
# decode twin): prefill attends over up to a whole table of quantized
# history, so its noise bound is the same documented 5e-2 — measured
# ~2e-2 on these geometries, still orders of magnitude below any
# wrong-scale bug.
INT8_KV_MAX_ABS_ERR = 5e-2


def test_prefill_attention_int8_differential(empty_plan_cache):
    """int8 pools + per-page scales through the ragged prefill kernel:
    in-tile dequant agrees with the dequantizing reference at fp32
    tolerance; both stay within the quantization-noise bound of the
    fp32 oracle."""
    from repro.core import quant
    cfg = ARCHS["gemma-2b"].smoke()
    q, kp, vp, table, starts = _prefill_inputs(
        cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, jnp.float32)
    kq, ks = quant.quantize_pages(kp)
    vq, vs = quant.quantize_pages(vp)
    with dispatch.stats_scope() as stats:
        got = dispatch.prefill_attention(q, kq, vq, table, starts, ks, vs,
                                         policy="kernels")
        oracle = dispatch.prefill_attention(q, kq, vq, table, starts,
                                            ks, vs, policy="reference")
        s = stats()
    _assert_close(got, oracle, "float32")
    full = dispatch.prefill_attention(q, kp, vp, table, starts,
                                      policy="reference")
    err = float(jnp.max(jnp.abs(got - full)))
    assert err < INT8_KV_MAX_ABS_ERR, (
        f"int8 prefill error {err} exceeds bound {INT8_KV_MAX_ABS_ERR}")
    assert s[("prefill_attention", "kernel")] == 1


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_prefill_attention_stacked_pool_reads_its_layer(kv_dtype,
                                                        empty_plan_cache):
    """The model's layer-stacked (L, P, Hkv, page, hd) pool plus a layer
    index reads exactly what the same op reads from that layer's pool
    alone, on the kernel and the reference route; every layer holds
    distinct, nonzero data (int8 stacks pass that layer's scales)."""
    from repro.core import quant
    q, kp, vp, table, starts = _prefill_inputs(4, 2, 16, jnp.bfloat16)
    kst = jnp.stack([kp.astype(jnp.float32) * (l + 1) + l
                     for l in range(3)])
    vst = jnp.stack([vp.astype(jnp.float32) * (l + 1) - l
                     for l in range(3)])
    if kv_dtype == "int8":
        (kst, kst_s), (vst, vst_s) = (quant.quantize_pages(kst),
                                      quant.quantize_pages(vst))
    else:
        kst, vst = kst.astype(jnp.bfloat16), vst.astype(jnp.bfloat16)

    def scales(layer):
        return () if kv_dtype != "int8" else (kst_s[layer], vst_s[layer])

    for layer in (0, 2):
        for policy in ("kernels", "reference"):
            got = dispatch.prefill_attention(
                q, kst, vst, table, starts, *scales(layer),
                layer=jnp.int32(layer), policy=policy)
            want = dispatch.prefill_attention(
                q, kst[layer], vst[layer], table, starts, *scales(layer),
                policy=policy)
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
        other = dispatch.prefill_attention(
            q, kst[1], vst[1], table, starts, *scales(1), policy="kernels")
        assert not np.allclose(np.asarray(got, np.float32),
                               np.asarray(other, np.float32))


def test_prefill_pages_per_tile_invariant():
    """KV-tile geometry is a pure performance knob: every pages_per_tile
    (incl. non-divisors of n_pages -> padded tail tiles) agrees."""
    from repro.kernels.attention import prefill_attention as prefill_op
    q, kp, vp, table, starts = _prefill_inputs(4, 2, 16, jnp.float32)
    base = prefill_op(q, kp, vp, table, starts, pages_per_tile=1)
    for ppt in (2, 3, 4, 16):
        got = prefill_op(q, kp, vp, table, starts, pages_per_tile=ppt)
        _assert_close(got, base, "float32", f"ppt={ppt}")


def test_prefill_first_chunk_matches_pure_causal_attention():
    """A chunk at start=0 with its own K/V written into the pages is
    plain causal self-attention — check against the flash oracle."""
    from repro.kernels.attention import ref
    chunk, h, hd, page = 8, 4, 16, 8
    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = (0.5 * jax.random.normal(kk, (1, chunk, h, hd), jnp.float32)
               for kk in ks)
    pool = jnp.zeros((3, h, page, hd), jnp.float32)
    kp = pool.at[1].set(k[0].transpose(1, 0, 2))
    vp = pool.at[1].set(v[0].transpose(1, 0, 2))
    table = jnp.asarray([[1, 0]], jnp.int32)
    out = dispatch.prefill_attention(q, kp, vp, table,
                                     jnp.asarray([0], jnp.int32),
                                     policy="kernels")
    want = ref.attention_ref(q.transpose(0, 2, 1, 3),
                             k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=True)
    _assert_close(out, want.transpose(0, 2, 1, 3), "float32")


def test_prefill_padded_tail_hidden_by_causality():
    """Garbage K/V beyond the last real token (the padded final chunk)
    must not leak into real positions' outputs — causality hides it."""
    q, kp, vp, table, _ = _prefill_inputs(4, 2, 16, jnp.float32, slots=1,
                                          n_pages=2)
    starts = jnp.asarray([8], jnp.int32)
    base = dispatch.prefill_attention(q, kp, vp, table, starts,
                                      policy="kernels")
    # trash everything at positions > the chunk's last real token: the
    # pages beyond the chunk's own page (there are none here) and nothing
    # else — instead, poison a *later* logical page mapped by the table
    kp2 = kp.at[table[0, 1], :, 4:].set(1e3)   # chunk positions 12..
    vp2 = vp.at[table[0, 1], :, 4:].set(1e3)
    got = dispatch.prefill_attention(q, kp2, vp2, table, starts,
                                     policy="kernels")
    # rows 0..3 (positions 8..11) never see positions 12..15
    _assert_close(got[:, :4], base[:, :4], "float32")


def test_prefill_tuned_plan_consumed(tmp_path, monkeypatch):
    """A seeded exact-shape prefill plan is picked up by the kernel route
    (lookup counters + plan-source tags prove the cache was consulted)."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    q, kp, vp, table, starts = _prefill_inputs(4, 2, 16, jnp.float32)
    shape = (q.shape[0], q.shape[1], q.shape[2], table.shape[1],
             kp.shape[2], q.shape[3])
    cache = tune_cache.PlanCache(tmp_path / "plans.json")
    cache.put("prefill_attention", shape, jnp.float32,
              {"level": 3, "page_size": kp.shape[2], "pages_per_tile": 2,
               "prefetch_depth": 2}, us=1.0)
    cache.save()
    tune_cache.preload()
    try:
        with tune_cache.lookup_scope() as looks, \
                dispatch.stats_scope() as stats:
            got = dispatch.prefill_attention(q, kp, vp, table, starts,
                                             policy="kernels")
            assert looks()["exact"] == 1
            assert stats()[("prefill_attention", "kernel")] == 1
            assert dispatch.plan_source_stats().get(
                ("prefill_attention", "kernel", "exact"), 0) == 1
        want = dispatch.prefill_attention(q, kp, vp, table, starts,
                                          policy="reference")
        _assert_close(got, want, "float32")
    finally:
        monkeypatch.delenv("REPRO_TUNE_CACHE")
        tune_cache.preload()


# ------------------------------------------------------------ route level
def _tiny_cfg(name, **overrides):
    cfg = ARCHS[name].smoke()
    return dataclasses.replace(
        cfg, d_model=32, n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
        vocab_size=128, n_experts=min(cfg.n_experts, 4) or 0,
        **overrides)


@pytest.mark.parametrize("arch", ["gemma-2b", "gemma3-4b"])
def test_paged_serve_prefill_takes_kernel_route(arch):
    """The acceptance probe: chunked prefill through the PagedScheduler
    with dispatch="kernels" fires (prefill_attention, kernel) — across a
    global-causal arch and a sliding-window arch — and the generated
    tokens match a pure-reference scheduler run."""
    from repro.launch.serve import PagedScheduler, Request
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 128, 9), rng.integers(0, 128, 5)]

    outs = {}
    for policy in ("kernels", "reference"):
        cfg = _tiny_cfg(arch, dispatch=policy)
        assert paged_supported(cfg)
        model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                      opts=ExecOptions(mode="run"))
        params = model.init(jax.random.key(0))
        with dispatch.stats_scope() as stats:
            sched = PagedScheduler(model, params, slots=2, max_len=32,
                                   page_size=4)
            done = sched.run([Request(i, p, 4)
                              for i, p in enumerate(prompts)])
            s = stats()
        assert len(done) == 2
        outs[policy] = {r.rid: list(r.out) for r in done}
        route = "kernel" if policy == "kernels" else "reference"
        assert s.get(("prefill_attention", route), 0) > 0, s
        assert s.get(("prefill_attention",
                      "kernel" if route == "reference" else "reference"),
                     0) == 0, s
    assert outs["kernels"] == outs["reference"]
