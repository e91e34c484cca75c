"""Paged-KV serving runtime tests.

Three layers of evidence, mirroring the dispatch discipline:

1. kernel differential — the Pallas ragged decode kernel against the
   gather-and-mask reference, for ragged lengths x every attention arch's
   own geometry (GQA groups, windows) x {fp32, bf16};
2. paged-vs-dense equivalence — chunked prefill + batched ragged decode
   must produce the same logits as the dense full-sequence forward (same
   tokens in -> same logits out), including across slot-recycle
   boundaries in the scheduler;
3. runtime properties — admission control, page recycling, tuned-plan
   consumption, and the paged-arch support gate.

All probes run inside ``dispatch.stats_scope()`` / ``tune.lookup_scope()``
so counters never leak across test modules.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCHS
from repro.core.memory import DtypePolicy
from repro.kernels import dispatch
from repro.models.transformer import (ExecOptions, Model, paged_supported)
from repro.tune import cache as tune_cache

DTYPES = {
    "float32": DtypePolicy(compute=jnp.float32),
    "bfloat16": DtypePolicy(),
}
TOLS = {
    "float32": dict(rtol=2e-4, atol=2e-4),
    "bfloat16": dict(rtol=5e-2, atol=5e-2),
}

# ragged length vectors covering: inactive slot, single token, page
# boundary +/- 1, exactly-full cache
RAGGED_LENGTHS = [(0, 24, 9), (1, 8, 7), (17, 24, 16)]


def _assert_close(got, want, dtype_name):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **TOLS[dtype_name])


def _paged_inputs(n_heads, n_kv_heads, hd, dtype, *, slots=3, page=8,
                  n_pages=3):
    pool = 1 + slots * n_pages
    ks = jax.random.split(jax.random.key(0), 3)
    q = (0.5 * jax.random.normal(ks[0], (slots, n_heads, hd),
                                 jnp.float32)).astype(dtype)
    kp = (0.5 * jax.random.normal(ks[1], (pool, n_kv_heads, page, hd),
                                  jnp.float32)).astype(dtype)
    vp = (0.5 * jax.random.normal(ks[2], (pool, n_kv_heads, page, hd),
                                  jnp.float32)).astype(dtype)
    rng = np.random.default_rng(0)
    table = jnp.asarray(
        1 + rng.permutation(pool - 1)[:slots * n_pages].reshape(
            slots, n_pages), jnp.int32)
    return q, kp, vp, table


# ---------------------------------------------------- kernel differential
@pytest.fixture
def empty_plan_cache(tmp_path, monkeypatch):
    """Point the tuned-plan cache at an empty file: the repo cache may
    hold a (CPU-tuned) level-1 decode plan, which would silently resolve
    the kernel route's ``plan="tuned"`` to the reference lowering — the
    differential must drive the actual Pallas kernel."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "empty.json"))
    tune_cache.preload()
    yield
    monkeypatch.delenv("REPRO_TUNE_CACHE")
    tune_cache.preload()


@pytest.mark.parametrize("dtype_name", sorted(DTYPES))
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_attention_differential(arch, dtype_name, empty_plan_cache):
    """Kernel route == reference route for the arch's own attention
    geometry over ragged lengths (masked tail pages, GQA, windows)."""
    cfg = ARCHS[arch].smoke()
    mixers = {m for m, _ in cfg.layer_kinds()}
    if not ({"attn", "swa"} & mixers):
        pytest.skip("attention-free arch")
    window = cfg.window if "swa" in mixers else 0
    dt = DTYPES[dtype_name]
    q, kp, vp, table = _paged_inputs(cfg.n_heads, cfg.n_kv_heads,
                                     cfg.head_dim, dt.compute)
    with dispatch.stats_scope() as stats:
        for lens in RAGGED_LENGTHS:
            lengths = jnp.asarray(lens, jnp.int32)
            got = dispatch.decode_attention(
                q, kp, vp, table, lengths, window=window,
                policy="kernels")
            want = dispatch.decode_attention(
                q, kp, vp, table, lengths, window=window,
                policy="reference")
            assert got.dtype == want.dtype
            _assert_close(got, want, dtype_name)
        s = stats()
    assert s[("decode_attention", "kernel")] == len(RAGGED_LENGTHS)
    assert s[("decode_attention", "reference")] == len(RAGGED_LENGTHS)


def test_decode_attention_inactive_slot_zero_and_finite():
    """lengths == 0 slots (pointing at the trash page) must come out
    exactly zero on both routes — no NaNs from empty softmaxes."""
    q, kp, vp, table = _paged_inputs(4, 2, 16, jnp.float32)
    lengths = jnp.asarray([0, 0, 5], jnp.int32)
    for policy in ("kernels", "reference"):
        out = dispatch.decode_attention(q, kp, vp, table, lengths,
                                        policy=policy)
        assert bool(jnp.all(jnp.isfinite(out)))
        assert float(jnp.max(jnp.abs(out[:2]))) == 0.0


def test_decode_attention_pages_per_tile_invariant():
    """KV-tile geometry is a pure performance knob: every pages_per_tile
    (incl. non-divisors of n_pages -> padded tail tiles) agrees."""
    from repro.kernels.attention import decode_attention as decode_op
    q, kp, vp, table = _paged_inputs(4, 2, 16, jnp.float32, n_pages=4)
    lengths = jnp.asarray([3, 30, 12], jnp.int32)
    base = decode_op(q, kp, vp, table, lengths, pages_per_tile=1)
    for ppt in (2, 3, 4, 16):
        got = decode_op(q, kp, vp, table, lengths, pages_per_tile=ppt)
        _assert_close(got, base, "float32")


# Accuracy bound for the int8 KV path: symmetric per-(page, kv-head)
# quantization of ~N(0, 0.5) K/V keeps the attention output within this
# max-abs-error of the fp32 oracle (measured ~1e-2 on these geometries;
# 5e-2 leaves noise headroom while still failing a wrong-scale bug by
# orders of magnitude).  The kernel's in-tile dequant vs the dequantizing
# reference is a SAME-MATH differential and runs at the fp32 tolerance.
INT8_KV_MAX_ABS_ERR = 5e-2


def _quantized_pools(kp, vp):
    from repro.core import quant
    kq, ks = quant.quantize_pages(kp)
    vq, vs = quant.quantize_pages(vp)
    return kq, ks, vq, vs


def _check_decode_int8(cfg, window):
    q, kp, vp, table = _paged_inputs(cfg.n_heads, cfg.n_kv_heads,
                                     cfg.head_dim, jnp.float32)
    kq, ks, vq, vs = _quantized_pools(kp, vp)
    with dispatch.stats_scope() as stats:
        for lens in RAGGED_LENGTHS:
            lengths = jnp.asarray(lens, jnp.int32)
            got = dispatch.decode_attention(
                q, kq, vq, table, lengths, ks, vs, window=window,
                policy="kernels")
            oracle = dispatch.decode_attention(
                q, kq, vq, table, lengths, ks, vs, window=window,
                policy="reference")
            _assert_close(got, oracle, "float32")
            full = dispatch.decode_attention(
                q, kp, vp, table, lengths, window=window,
                policy="reference")
            err = float(jnp.max(jnp.abs(got - full)))
            assert err < INT8_KV_MAX_ABS_ERR, (
                f"int8 decode error {err} exceeds bound "
                f"{INT8_KV_MAX_ABS_ERR} (lengths={lens})")
        s = stats()
    assert s[("decode_attention", "kernel")] == len(RAGGED_LENGTHS)


def test_decode_attention_int8_differential(empty_plan_cache):
    """int8 pools + per-page scales: the kernel's in-tile dequant agrees
    with the dequantizing reference at fp32 tolerance, and both stay
    within the documented quantization-noise bound of the fp32 oracle."""
    _check_decode_int8(ARCHS["gemma-2b"].smoke(), 0)


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_attention_int8_all_archs(arch, empty_plan_cache):
    """The int8 decode differential swept over every attention arch's own
    geometry (GQA groups, windows)."""
    cfg = ARCHS[arch].smoke()
    mixers = {m for m, _ in cfg.layer_kinds()}
    if not ({"attn", "swa"} & mixers):
        pytest.skip("attention-free arch")
    _check_decode_int8(cfg, cfg.window if "swa" in mixers else 0)


def _stacked_pools(kp, vp, kv_dtype, n_layers=3):
    """A layer-stacked (L, P, Hkv, page, hd) pair from one layer's pools:
    every layer holds distinct, nonzero data (layer l is the pool scaled
    by l + 1 and shifted by l), so reading the wrong layer shows.  int8
    stacks come back quantized with their (L, P, Hkv) scales."""
    k = jnp.stack([kp.astype(jnp.float32) * (l + 1) + l
                   for l in range(n_layers)])
    v = jnp.stack([vp.astype(jnp.float32) * (l + 1) - l
                   for l in range(n_layers)])
    if kv_dtype == "int8":
        return _quantized_pools(k, v)
    return k.astype(kp.dtype), None, v.astype(vp.dtype), None


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_attention_stacked_pool_reads_its_layer(kv_dtype,
                                                       empty_plan_cache):
    """The model's layer-stacked pool plus a layer index reads exactly
    what the same op reads from that layer's pool alone, on the kernel
    and the reference route — int8 pools with that layer's scales."""
    q, kp, vp, table = _paged_inputs(4, 2, 16, jnp.bfloat16)
    kst, kst_s, vst, vst_s = _stacked_pools(kp, vp, kv_dtype)
    lengths = jnp.asarray([3, 24, 9], jnp.int32)
    for layer in (0, 2):
        scales = () if kst_s is None else (kst_s[layer], vst_s[layer])
        for policy in ("kernels", "reference"):
            got = dispatch.decode_attention(
                q, kst, vst, table, lengths, *scales,
                layer=jnp.int32(layer), policy=policy)
            want = dispatch.decode_attention(
                q, kst[layer], vst[layer], table, lengths, *scales,
                policy=policy)
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
        other = dispatch.decode_attention(
            q, kst[1], vst[1], table, lengths,
            *(() if kst_s is None else (kst_s[1], vst_s[1])),
            policy="kernels")
        assert not np.allclose(np.asarray(got, np.float32),
                               np.asarray(other, np.float32))


# the work-list grid's edges: 8-token pages, 2 pages to a 16-token KV
# tile, 6 pages (48 tokens) a slot
PAGE_W, PPT_W, NP_W = 8, 2, 6
EDGE_LENGTHS = (0, 1, PAGE_W - 1, PAGE_W, PPT_W * PAGE_W,
                PPT_W * PAGE_W + 1, PAGE_W * NP_W)


@pytest.mark.parametrize("case", ["ragged_edges", "one_live_row", "window",
                                  "int8", "stacked_layer"])
def test_decode_work_grid_matches_reference(case, empty_plan_cache):
    """The kernel's grid walks only the live (row, tile) pairs: at every
    length edge (empty, one token, page and tile boundaries +/- 1, full),
    with all rows idle but one, under a window, on int8 pools and on a
    stacked pool read at a nonzero layer, it agrees with the reference
    route, and rows of length 0 come out exactly zero."""
    from repro.kernels.attention import decode_attention as decode_op
    slots = len(EDGE_LENGTHS)
    q, kp, vp, table = _paged_inputs(4, 2, 16, jnp.float32, slots=slots,
                                     page=PAGE_W, n_pages=NP_W)
    lengths = EDGE_LENGTHS
    if case == "one_live_row":
        lengths = (0,) * (slots - 2) + (PPT_W * PAGE_W + 5, 0)
    window = PAGE_W + 3 if case == "window" else 0
    scales, layer = (), None
    if case == "int8":
        kp, ks, vp, vs = _quantized_pools(kp, vp)
        scales = (ks, vs)
    if case == "stacked_layer":
        kp, _, vp, _ = _stacked_pools(kp, vp, "f32")
        layer = jnp.int32(2)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = decode_op(q, kp, vp, table, lengths, *scales, layer=layer,
                    window=window, pages_per_tile=PPT_W, plan="heuristic")
    want = dispatch.decode_attention(q, kp, vp, table, lengths, *scales,
                                     layer=layer, window=window,
                                     policy="reference")
    _assert_close(got, want, "float32")
    idle = np.asarray(lengths) == 0
    assert np.all(np.asarray(got)[idle] == 0.0)
    assert bool(jnp.all(jnp.isfinite(got)))
    # a work list built once for a step's layers gives the same output;
    # one at another tile geometry (the default: 6 pages a tile) is
    # ignored, and the call builds its own
    from repro.kernels.attention.decode import decode_work
    for work in (decode_work(lengths, table, page=PAGE_W,
                             pages_per_tile=PPT_W, window=window),
                 dispatch.decode_work(lengths, table, page=PAGE_W,
                                      window=window)):
        again = decode_op(q, kp, vp, table, lengths, *scales, layer=layer,
                          window=window, pages_per_tile=PPT_W,
                          plan="heuristic", work=work)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_decode_work_list_orders_live_pairs_and_host_twin_agrees():
    """The work list holds each live (row, tile) pair once, by row then
    tile, flags a row's first and last tile, starts a windowed row at the
    tile of its oldest visible key and repeats the last live item up to
    its static capacity; the host's count of its items agrees."""
    from repro.kernels.attention.decode import FIRST, LAST, decode_work_list
    fl = FIRST | LAST
    lengths = [0, 17, 0, 16, 1, 48]
    n_tiles, tile = NP_W // PPT_W, PPT_W * PAGE_W
    expect = {
        0: [(1, 0, FIRST), (1, 1, LAST), (3, 0, fl), (4, 0, fl),
            (5, 0, FIRST), (5, 1, 0), (5, 2, LAST)],
        # keys 6..16 of row 1 span tiles 0-1; row 5's 37..47 tile 2 alone
        11: [(1, 0, FIRST), (1, 1, LAST), (3, 0, fl), (4, 0, fl),
             (5, 2, fl)],
    }
    for window, items in expect.items():
        row, tl, flag, n_work = decode_work_list(
            jnp.asarray(lengths), n_tiles, tile, window)
        n = len(items)
        assert int(n_work) == n
        got = list(zip(np.asarray(row).tolist(), np.asarray(tl).tolist(),
                       np.asarray(flag).tolist()))
        assert len(got) == len(lengths) * n_tiles
        assert got[:n] == items
        assert set(got[n:]) == {items[-1]}
    assert int(decode_work_list(jnp.zeros((4,), jnp.int32), 3, 16)[3]) == 0
    # the host count agrees at the serving geometry: 8 pages of 64 a tile
    serve = [0, 1, 512, 513, 8192, 700]
    for window, n in ((0, 1 + 1 + 2 + 16 + 2), (600, 1 + 1 + 2 + 2 + 2)):
        assert int(decode_work_list(jnp.asarray(serve), 16, 512,
                                    window)[3]) == n
        assert dispatch.decode_work_count(serve, 128, 64, window) == n


def test_decode_step_builds_the_work_list_once(monkeypatch):
    """The layers of one paged decode step read the same lengths and
    table, so the step builds the decode kernel's work list once for each
    window its layers use, outside the layers, and not once a layer."""
    from repro.kernels.attention import decode as decode_mod
    from repro.kernels.attention import ops as attention_ops
    built = []

    def counted(fn):
        def wrap(*args, **kwargs):
            built.append(kwargs.get("window", 0))
            return fn(*args, **kwargs)
        return wrap

    monkeypatch.setattr(decode_mod, "decode_work",
                        counted(decode_mod.decode_work))
    monkeypatch.setattr(attention_ops, "_decode_work",
                        counted(attention_ops._decode_work))
    page, slots, max_len = 4, 2, 32
    cfg = _tiny_cfg("gemma3-4b", dispatch="kernels")   # local + global
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    cache = model.init_paged_cache(slots, max_len, page)
    table = jnp.arange(1, 1 + slots * (max_len // page),
                       dtype=jnp.int32).reshape(slots, -1)
    jax.eval_shape(lambda c: model.decode_step(
        params, c, {"tokens": jnp.zeros((slots, 1), jnp.int32)},
        jnp.int32(0), paged=(jnp.asarray([5, 0], jnp.int32), table)),
        cache)
    kinds = (*model.layout.prefix, *model.layout.period, *model.layout.tail)
    assert sum(m in ("attn", "swa") for m, _ in kinds) > 2
    assert sorted(built) == [0, cfg.window]


def test_decode_tuned_plan_consumed(tmp_path, monkeypatch):
    """A seeded exact-shape decode plan is picked up by the kernel route
    (lookup counters prove the cache was consulted)."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    q, kp, vp, table = _paged_inputs(4, 2, 16, jnp.float32)
    shape = (q.shape[0], q.shape[1], table.shape[1], kp.shape[2],
             q.shape[2])
    cache = tune_cache.PlanCache(tmp_path / "plans.json")
    cache.put("decode_attention", shape, jnp.float32,
              {"level": 3, "page_size": kp.shape[2], "pages_per_tile": 2,
               "prefetch_depth": 2}, us=1.0)
    cache.save()
    tune_cache.preload()
    try:
        lengths = jnp.asarray([4, 20, 11], jnp.int32)
        with tune_cache.lookup_scope() as looks, \
                dispatch.stats_scope() as stats:
            got = dispatch.decode_attention(q, kp, vp, table, lengths,
                                            policy="kernels")
            assert looks()["exact"] == 1
            assert stats()[("decode_attention", "kernel")] == 1
        want = dispatch.decode_attention(q, kp, vp, table, lengths,
                                         policy="reference")
        _assert_close(got, want, "float32")
    finally:
        monkeypatch.delenv("REPRO_TUNE_CACHE")
        tune_cache.preload()             # restore the repo default cache


# ------------------------------------------------- paged-vs-dense logits
def _tiny_cfg(name, **overrides):
    cfg = ARCHS[name].smoke()
    return dataclasses.replace(
        cfg, d_model=32, n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
        vocab_size=128, n_experts=min(cfg.n_experts, 4) or 0,
        **overrides)


@pytest.mark.parametrize("arch,policy,layout", [
    ("gemma-2b", "reference", "prefix"),
    ("gemma-2b", "kernels", "prefix"),
    ("gemma-2b", "reference", "scan"),    # scanned layer periods
    ("gemma3-4b", "reference", "prefix"),  # sliding-window mask
    ("gemma3-4b", "kernels", "prefix"),
])
def test_paged_prefill_decode_matches_dense_forward(arch, policy, layout):
    """Same tokens in -> same logits out: chunked prefill (incl. a padded
    partial page) + teacher-forced ragged decode against the paged cache
    reproduce the dense full-sequence forward, on both dispatch routes
    and through both stacking strategies (unrolled prefix layers and
    lax.scan'd layer periods)."""
    page, slots, max_len = 4, 2, 32
    cfg = _tiny_cfg(arch, dispatch=policy)
    if layout == "scan":
        cfg = dataclasses.replace(
            cfg, n_layers=5, prefix=(("attn", "mlp"),),
            pattern=(("attn", "mlp"), ("attn", "mlp")))
    assert paged_supported(cfg)
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    cache = model.init_paged_cache(slots, max_len, page)
    n_slot_pages = max_len // page

    rng = np.random.default_rng(1)
    L = 6                                  # not page-aligned: padded tail
    prompt = rng.integers(0, cfg.vocab_size, L)
    table = np.zeros((slots, n_slot_pages), np.int32)
    table[0] = np.arange(1, 1 + n_slot_pages)
    lengths = np.zeros((slots,), np.int32)

    toks = np.zeros((((L + page - 1) // page) * page,), np.int32)
    toks[:L] = prompt
    logits = None
    for t0 in range(0, L, page):
        last = min(L, t0 + page) - 1 - t0
        logits, cache = model.prefill_step_paged(
            params, cache, jnp.asarray(toks[t0:t0 + page])[None],
            jnp.int32(t0), jnp.asarray(table[0]), jnp.int32(last))
    lengths[0] = L

    # paged-incremental and full-forward are different (equivalent)
    # reduction orders; multi-layer fp32 drift on logits of magnitude ~10
    # sits near 2e-4, so this equivalence check runs at 1e-3 — a wrong
    # mask/page/position produces O(1) errors, far above it
    eq_tol = dict(rtol=1e-3, atol=1e-3)

    seq = list(prompt)
    full = model.forward(params, {"tokens": jnp.asarray(seq)[None]})
    np.testing.assert_allclose(np.asarray(logits[0], np.float32),
                               np.asarray(full[0, -1], np.float32), **eq_tol)

    for _ in range(4):                     # teacher-forced ragged decode
        nxt = int(np.argmax(np.asarray(logits[0])))
        seq.append(nxt)
        # a copy: the host buffer may still be read after the call
        # returns, and ``lengths`` is bumped right below
        dl, cache = model.decode_step(
            params, cache, {"tokens": jnp.asarray([[nxt], [0]], jnp.int32)},
            jnp.int32(0),
            paged=(jnp.asarray(lengths.copy()), jnp.asarray(table)))
        lengths[0] += 1
        full = model.forward(params, {"tokens": jnp.asarray(seq)[None]})
        np.testing.assert_allclose(np.asarray(dl[0], np.float32),
                                   np.asarray(full[0, -1], np.float32),
                                   **eq_tol)
        logits = dl[:1]


# --------------------------------------------------- scheduler properties
def _make_scheduler(slots=2, max_len=32, page=4, total_pages=0,
                    arch="gemma-2b", dispatch="reference", kv_dtype="",
                    log=print):
    from repro.launch.serve import PagedScheduler
    cfg = _tiny_cfg(arch, dispatch=dispatch, kv_dtype=kv_dtype)
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    return PagedScheduler(model, params, slots=slots, max_len=max_len,
                          page_size=page, total_pages=total_pages,
                          log=log), cfg


def test_paged_scheduler_recycle_equivalence():
    """Slot recycling is invisible to results: requests served through a
    2-slot scheduler (forcing recycles + batched ragged decode) emit the
    same tokens as each request alone in a fresh scheduler."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 128, rng.integers(3, 9)) for _ in range(4)]

    sched, _ = _make_scheduler(slots=2)
    done = sched.run([Request(i, p, 5) for i, p in enumerate(prompts)])
    assert len(done) == 4
    batched = {r.rid: list(r.out) for r in done}

    for i, p in enumerate(prompts):
        solo_sched, _ = _make_scheduler(slots=2)
        solo = solo_sched.run([Request(0, p, 5)])
        assert batched[i] == list(solo[0].out), f"request {i} diverged"


def test_paged_scheduler_admission_and_page_accounting():
    """Reserve-on-admit: with a pool of 5 usable pages and 3-page
    requests, only one runs at a time; every page returns to the free
    list when its request retires."""
    from repro.launch.serve import Request
    sched, _ = _make_scheduler(slots=2, max_len=16, page=4, total_pages=6)
    free0 = sched.alloc.available()
    assert free0 == 5
    rng = np.random.default_rng(3)
    reqs = [Request(i, rng.integers(0, 128, 6), 4) for i in range(3)]
    assert sched.pages_needed(reqs[0]) == 3          # ceil((6+4)/4)
    done = sched.run(reqs)
    assert len(done) == 3 and all(len(r.out) == 4 for r in done)
    assert sched.alloc.available() == free0          # no page leaked
    assert all(not pages for pages in sched.slot_pages)


def test_paged_scheduler_instant_finish_readmits():
    """max_new == 1 requests finish straight out of prefill; the freed
    slot must be re-offered to the queue in the same admission pass (more
    one-token requests than slots used to trip the deadlock guard)."""
    from repro.launch.serve import Request
    sched, _ = _make_scheduler(slots=2)
    rng = np.random.default_rng(6)
    reqs = [Request(i, rng.integers(0, 128, 4), 1) for i in range(5)]
    done = sched.run(reqs)
    assert sorted(r.rid for r in done) == list(range(5))
    assert all(len(r.out) == 1 for r in done)


def test_paged_scheduler_rejects_oversized_request():
    """A request that can never be admitted (prompt >= max_len leaves no
    room to generate) must be rejected (done=False, no output), not
    head-of-line block the queue."""
    from repro.launch.serve import Request
    sched, _ = _make_scheduler(slots=2, max_len=16, page=4)
    rng = np.random.default_rng(5)
    big = Request(0, rng.integers(0, 128, 17), 8)   # prompt >= max_len
    ok = Request(1, rng.integers(0, 128, 5), 3)
    done = sched.run([big, ok])
    assert [r.rid for r in done] == [1]
    assert len(done[0].out) == 3
    assert big.done is False and big.out == []


def test_paged_gate_rejects_recurrent_archs():
    from repro.launch.serve import PagedScheduler
    cfg = ARCHS["rwkv6-7b"].smoke()
    assert not paged_supported(cfg)
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32))
    with pytest.raises(ValueError, match="paged serving requires"):
        PagedScheduler(model, None, slots=1, max_len=16, page_size=4)


def _all_swa_cfg(window, **overrides):
    """A fully sliding-window stack (every attention layer windowed) —
    the only layout where window page reclamation is sound."""
    cfg = _tiny_cfg("gemma3-4b", window=window, **overrides)
    return dataclasses.replace(
        cfg, n_layers=2, prefix=(("swa", "mlp"), ("swa", "mlp")),
        pattern=())


def test_window_reclamation_frees_pages_behind_window():
    """swa slots stop holding max_len pages: once decode advances past
    the window, wholly-dead pages return to the free list mid-request,
    and the accounting invariant (held + free + trash == total) holds."""
    from repro.launch.serve import PagedScheduler, Request
    page, window, max_len = 4, 8, 32
    cfg = _all_swa_cfg(window, dispatch="reference")
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    sched = PagedScheduler(model, params, slots=1, max_len=max_len,
                           page_size=page)
    assert sched.window == window
    free0 = sched.alloc.available()
    rng = np.random.default_rng(8)
    done = sched.run([Request(0, rng.integers(0, 128, 6), 18)])
    assert len(done) == 1 and len(done[0].out) == 18
    # final length 6 + 18 = 24 -> (24 - 8) // 4 = 4 pages were dead by
    # the end; all pages back after retirement, none double-freed
    assert sched.pages_reclaimed >= 3
    assert sched.alloc.available() == free0
    sched.check_page_accounting()


def test_window_reclamation_lets_queued_requests_admit_early():
    """Reclaimed pages are immediately admissible capital: with a pool
    too small for two whole-lifetime reservations, the second request
    admits while the first is still decoding (it could not without
    reclamation, since the first holds its full budget until retirement)."""
    from repro.launch.serve import PagedScheduler, Request
    page, window, max_len = 4, 4, 32
    cfg = _all_swa_cfg(window, dispatch="reference")
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    # each request: 6 prompt + 14 new = 20 tokens -> 5 pages; pool of 8
    # usable pages cannot hold two reservations at once
    sched = PagedScheduler(model, params, slots=2, max_len=max_len,
                           page_size=page, total_pages=9)
    rng = np.random.default_rng(9)
    reqs = [Request(i, rng.integers(0, 128, 6), 14) for i in range(2)]
    done = sched.run(reqs)
    assert sorted(r.rid for r in done) == [0, 1]
    assert all(len(r.out) == 14 for r in done)
    assert sched.pages_reclaimed > 0
    assert sched.alloc.available() == 8
    sched.check_page_accounting()


def test_window_reclamation_does_not_change_outputs():
    """Reclamation only frees provably-dead pages: generated tokens match
    a run with reclamation disabled (window forced off on the scheduler),
    and the paged outputs still match the dense full-sequence forward."""
    from repro.launch.serve import PagedScheduler, Request
    page, window = 4, 8
    cfg = _all_swa_cfg(window, dispatch="reference")
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(10)
    prompt = rng.integers(0, 128, 7)

    def run(reclaim):
        sched = PagedScheduler(model, params, slots=1, max_len=32,
                               page_size=page)
        if not reclaim:
            sched.window = 0          # disable reclamation only
        done = sched.run([Request(0, prompt, 12)])
        return list(done[0].out), sched.pages_reclaimed

    with_reclaim, n_freed = run(True)
    without_reclaim, n_kept = run(False)
    assert n_freed > 0 and n_kept == 0
    assert with_reclaim == without_reclaim
    # and against the dense forward: teacher-force the same sequence
    seq = list(prompt) + with_reclaim[:-1]
    full = model.forward(params, {"tokens": jnp.asarray(seq)[None]})
    assert int(jnp.argmax(full[0, -1])) == with_reclaim[-1]


def test_no_reclamation_for_global_or_mixed_attention():
    """A single global-attention layer reads the whole history: schedulers
    over global or mixed (gemma3 5:1 swa:attn) stacks must never reclaim."""
    sched, _ = _make_scheduler(slots=1, arch="gemma-2b")
    assert sched.window == 0
    mixed, _ = _make_scheduler(slots=1, arch="gemma3-4b")
    assert mixed.window == 0          # swa AND global layers -> unsound


# ------------------------------------------------ continuous-batching engine
def _make_engine(slots=2, max_len=32, page=4, total_pages=0,
                 dispatch="reference", kv_dtype="", token_budget=0,
                 log=None):
    from repro.launch.engine import ContinuousEngine
    sched, cfg = _make_scheduler(slots=slots, max_len=max_len, page=page,
                                 total_pages=total_pages,
                                 dispatch=dispatch, kv_dtype=kv_dtype,
                                 log=log)
    return ContinuousEngine(sched, token_budget=token_budget,
                            clock="tick", log=log), cfg


def test_continuous_engine_seeded_determinism():
    """Same loadgen seed -> identical arrival times, admission order, and
    token streams across two fresh engines (tick clock: the run is a pure
    function of the seed)."""
    from repro.launch.loadgen import poisson_stream

    def run_once():
        engine, _ = _make_engine()
        reqs = poisson_stream(5, rate=2.0, vocab_size=128, prompt_len=5,
                              max_new=4, seed=7, prompt_jitter=3)
        done = engine.run(reqs)
        return (list(engine.admission_order),
                {r.rid: list(r.out) for r in done},
                engine.metrics.summary())

    order_a, out_a, sum_a = run_once()
    order_b, out_b, sum_b = run_once()
    assert len(out_a) == 5 and all(len(o) == 4 for o in out_a.values())
    assert order_a == order_b
    assert out_a == out_b
    assert sum_a == sum_b
    assert sum_a["requests_finished"] == 5
    assert sum_a["ttft_p50"] is not None and sum_a["ttft_p50"] >= 0
    assert sum_a["tok_latency_p99"] is not None


def test_continuous_burst_matches_static_schedule_outputs():
    """The engine's interleaved chunked prefill + masked ride-along decode
    is invisible to results: a burst workload emits exactly the tokens the
    static run-to-completion schedule emits."""
    from repro.launch.loadgen import poisson_stream

    def stream():
        return poisson_stream(4, rate=0.0, vocab_size=128, prompt_len=6,
                              max_new=4, seed=13)

    engine, _ = _make_engine(slots=2)
    done_c = engine.run(stream())
    sched, _ = _make_scheduler(slots=2)
    done_s = sched.run(stream())
    assert {r.rid: list(r.out) for r in done_c} \
        == {r.rid: list(r.out) for r in done_s}
    assert engine.executor.max_prefill_batch >= 2   # and it DID batch


def test_continuous_interleaved_kernels_match_reference():
    """Kernel route == reference route token-for-token under interleaved
    multi-slot prefill + decode, with route counters proving a B > 1
    batched prefill_attention kernel forward fired."""
    from repro.launch.loadgen import poisson_stream

    def run(policy):
        engine, _ = _make_engine(slots=2, dispatch=policy)
        with dispatch.stats_scope() as stats:
            engine.warmup()      # trace-time counters tick at compile
            done = engine.run(poisson_stream(
                4, rate=0.0, vocab_size=128, prompt_len=6, max_new=4,
                seed=11))
            s = stats()
        return ({r.rid: list(r.out) for r in done},
                engine.executor.max_prefill_batch, s)

    got, width_k, s_kern = run("kernels")
    want, width_r, _ = run("reference")
    assert got == want
    assert len(got) == 4
    assert width_k >= 2 and width_r >= 2
    assert s_kern.get(("prefill_attention", "kernel"), 0) > 0
    assert s_kern.get(("decode_attention", "kernel"), 0) > 0


def test_continuous_page_accounting_under_oversubscription():
    """Oversubscribed pool + mid-stream arrivals: the page-accounting
    invariant (held + free + trash == total) holds after EVERY engine
    iteration, requests queue instead of deadlocking, and every page
    returns to the free list at drain."""
    from repro.launch.loadgen import trace_stream
    # 3 pages per request (ceil((6+4)/4)); 5 usable pages -> one resident
    # reservation at a time, later arrivals must wait for recycling
    engine, _ = _make_engine(slots=2, max_len=16, total_pages=6)
    sched = engine.sched
    trace = [{"t": 0.0, "prompt_len": 6, "max_new": 4},
             {"t": 0.5, "prompt_len": 6, "max_new": 4},
             {"t": 3.0, "prompt_len": 6, "max_new": 4}]
    engine.submit(trace_stream(trace, vocab_size=128, seed=3))
    steps = 0
    while engine.step():
        sched.check_page_accounting()
        steps += 1
        assert steps < 200, "engine failed to drain"
    assert len(engine.done) == 3
    assert all(len(r.out) == 4 for r in engine.done)
    assert sched.rejected == 0
    assert sched.alloc.available() == 5
    sched.check_page_accounting()


def test_continuous_engine_rejects_and_counts():
    """An inadmissible request is counted + logged through the injected
    callback and surfaced in the metrics summary; admissible traffic
    behind it still completes."""
    from repro.launch.loadgen import trace_stream
    logs = []
    engine, _ = _make_engine(slots=2, max_len=16, log=logs.append)
    trace = [{"t": 0.0, "prompt_len": 17, "max_new": 8},  # >= max_len
             {"t": 0.0, "prompt_len": 5, "max_new": 3}]
    done = engine.run(trace_stream(trace, vocab_size=128, seed=5))
    sched = engine.sched
    assert [r.rid for r in done] == [1] and len(done[0].out) == 3
    assert sched.rejected == 1
    assert sched.rejected_requests[0].rid == 0
    assert engine.metrics.summary()["requests_rejected"] == 1
    assert any("rejecting" in m for m in logs)


def test_static_rejection_is_counted_and_logged(capsys):
    """The static schedule's rejection path routes through the injected
    log callback (no bare print) and ticks the counted ``rejected`` stat."""
    from repro.launch.serve import Request
    logs = []
    sched, _ = _make_scheduler(slots=2, max_len=16, page=4,
                               log=logs.append)
    rng = np.random.default_rng(5)
    big = Request(0, rng.integers(0, 128, 17), 8)    # prompt >= max_len
    ok = Request(1, rng.integers(0, 128, 5), 3)
    done = sched.run([big, ok])
    assert [r.rid for r in done] == [1]
    assert sched.rejected == 1
    assert sched.rejected_requests == [big]
    assert len(logs) == 1 and "rejecting request 0" in logs[0]
    assert "rejecting" not in capsys.readouterr().out


def test_paged_serve_executes_through_dispatch():
    """The acceptance probe: a paged serve (prefill + decode) with
    dispatch="kernels" takes the decode-attention kernel route, counted
    inside an isolated stats scope."""
    from repro.launch.serve import PagedScheduler, Request
    cfg = _tiny_cfg("gemma-2b", dispatch="kernels")
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    outside = dispatch.stats()
    with dispatch.stats_scope() as stats:
        sched = PagedScheduler(model, params, slots=2, max_len=16,
                               page_size=4)
        rng = np.random.default_rng(4)
        done = sched.run([Request(i, rng.integers(0, 128, 5), 3)
                          for i in range(3)])
        assert len(done) == 3
        s = stats()
    assert s.get(("decode_attention", "kernel"), 0) > 0
    assert s.get(("matmul", "kernel"), 0) > 0
    assert dispatch.stats() == outside       # scope did not leak


# ------------------------------------------------------- int8 KV serving
def test_paged_scheduler_int8_greedy_matches_fp32():
    """Quantization noise must not flip greedy decisions on the smoke
    arch: an int8-pool scheduler emits token-for-token the fp32 streams
    (same prompts, same seeds) — the end-to-end accuracy gate."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 128, rng.integers(3, 9)) for _ in range(4)]

    def run(kv_dtype):
        sched, _ = _make_scheduler(slots=2, kv_dtype=kv_dtype)
        done = sched.run([Request(i, p, 5) for i, p in enumerate(prompts)])
        assert len(done) == 4
        return {r.rid: list(r.out) for r in done}

    assert run("int8") == run("")


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
def test_decode_step_writes_only_its_rows_in_every_layer(kv_dtype):
    """A scheduler decode step writes each slot's new K/V row into every
    layer's pool (unrolled prefix layer and the scanned stack alike) and
    leaves every other byte of every pool as it was: the stacked pool is
    updated in place at the layer index, not rebuilt.  Pools start full
    of distinct nonzero data.  int8 pools requantize the written page
    (running-max append), so there the page and its scale may change."""
    from repro.launch.serve import PagedScheduler, Request
    cfg = dataclasses.replace(
        _tiny_cfg("gemma-2b", dispatch="kernels", kv_dtype=kv_dtype),
        n_layers=5, prefix=(("attn", "mlp"),),
        pattern=(("attn", "mlp"), ("attn", "mlp")))
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    sched = PagedScheduler(model, params, slots=2, max_len=16, page_size=4)
    leaves, tree = jax.tree.flatten(sched.cache)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    sched.cache = jax.tree.unflatten(tree, [
        jax.random.randint(k, a.shape, 1, 100).astype(a.dtype)
        if a.dtype == jnp.int8 else
        (1.0 + jax.random.uniform(k, a.shape)).astype(a.dtype)
        for k, a in zip(keys, leaves)])
    r = Request(0, np.arange(1, 7), 4)
    assert sched.try_admit(r, 0)
    before = jax.tree.map(lambda a: np.array(a, copy=True), sched.cache)
    sched.prepare_decode([0])
    sched.step(np.asarray([r.out[-1], 0], np.int32))
    after = jax.tree.map(np.asarray, sched.cache)

    # slot 0 writes row lengths[0] of its page; idle slot 1 the trash page
    length = int(sched.lengths[0])
    pid, off = int(sched.table[0, length // 4]), length % 4
    written = [(pid, off), (0, 0)]
    checked = 0
    for (path, old), new in zip(jax.tree_util.tree_leaves_with_path(before),
                                jax.tree.leaves(after)):
        name, where = path[-1].key, jax.tree_util.keystr(path)
        pool = name.endswith("pages")
        lead = old.ndim - (4 if pool else 2)     # 1 for the scanned stack
        mask = np.ones(old.shape, bool)
        for p_, o_ in written:
            at = (slice(None),) * lead + (p_,)
            if pool and not kv_dtype:
                at += (slice(None), o_)
            mask[at] = False
        np.testing.assert_array_equal(new[mask], old[mask], err_msg=where)
        if name == "k_pages":
            per_layer = (-1,) + old.shape[-4:]
            for ln, lo in zip(new.reshape(per_layer),
                              old.reshape(per_layer)):
                assert not np.array_equal(ln[pid, :, off],
                                          lo[pid, :, off]), where
            checked += 1
    assert checked == 3     # the prefix layer's pool and the stack's two


def test_int8_scale_lockstep_and_byte_residency():
    """int8 pools carry per-page scale leaves whose lifecycle is slaved
    to the page allocator: check_page_accounting's lockstep invariant
    holds through a full serve, byte residency drains to zero with the
    pages (no scale leak on recycle), and reallocated pages come back
    with their scale rows reset."""
    from repro.launch.serve import Request
    sched8, _ = _make_scheduler(slots=2, kv_dtype="int8")
    sched32, _ = _make_scheduler(slots=2)
    assert sched8._page_bytes < sched32._page_bytes
    assert sched8.kv_bytes_resident() == 0

    rng = np.random.default_rng(9)
    done = sched8.run([Request(i, rng.integers(0, 128, 6), 4)
                       for i in range(3)])
    assert len(done) == 3
    sched8.check_page_accounting()          # incl. scale-lockstep check
    assert sched8.kv_bytes_resident() == 0  # all pages back, none leaked

    # retired sequences leave stale scale rows behind; the allocator's
    # on_alloc hook must wipe them before the page is reused
    stale = [leaf for leaf in jax.tree.leaves(sched8.cache)
             if leaf.ndim in (2, 3)]
    assert stale and any(float(jnp.abs(s).max()) > 0 for s in stale)
    got = sched8.alloc.alloc(sched8.alloc.available())
    for leaf in (l for l in jax.tree.leaves(sched8.cache)
                 if l.ndim in (2, 3)):
        rows = leaf[:, jnp.asarray(got)] if leaf.ndim == 3 \
            else leaf[jnp.asarray(got)]
        assert float(jnp.abs(rows).max()) == 0.0
    sched8.alloc.release(got)
    sched8.check_page_accounting()


def test_continuous_engine_tracks_kv_byte_residency():
    """The engine's max_resident_kv_bytes is the dtype-aware residency
    peak: positive under load, and strictly smaller for an int8 pool
    than for the fp32 pool on the same workload (the capacity win the
    quantized cache exists to deliver); the token streams still agree."""
    from repro.launch.loadgen import poisson_stream

    def run(kv_dtype):
        engine, _ = _make_engine(slots=2, kv_dtype=kv_dtype)
        done = engine.run(poisson_stream(
            4, rate=0.0, vocab_size=128, prompt_len=6, max_new=4, seed=13))
        assert len(done) == 4
        return engine.max_resident_kv_bytes, \
            {r.rid: list(r.out) for r in done}

    bytes8, out8 = run("int8")
    bytes32, out32 = run("")
    assert 0 < bytes8 < bytes32
    assert out8 == out32
