"""jit'd wrappers + op registrations for the attention family.

This module is the complete registry story for attention (see
``repro.kernels.registry``): the staged wrappers (``flash_attention``,
``flash_attention_bwd``, ``decode_attention``, ``prefill_attention``), the
dispatch-level reference lowerings the models route against (naive +
blockwise self-attention, paged ragged decode, paged ragged prefill), and
the ``OpSpec`` declarations wiring eligibility, tuned-plan key schemas,
the custom-VJP pair, and tune-space hookups — everything one registration
per op.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from ...core.plan import Level
from ...tune.cache import resolve_plan, resolve_plan_source
from .. import registry
from ..common import interpret_default
from . import ref
from .backward import flash_attention_bwd_pallas
from .decode import (decode_attention_pallas, heuristic_pages_per_tile,
                     decode_work as _decode_work,
                     decode_work_count as _decode_work_count)
from .flash import flash_attention_pallas
from .prefill import prefill_attention_pallas


def _fit_blocks(s: int, block_q: int, block_kv: int):
    bq = min(block_q, s)
    bkv = min(block_kv, s)
    while s % bq:
        bq //= 2
    while s % bkv:
        bkv //= 2
    return bq, bkv


@functools.partial(jax.jit, static_argnames=("causal", "window", "level",
                                             "block_q", "block_kv",
                                             "return_residuals",
                                             "interpret"))
def _flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool, window: int, level: Level,
                     block_q: int, block_kv: int, return_residuals: bool,
                     interpret: bool):
    if level in (Level.T0_NAIVE, Level.T1_PIPELINED):
        out = ref.attention_ref(q, k, v, causal=causal, window=window)
        if return_residuals:
            return out, ref.attention_lse_ref(q, k, causal=causal,
                                              window=window)
        return out
    bq, bkv = _fit_blocks(q.shape[2], block_q, block_kv)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=bq, block_kv=bkv,
                                  return_residuals=return_residuals,
                                  interpret=interpret)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    level: Level = Level.T3_REPLICATED,
                    block_q: int = 512, block_kv: int = 512,
                    plan: Union[str, dict, None] = "heuristic",
                    return_residuals: bool = False,
                    interpret: Optional[bool] = None):
    """(B, H, S, hd) attention.  T0/T1 materialize (S, S); T2+ run the
    online-softmax Pallas kernel.

    ``plan`` selects the tile geometry: ``"heuristic"`` (the ``block_q``/
    ``block_kv`` arguments), ``"tuned"`` (autotuner cache, heuristic on a
    miss), or a tuned kwargs dict (``block_q``/``block_kv``, optional
    ``level``).  ``return_residuals`` additionally returns the per-row
    logsumexp (B, H, S) f32 — the forward state ``flash_attention_bwd``
    consumes.
    """
    if interpret is None:
        interpret = interpret_default()
    level, kw = resolve_plan("attention", q.shape, q.dtype, level, plan)
    if kw:
        block_q = kw.get("block_q", block_q)
        block_kv = kw.get("block_kv", block_kv)
    return _flash_attention(q, k, v, causal=causal, window=window,
                            level=level, block_q=block_q, block_kv=block_kv,
                            return_residuals=return_residuals,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "window", "level",
                                             "block_q", "block_kv",
                                             "interpret"))
def _flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool, window: int,
                         level: Level, block_q: int, block_kv: int,
                         interpret: bool):
    if level in (Level.T0_NAIVE, Level.T1_PIPELINED):
        # "stash" schedule: the dense-score reference VJP (materializes
        # (S, S) — exactly what it re-derives instead of recomputing
        # tiles); fine when the whole score matrix fits on chip
        _, vjp = jax.vjp(
            lambda q_, k_, v_: ref.attention_ref(q_, k_, v_, causal=causal,
                                                 window=window), q, k, v)
        return vjp(do)
    bq, bkv = _fit_blocks(q.shape[2], block_q, block_kv)
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, o, lse, do, causal=causal, window=window, block_q=bq,
        block_kv=bkv, interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        o: jax.Array, lse: jax.Array, do: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        level: Level = Level.T3_REPLICATED,
                        block_q: int = 256, block_kv: int = 256,
                        plan: Union[str, dict, None] = "heuristic",
                        interpret: Optional[bool] = None):
    """Gradients (dq, dk, dv) of ``flash_attention`` from the saved
    residuals: ``o``/``do`` (B, H, S, hd) f32 and ``lse`` (B, H, S) f32.

    T0/T1 run the dense reference VJP (the "stash" schedule — the (S, S)
    matrix is re-derived wholesale); T2+ run the fused recompute Pallas
    kernels (``backward.py``), which never materialize (S, S).  ``plan``
    selects the backward tile geometry under kernel key
    ``flash_attention_bwd`` — the tuner's per-shape level pick IS the
    recompute-vs-stash threshold.  Gradients come back in the primal
    dtypes (custom-VJP contract).
    """
    if interpret is None:
        interpret = interpret_default()
    level, kw = resolve_plan("flash_attention_bwd", q.shape, q.dtype, level,
                             plan)
    if kw:
        block_q = kw.get("block_q", block_q)
        block_kv = kw.get("block_kv", block_kv)
    return _flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                                window=window, level=level, block_q=block_q,
                                block_kv=block_kv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "level",
                                             "pages_per_tile", "interpret"))
def _decode_attention(q, k_pages, v_pages, table, lengths, k_scale,
                      v_scale, layer, work, *, window: int, level: Level,
                      pages_per_tile: int, interpret: bool) -> jax.Array:
    if level in (Level.T0_NAIVE, Level.T1_PIPELINED):
        return ref.decode_attention_ref(q, k_pages, v_pages, table, lengths,
                                        k_scale, v_scale, window=window,
                                        layer=layer)
    return decode_attention_pallas(q, k_pages, v_pages, table, lengths,
                                   k_scale, v_scale, layer=layer,
                                   window=window,
                                   pages_per_tile=pages_per_tile,
                                   work=work, interpret=interpret)


def decode_work(lengths: jax.Array, table: jax.Array, *, page: int,
                window: int = 0):
    """The decode kernel's work list (``decode.decode_work``) at the
    default KV-tile geometry: the live (row, KV tile) pairs of ``lengths``
    and the page ids each reads.  Every layer of a decode step reads the
    same lengths and table, so the step builds it once and passes it to
    each layer's ``decode_attention(work=)``; a call at another geometry
    (a tuned ``pages_per_tile``) builds its own."""
    return _decode_work(lengths, table, page=page, window=window,
                        pages_per_tile=heuristic_pages_per_tile(
                            table.shape[1], page))


def decode_work_count(lengths, n_pages: int, page: int,
                      window: int = 0) -> int:
    """The items of :func:`decode_work`, counted on the host from the
    same lengths: the kernel's grid steps per kv head at the default
    geometry."""
    return _decode_work_count(lengths, n_pages, page,
                              heuristic_pages_per_tile(n_pages, page),
                              window=window)


def decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     table: jax.Array, lengths: jax.Array,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None, *,
                     layer: Optional[jax.Array] = None,
                     window: int = 0,
                     level: Level = Level.T3_REPLICATED,
                     pages_per_tile: Optional[int] = None,
                     plan: Union[str, dict, None] = "heuristic",
                     work=None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Ragged decode attention over a paged KV cache.

    q (B, H, hd) — one query token per slot; k_pages / v_pages (P, Hkv,
    page, hd) shared page pools, or the model's layer-stacked (L, P, Hkv,
    page, hd) pools read at the scalar ``layer``; table (B, n_pages) int32
    logical->physical page ids; lengths (B,) int32 valid tokens per slot
    (0 = inactive slot, output 0).  int8 pools additionally take
    ``k_scale`` / ``v_scale`` (P, Hkv) f32 per-page per-kv-head scales of
    the layer read (in-kernel dequant, §4.4).
    Returns (B, H, hd) f32.  T0/T1 gather pages to a dense masked
    reference; T2+ run the scalar-prefetch Pallas kernel.

    ``plan`` selects the KV-tile geometry: ``"heuristic"`` (the
    ``pages_per_tile`` argument, default ~512-row tiles), ``"tuned"``
    (autotuner cache keyed on (B, H, n_pages, page, hd) and the POOL dtype
    — the dtype axis of the serving-cache design space; heuristic on a
    miss), or a tuned kwargs dict (``pages_per_tile``, optional ``level``;
    ``page_size`` / ``prefetch_depth`` entries are layout / feasibility
    knobs and are ignored at call time).

    ``work`` is :func:`decode_work` of the same ``lengths``, ``table`` and
    ``window``, shared by the layers of one step; without it the kernel
    route builds its own.
    """
    if interpret is None:
        interpret = interpret_default()
    b, h, hd = q.shape
    page = k_pages.shape[-2]
    n_pages = table.shape[1]
    shape = (b, h, n_pages, page, hd)
    level, kw = resolve_plan("decode_attention", shape, k_pages.dtype,
                             level, plan)
    if kw:
        pages_per_tile = kw.get("pages_per_tile", pages_per_tile)
    if pages_per_tile is None:
        pages_per_tile = heuristic_pages_per_tile(n_pages, page)
    return _decode_attention(q, k_pages, v_pages, table, lengths,
                             k_scale, v_scale, layer, work, window=window,
                             level=level,
                             pages_per_tile=int(pages_per_tile),
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "level",
                                             "pages_per_tile", "interpret"))
def _prefill_attention(q, k_pages, v_pages, table, starts, k_scale,
                       v_scale, layer, *, window: int, level: Level,
                       pages_per_tile: int, interpret: bool) -> jax.Array:
    if level in (Level.T0_NAIVE, Level.T1_PIPELINED):
        return ref.prefill_attention_ref(q, k_pages, v_pages, table, starts,
                                         k_scale, v_scale, window=window,
                                         layer=layer)
    return prefill_attention_pallas(q, k_pages, v_pages, table, starts,
                                    k_scale, v_scale, layer=layer,
                                    window=window,
                                    pages_per_tile=pages_per_tile,
                                    interpret=interpret)


def prefill_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      table: jax.Array, starts: jax.Array,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None, *,
                      layer: Optional[jax.Array] = None,
                      window: int = 0,
                      level: Level = Level.T3_REPLICATED,
                      pages_per_tile: Optional[int] = None,
                      plan: Union[str, dict, None] = "heuristic",
                      interpret: Optional[bool] = None) -> jax.Array:
    """Ragged multi-token prefill attention over a paged KV cache.

    q (B, C, H, hd) — one chunk of C prompt tokens per slot, already
    written into the pools; k_pages / v_pages (P, Hkv, page, hd) shared
    page pools, or layer-stacked (L, P, Hkv, page, hd) pools read at the
    scalar ``layer``; table (B, n_pages) int32 page ids; starts (B,) int32
    page-aligned chunk offsets (slot b's queries sit at positions
    ``starts[b] + [0, C)``).  int8 pools additionally take ``k_scale`` /
    ``v_scale`` (P, Hkv) f32 per-page per-kv-head scales (in-kernel
    dequant, §4.4).  Returns (B, C, H, hd) f32.  T0/T1 gather pages to a
    dense causally-masked reference; T2+ run the scalar-prefetch Pallas
    kernel with causal intra-chunk masking.

    ``plan`` selects the KV-tile geometry under kernel key
    ``prefill_attention`` (shape key (B, C, H, n_pages, page, hd) plus the
    pool dtype); same semantics as ``decode_attention``.
    """
    if interpret is None:
        interpret = interpret_default()
    b, c, h, hd = q.shape
    page = k_pages.shape[-2]
    n_pages = table.shape[1]
    shape = (b, c, h, n_pages, page, hd)
    level, kw = resolve_plan("prefill_attention", shape, k_pages.dtype,
                             level, plan)
    if kw:
        pages_per_tile = kw.get("pages_per_tile", pages_per_tile)
    if pages_per_tile is None:
        pages_per_tile = heuristic_pages_per_tile(n_pages, page)
    return _prefill_attention(q, k_pages, v_pages, table, starts,
                              k_scale, v_scale, layer, window=window,
                              level=level,
                              pages_per_tile=int(pages_per_tile),
                              interpret=interpret)


# --------------------------------------------------------------------------
# dispatch-level reference lowerings
# --------------------------------------------------------------------------
# THE reference paths the models route against (the einsum contractions
# that used to live inline in models/layers.py, then in dispatch.py).
# ``models/layers.py`` holds no attention contraction of its own.

def causal_mask(qpos: jax.Array, kpos: jax.Array, window: int,
                causal: bool = True) -> jax.Array:
    """Branch-free causal (+ sliding window) mask — condition flattening
    (paper §2.7).  qpos (Sq,), kpos (Skv,) -> bool (Sq, Skv)."""
    if causal:
        m = kpos[None, :] <= qpos[:, None]
    else:
        m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if window > 0:
        m &= kpos[None, :] > (qpos[:, None] - window)
    return m


def attention_reference(q, k, v, *, causal, window, softcap, mask,
                        accum_dtype, out_dtype):
    """Naive reference: materializes the (Sq, Skv) score tensor."""
    registry.assert_no_dense_scores("attention_reference",
                                    q.shape[1], k.shape[1])
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(accum_dtype) * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    if mask is None:
        mask = causal_mask(jnp.arange(q.shape[1]), jnp.arange(k.shape[1]),
                           window, causal)[None, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    return jnp.einsum("bhqs,bshk->bqhk", probs, v)


def attention_blockwise_reference(q, k, v, *, causal, window, softcap,
                                  accum_dtype, out_dtype, block_kv,
                                  q_splits, unroll):
    """Blockwise (flash-style) reference in pure XLA — tiled accumulation
    interleaving (§2.1.2) on the softmax reduction; never materializes
    (S, S).  Ported verbatim from the pre-dispatch model layer: q stays
    un-blocked (its sharding passes through), only K/V are tiled and
    scanned, and causality is exploited with ``q_splits`` *static*
    sequence quarters so GSPMD never sees a dynamic q loop.
    ``unroll=True`` (dry-run cost compiles) python-unrolls the KV scans so
    ``cost_analysis`` counts every tile with identical math/FLOPs."""
    b, sq, h, hd = q.shape
    block_kv = min(block_kv, sq)
    while block_kv > 1 and sq % block_kv:
        block_kv //= 2
    nkv = sq // block_kv
    scale = 1.0 / math.sqrt(hd)

    kb = jnp.moveaxis(k.reshape(b, nkv, block_kv, h, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nkv, block_kv, h, hd), 1, 0)

    while q_splits > 1 and sq % q_splits != 0:
        q_splits //= 2
    qlen = sq // q_splits

    def kv_step(carry, kj, q_slice, qpos):
        m, l, acc = carry
        kpos = kj * block_kv + jnp.arange(block_kv)
        sc = jnp.einsum("bqhk,bshk->bhqs", q_slice,
                        jax.lax.dynamic_index_in_dim(kb, kj, 0, False)) \
            .astype(accum_dtype) * scale
        if softcap > 0:
            sc = jnp.tanh(sc / softcap) * softcap
        msk = causal_mask(qpos, kpos, window, causal)[None, None]
        sc = jnp.where(msk, sc, -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(sc - m_new[..., None])
        l_new = l * alpha + pexp.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqs,bshk->bhqk", pexp.astype(out_dtype),
            jax.lax.dynamic_index_in_dim(vb, kj, 0, False)) \
            .astype(accum_dtype)
        return (m_new, l_new, acc_new)

    outs = []
    for qi in range(q_splits):
        q_lo, q_hi = qi * qlen, (qi + 1) * qlen - 1
        q_slice = jax.lax.slice_in_dim(q, q_lo, q_hi + 1, axis=1)
        qpos = jnp.arange(q_lo, q_hi + 1)
        # static KV range this quarter can see (causal upper bound,
        # window lower bound) — condition flattening at compile time
        kj_hi = min(nkv - 1, q_hi // block_kv) if causal else nkv - 1
        kj_lo = 0
        if window > 0:
            kj_lo = max(0, (q_lo - window + 1) // block_kv)
        m0 = jnp.full((b, h, qlen), -1e30, accum_dtype)
        l0 = jnp.zeros((b, h, qlen), accum_dtype)
        a0 = jnp.zeros((b, h, qlen, hd), accum_dtype)
        if unroll:
            carry = (m0, l0, a0)
            for kj in range(kj_lo, kj_hi + 1):
                carry = kv_step(carry, kj, q_slice, qpos)
            m, l, acc = carry
        else:
            def body(c, kj, _q=q_slice, _p=qpos):
                return kv_step(c, kj, _q, _p), None
            (m, l, acc), _ = jax.lax.scan(
                body, (m0, l0, a0), jnp.arange(kj_lo, kj_hi + 1))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(out.astype(out_dtype))       # (b, h, qlen, hd)

    out = jnp.concatenate(outs, axis=2) if len(outs) > 1 else outs[0]
    return jnp.moveaxis(out, 1, 2)               # (b, sq, h, hd)


def decode_attention_reference(q, k_pages, v_pages, table, lengths,
                               k_scale=None, v_scale=None, layer=None, *,
                               window, softcap, accum_dtype, out_dtype):
    """Paged ragged decode reference: gather pages to a dense view
    (dequantizing int8 pools through the per-page scales; a layer-stacked
    pool at ``layer``), mask by per-slot length (and window), softmax in
    ``accum_dtype``.  The einsum lowering the paged serve path uses when
    the kernel route is off."""
    b, h, hd = q.shape
    hkv = k_pages.shape[-3]
    grp = h // hkv
    k = ref._gather_pages(k_pages, table, k_scale, layer)
    v = ref._gather_pages(v_pages, table, v_scale, layer)
    if grp > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             k.shape[:3] + (grp, hd)).reshape(b, -1, h, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             v.shape[:3] + (grp, hd)).reshape(b, -1, h, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bhd,bshd->bhs", q, k).astype(accum_dtype) * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    kpos = jnp.arange(k.shape[1])[None, :]
    valid = kpos < lengths[:, None]
    if window > 0:
        valid &= kpos >= lengths[:, None] - window
    scores = jnp.where(valid[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    out = jnp.einsum("bhs,bshd->bhd", probs, v)
    # inactive slots (length 0): every key masked -> exact zeros, no NaNs
    return jnp.where((lengths > 0)[:, None, None], out,
                     jnp.zeros((), out.dtype))


def prefill_attention_reference(q, k_pages, v_pages, table, starts,
                                k_scale=None, v_scale=None, layer=None, *,
                                window, softcap, accum_dtype, out_dtype):
    """Paged ragged prefill reference: gather pages to a dense view
    (dequantizing int8 pools through the per-page scales; a layer-stacked
    pool at ``layer``), mask causally
    against each chunk's positions (and the sliding window), softmax in
    ``accum_dtype`` — numerically identical to the gather +
    naive-attention path chunked prefill took before this op existed."""
    b, c, h, hd = q.shape
    hkv, page = k_pages.shape[-3], k_pages.shape[-2]
    grp = h // hkv
    registry.assert_no_dense_scores("prefill_attention_reference",
                                    c, table.shape[1] * page)
    k = ref._gather_pages(k_pages, table, k_scale, layer)
    v = ref._gather_pages(v_pages, table, v_scale, layer)
    if grp > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             k.shape[:3] + (grp, hd)).reshape(b, -1, h, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             v.shape[:3] + (grp, hd)).reshape(b, -1, h, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(accum_dtype) * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    qpos = starts[:, None] + jnp.arange(c)[None, :]          # (B, C)
    kpos = jnp.arange(k.shape[1])                            # (S,)
    mask = kpos[None, None, :] <= qpos[:, :, None]           # (B, C, S)
    if window > 0:
        mask &= kpos[None, None, :] > qpos[:, :, None] - window
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    return jnp.einsum("bhqs,bshk->bqhk", probs, v)


# --------------------------------------------------------------------------
# op registrations (repro.kernels.registry)
# --------------------------------------------------------------------------

_BHS = (0, 2, 1, 3)      # (B, S, H, hd) <-> (B, H, S, hd)


def _attention_eligible(st, q, k, v, mask) -> bool:
    if mask is not None or st["softcap"] > 0:
        return False
    if q.shape != k.shape or k.shape != v.shape:
        return False          # decode / cross-length: no self-attn kernel
    if q.shape[1] < 2:
        return False
    return all(jnp.issubdtype(t.dtype, jnp.floating) for t in (q, k, v))


def _attention_plan_shape(st, q, k, v, mask):
    return (q.shape[0], q.shape[2], q.shape[1], q.shape[3])


def _attention_ref_lowering(ctx, q, k, v, mask):
    kw = ctx.kw
    common = dict(causal=kw["causal"], window=kw["window"],
                  softcap=kw["softcap"], accum_dtype=kw["accum_dtype"],
                  out_dtype=kw["out_dtype"])
    # the blockwise lowering tiles a single self-attention length; any
    # cross-length (decode) call falls back to the naive lowering
    if kw["impl"] == "naive" or mask is not None \
            or q.shape[1] != k.shape[1]:
        return attention_reference(q, k, v, mask=mask, **common)
    return attention_blockwise_reference(
        q, k, v, block_kv=kw["block_kv"], q_splits=kw["q_splits"],
        unroll=kw["unroll"], **common)


def _attention_kernel_lowering(ctx, q, k, v, mask):
    kw = ctx.kw
    qt, kt, vt = (t.transpose(*_BHS) for t in (q, k, v))
    out = flash_attention(qt, kt, vt, causal=kw["causal"],
                          window=kw["window"], plan=ctx.ops_plan())
    return out.transpose(*_BHS).astype(kw["out_dtype"])


def _attention_vjp_fwd(ctx, q, k, v, mask):
    kw = ctx.kw
    qt, kt, vt = (t.transpose(*_BHS) for t in (q, k, v))
    o, lse = flash_attention(qt, kt, vt, causal=kw["causal"],
                             window=kw["window"], plan=ctx.ops_plan(),
                             return_residuals=True)
    out = o.transpose(*_BHS).astype(kw["out_dtype"])
    return out, (qt, kt, vt, o, lse)


def _attention_vjp_bwd(ctx, res, g):
    """Forward/backward are a paired schedule: the forward emitted per-row
    logsumexp residuals, the backward recomputes P tiles from them in the
    fused Pallas kernels (``backward.py``) — neither direction
    materializes (S, S).  The tuned ``flash_attention_bwd`` plan may route
    a shape to the dense reference VJP instead (the stash schedule); an
    explicit ``mode="kernels"`` overrides that, forcing the fused
    backward, exactly as the forward policy promises the differential
    tests."""
    qt, kt, vt, o, lse = res
    kw = ctx.kw
    causal, window = kw["causal"], kw["window"]
    # the forward's astype(out_dtype) + transpose happen inside the VJP
    # boundary, so their cotangent rules are applied by hand here
    gt = g.transpose(*_BHS).astype(jnp.float32)
    level, bkw, source = resolve_plan_source(
        "flash_attention_bwd", qt.shape, qt.dtype, Level.T3_REPLICATED,
        "tuned")
    use_fused = not (level in (Level.T0_NAIVE, Level.T1_PIPELINED)
                     and ctx.mode != "kernels")
    registry.count_route("attention_bwd",
                         "kernel" if use_fused else "reference", source)
    if use_fused:
        bkw = {k_: v_ for k_, v_ in (bkw or {}).items()
               if k_ in ("block_q", "block_kv")}
        dq, dk, dv = flash_attention_bwd(qt, kt, vt, o, lse, gt,
                                         causal=causal, window=window,
                                         plan=None, **bkw)
    else:
        registry.assert_no_dense_scores("attention reference VJP",
                                        qt.shape[2], kt.shape[2])
        _, vjp = jax.vjp(
            lambda q_, k_, v_: ref.attention_ref(q_, k_, v_, causal=causal,
                                                 window=window),
            qt, kt, vt)
        dq, dk, dv = vjp(gt)
    return (dq.transpose(*_BHS), dk.transpose(*_BHS),
            dv.transpose(*_BHS), None)


def _attention_example(dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 8, 4, 16), dtype) for kk in ks)
    return (q, k, v), {}


def _attention_bad_example():
    # cross-length (decode-shaped) q vs k/v: structurally ineligible
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, 8, 4, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, 8, 4, 16), jnp.float32)
    return (q, k, v), {}


def _paged_pools_ok(q, k_pages, v_pages, k_scale, v_scale) -> bool:
    """Pool dtype contract shared by decode/prefill eligibility: floating
    pools with no scales, or int8 pools with floating (P, Hkv) scales."""
    if not jnp.issubdtype(q.dtype, jnp.floating):
        return False
    if k_scale is None:
        return all(jnp.issubdtype(t.dtype, jnp.floating)
                   for t in (k_pages, v_pages))
    if v_scale is None:
        return False
    expect = k_pages.shape[-4:-2]          # one layer's (P, Hkv)
    return (all(t.dtype == jnp.int8 for t in (k_pages, v_pages))
            and all(jnp.issubdtype(s.dtype, jnp.floating)
                    and s.shape == expect for s in (k_scale, v_scale)))


def _decode_eligible(st, q, k_pages, v_pages, table, lengths,
                     k_scale=None, v_scale=None, layer=None,
                     work=None) -> bool:
    if st["softcap"] > 0:
        return False
    if q.shape[1] % k_pages.shape[-3]:
        return False              # GQA group must divide evenly
    return _paged_pools_ok(q, k_pages, v_pages, k_scale, v_scale)


def _decode_plan_shape(st, q, k_pages, v_pages, table, lengths,
                       k_scale=None, v_scale=None, layer=None, work=None):
    return (q.shape[0], q.shape[1], table.shape[1], k_pages.shape[-2],
            q.shape[2])


def _paged_plan_dtype(st, q, k_pages, *rest):
    # tuned plans key on the POOL dtype (the KV-cache dtype axis): an int8
    # pool's larger feasible tiles must never transplant onto a bf16 pool
    return k_pages.dtype


def _decode_ref_lowering(ctx, q, k_pages, v_pages, table, lengths,
                         k_scale=None, v_scale=None, layer=None, work=None):
    kw = ctx.kw
    return decode_attention_reference(
        q, k_pages, v_pages, table, lengths, k_scale, v_scale, layer,
        window=kw["window"], softcap=kw["softcap"],
        accum_dtype=kw["accum_dtype"], out_dtype=kw["out_dtype"])


def _decode_kernel_lowering(ctx, q, k_pages, v_pages, table, lengths,
                            k_scale=None, v_scale=None, layer=None,
                            work=None):
    kw = ctx.kw
    out = decode_attention(q, k_pages, v_pages, table, lengths,
                           k_scale, v_scale, layer=layer,
                           window=kw["window"], plan=ctx.ops_plan(),
                           work=work)
    return out.astype(kw["out_dtype"])


def _paged_pool_inputs(dtype, *, slots=3, page=8, n_pages=3, h=4, hkv=2,
                       hd=16, seed=0):
    pool = 1 + slots * n_pages
    ks = jax.random.split(jax.random.key(seed), 3)
    kp = jax.random.normal(ks[1], (pool, hkv, page, hd), dtype)
    vp = jax.random.normal(ks[2], (pool, hkv, page, hd), dtype)
    table = (1 + jax.random.permutation(jax.random.key(seed + 1), pool - 1)
             [:slots * n_pages].reshape(slots, n_pages)).astype(jnp.int32)
    return ks[0], kp, vp, table


def _decode_example(dtype):
    kq, kp, vp, table = _paged_pool_inputs(dtype)
    q = jax.random.normal(kq, (3, 4, 16), dtype)
    lengths = jnp.asarray([0, 5, 20], jnp.int32)
    return (q, kp, vp, table, lengths), {}


def _decode_bad_example():
    # softcap: the reference lowering supports it, the kernel does not —
    # eligibility must route it to the reference, not crash
    kq, kp, vp, table = _paged_pool_inputs(jnp.float32)
    q = jax.random.normal(kq, (3, 4, 16), jnp.float32)
    lengths = jnp.asarray([1, 5, 20], jnp.int32)
    return (q, kp, vp, table, lengths), {"softcap": 5.0}


def _prefill_eligible(st, q, k_pages, v_pages, table, starts,
                      k_scale=None, v_scale=None, layer=None) -> bool:
    if st["softcap"] > 0:
        return False
    if q.shape[2] % k_pages.shape[-3]:
        return False              # GQA group must divide evenly
    return _paged_pools_ok(q, k_pages, v_pages, k_scale, v_scale)


def _prefill_plan_shape(st, q, k_pages, v_pages, table, starts,
                        k_scale=None, v_scale=None, layer=None):
    return (q.shape[0], q.shape[1], q.shape[2], table.shape[1],
            k_pages.shape[-2], q.shape[3])


def _prefill_ref_lowering(ctx, q, k_pages, v_pages, table, starts,
                          k_scale=None, v_scale=None, layer=None):
    kw = ctx.kw
    return prefill_attention_reference(
        q, k_pages, v_pages, table, starts, k_scale, v_scale, layer,
        window=kw["window"], softcap=kw["softcap"],
        accum_dtype=kw["accum_dtype"], out_dtype=kw["out_dtype"])


def _prefill_kernel_lowering(ctx, q, k_pages, v_pages, table, starts,
                             k_scale=None, v_scale=None, layer=None):
    kw = ctx.kw
    out = prefill_attention(q, k_pages, v_pages, table, starts,
                            k_scale, v_scale, layer=layer,
                            window=kw["window"], plan=ctx.ops_plan())
    return out.astype(kw["out_dtype"])


def _prefill_example(dtype):
    kq, kp, vp, table = _paged_pool_inputs(dtype, slots=2, page=8,
                                           n_pages=3)
    q = jax.random.normal(kq, (2, 8, 4, 16), dtype)
    starts = jnp.asarray([0, 8], jnp.int32)
    return (q, kp, vp, table, starts), {}


def _prefill_bad_example():
    # softcap routes to the reference lowering (kernel bakes in plain
    # scaled-dot-product only)
    kq, kp, vp, table = _paged_pool_inputs(jnp.float32, slots=2, page=8,
                                           n_pages=3)
    q = jax.random.normal(kq, (2, 8, 4, 16), jnp.float32)
    starts = jnp.asarray([0, 8], jnp.int32)
    return (q, kp, vp, table, starts), {"softcap": 5.0}


# ----------------------------------------------------- tune input builders
def _attention_tune_inputs(shape, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    return tuple(jax.random.normal(kk, shape, dtype) for kk in ks)


def _attention_tune_call(args, plan):
    return flash_attention(*args, plan=plan)


def _flash_bwd_tune_inputs(shape, dtype):
    """Backward cell: run the (reference-level) forward once to build the
    (o, lse) residuals, then time the backward candidates on a fixed
    cotangent — the sweep never times the forward."""
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(kk, shape, dtype) for kk in ks[:3])
    o, lse = flash_attention(q, k, v, level=Level.T1_PIPELINED, plan=None,
                             return_residuals=True)
    do = jax.random.normal(ks[3], shape, jnp.float32)
    return (q, k, v, o, lse, do)


def _flash_bwd_tune_call(args, plan):
    return flash_attention_bwd(*args, plan=plan)


def _tune_pool(key, pool, page, hkv, hd, dtype):
    """One tune-cell page pool at ``dtype``; int8 returns (pool, scales)
    through the same abs-max quantizer the serve path writes with."""
    vals = jax.random.normal(key, (pool, hkv, page, hd), jnp.float32)
    if jnp.dtype(dtype) == jnp.int8:
        from ...core.quant import quantize_pages
        return quantize_pages(vals)
    return vals.astype(dtype), None


def _decode_tune_inputs(shape, dtype):
    """Paged ragged-decode cell: a shared pool with page 0 reserved, a
    shuffled (deterministic) page table, and staggered per-slot lengths so
    the sweep times the masked-tail path the serve loop actually runs.
    ``dtype`` is the POOL dtype (the cache's dtype axis): int8 cells build
    quantized pools + scales with bf16 queries."""
    b, h, n_pages, page, hd = shape
    hkv = max(1, h // 2)                       # exercise GQA grouping
    pool = 1 + b * n_pages
    ks = jax.random.split(jax.random.key(0), 3)
    q_dtype = jnp.bfloat16 if jnp.dtype(dtype) == jnp.int8 else dtype
    q = jax.random.normal(ks[0], (b, h, hd), q_dtype)
    k_pages, k_scale = _tune_pool(ks[1], pool, page, hkv, hd, dtype)
    v_pages, v_scale = _tune_pool(ks[2], pool, page, hkv, hd, dtype)
    perm = jax.random.permutation(jax.random.key(3), pool - 1) + 1
    table = perm[:b * n_pages].reshape(b, n_pages).astype(jnp.int32)
    lengths = ((jnp.arange(b) + 1) * (n_pages * page) // b).astype(jnp.int32)
    if k_scale is None:
        return (q, k_pages, v_pages, table, lengths)
    return (q, k_pages, v_pages, table, lengths, k_scale, v_scale)


def _decode_tune_call(args, plan):
    return decode_attention(*args, plan=plan)


def _prefill_tune_inputs(shape, dtype):
    """Paged ragged-prefill cell: staggered page-aligned chunk offsets so
    the sweep times the tile-skip path (early chunks see few live tiles).
    ``dtype`` is the POOL dtype; int8 cells quantize pools + carry scales."""
    b, c, h, n_pages, page, hd = shape
    hkv = max(1, h // 2)                       # exercise GQA grouping
    pool = 1 + b * n_pages
    ks = jax.random.split(jax.random.key(0), 3)
    q_dtype = jnp.bfloat16 if jnp.dtype(dtype) == jnp.int8 else dtype
    q = jax.random.normal(ks[0], (b, c, h, hd), q_dtype)
    k_pages, k_scale = _tune_pool(ks[1], pool, page, hkv, hd, dtype)
    v_pages, v_scale = _tune_pool(ks[2], pool, page, hkv, hd, dtype)
    perm = jax.random.permutation(jax.random.key(3), pool - 1) + 1
    table = perm[:b * n_pages].reshape(b, n_pages).astype(jnp.int32)
    max_start = (n_pages * page - c) // page
    starts = ((jnp.arange(b) * max(max_start, 0)) // max(b - 1, 1)
              * page).astype(jnp.int32)
    if k_scale is None:
        return (q, k_pages, v_pages, table, starts)
    return (q, k_pages, v_pages, table, starts, k_scale, v_scale)


def _prefill_tune_call(args, plan):
    return prefill_attention(*args, plan=plan)


def _tune_specs():
    from ...tune import space
    return {
        "attention": registry.TuneSpec(
            space=space.attention_space,
            make_inputs=_attention_tune_inputs,
            call=_attention_tune_call,
            default_dtype=jnp.bfloat16,
            default_shapes=((1, 2, 128, 64), (1, 4, 256, 64)),
        ),
        "flash_attention_bwd": registry.TuneSpec(
            space=space.flash_attention_bwd_space,
            make_inputs=_flash_bwd_tune_inputs,
            call=_flash_bwd_tune_call,
            default_dtype=jnp.bfloat16,
            default_shapes=((1, 2, 128, 64), (1, 4, 256, 64)),
        ),
        # (slots, heads, n_pages, page_size, head_dim): two page-size
        # layouts so the serve scheduler's page-size pick has entries
        "decode_attention": registry.TuneSpec(
            space=space.decode_attention_space,
            make_inputs=_decode_tune_inputs,
            call=_decode_tune_call,
            default_dtype=jnp.bfloat16,
            default_shapes=((4, 4, 8, 32, 64), (4, 4, 4, 64, 64)),
        ),
        # (slots, chunk, heads, n_pages, page_size, head_dim)
        "prefill_attention": registry.TuneSpec(
            space=space.prefill_attention_space,
            make_inputs=_prefill_tune_inputs,
            call=_prefill_tune_call,
            default_dtype=jnp.bfloat16,
            default_shapes=((2, 8, 4, 4, 8, 64), (2, 16, 4, 3, 16, 64)),
        ),
    }


_TUNE = _tune_specs()

registry.register(registry.OpSpec(
    name="attention",
    reference=_attention_ref_lowering,
    kernel=_attention_kernel_lowering,
    eligible=_attention_eligible,
    plan_shape=_attention_plan_shape,
    vjp_fwd=_attention_vjp_fwd,
    vjp_bwd=_attention_vjp_bwd,
    tune=_TUNE["attention"],
    example=_attention_example,
    bad_example=_attention_bad_example,
))

# the attention backward is not a dispatch surface of its own (it is the
# VJP half of ``attention``), but it IS a tuned kernel: the per-shape
# level pick is the recompute-vs-stash threshold
registry.register(registry.OpSpec(
    name="flash_attention_bwd",
    tune=_TUNE["flash_attention_bwd"],
))

registry.register(registry.OpSpec(
    name="decode_attention",
    reference=_decode_ref_lowering,
    kernel=_decode_kernel_lowering,
    eligible=_decode_eligible,
    plan_shape=_decode_plan_shape,
    plan_dtype=_paged_plan_dtype,
    tune=_TUNE["decode_attention"],
    example=_decode_example,
    bad_example=_decode_bad_example,
    tp={
        # heads are the sharded axis: q (B, H, hd) on dim 1, K/V pools
        # ([L,] P, Hkv, page, hd) on their Hkv dim (-3), per-page scales
        # (P, Hkv) on dim 1; table/lengths, the layer index and the work
        # list are host metadata, replicated. Each shard attends its own
        # heads against its own pool slice, then the per-shard (B, H/tp,
        # hd) outputs all-gather back to full heads on dim 1.
        "heads": registry.TPContract(
            in_axes=(1, -3, -3, None, None, 1, 1, None, None),
            collective="all_gather",
            gather_axis=1,
        ),
    },
))

registry.register(registry.OpSpec(
    name="prefill_attention",
    reference=_prefill_ref_lowering,
    kernel=_prefill_kernel_lowering,
    eligible=_prefill_eligible,
    plan_shape=_prefill_plan_shape,
    plan_dtype=_paged_plan_dtype,
    tune=_TUNE["prefill_attention"],
    example=_prefill_example,
    bad_example=_prefill_bad_example,
    tp={
        # same layout as decode with a chunk axis: q (B, C, H, hd) sharded
        # on dim 2, pools on dim -3, scales on dim 1; gather restores full
        # heads on dim 2 of the (B, C, H/tp, hd) per-shard output.
        "heads": registry.TPContract(
            in_axes=(2, -3, -3, None, None, 1, 1, None),
            collective="all_gather",
            gather_axis=2,
        ),
    },
))
