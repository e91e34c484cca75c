"""Pallas ragged multi-token prefill attention over a paged KV cache.

The prefill half of the serving hot path: a chunk of C query tokens of one
slot (C == the scheduler's page size) attends over that slot's cached
history *plus the chunk itself*, stored as fixed-size pages scattered
through the shared pool.  The decode kernel (``decode.py``) covers one
token per slot per step; this kernel closes the ROADMAP's "prefill chunks
still take the reference attention route" item with the same paper stack:

* memory access extraction (§4.1) — the scalar-prefetched ``table`` is
  resolved in the BlockSpec index maps, so the compute kernel only ever
  sees dense page tiles; ``starts`` rides along as the second prefetched
  scalar and parameterizes the causal window of every chunk;
* on-chip buffering (§4.2) — ``pages_per_tile`` separately pipelined page
  streams per KV tile, page fetches for tile j+1 overlapping the online-
  softmax update for tile j;
* tiled accumulation interleaving (§2.1.2) — the (C*grp, hd) accumulator
  in VMEM is revisited once per page tile with the exp(m_old - m_new)
  correction — the flash recurrence, now with C query rows per slot;
* condition flattening + tile skipping (§2.7) — causal intra-chunk
  masking is a branch-free ``where`` over (qpos, kpos) iotas; tiles wholly
  above the chunk's last position (or wholly behind its sliding window)
  are skipped with ``pl.when`` before any MXU work.

Layout: q (B, C, H, hd) — B chunked slots, GQA-grouped to (B, Hkv, C*grp,
hd) so each grid step feeds one (C*grp, page*ppt) MXU score tile;
k_pages / v_pages (P, Hkv, page, hd) (head-major: one kv head's page is
a contiguous (page, hd) tile), or the layer-stacked (L, P, Hkv, page, hd)
pool read at a scalar-prefetched ``layer`` as in the decode kernel;
table (B, n_pages) int32 page ids;
starts (B,) int32 page-aligned chunk offsets — slot b's queries sit at
positions ``starts[b] + [0, C)`` and its live KV length is
``starts[b] + C`` (the chunk was just written into its page).  Padded
tail positions inside the final chunk need no extra masking: causality
already hides them from every real query row.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import tpu_compiler_params


def _prefill_kernel(starts_ref, table_ref, layer_ref, *rest, n_tiles: int,
                    page: int, ppt: int, grp: int, chunk: int, window: int,
                    scale: float, quantized: bool):
    del layer_ref                     # read by the page index maps only
    if quantized:
        k_scale_ref, v_scale_ref, q_ref, *refs = rest
    else:
        k_scale_ref = v_scale_ref = None
        q_ref, *refs = rest
    k_refs = refs[:ppt]
    v_refs = refs[ppt:2 * ppt]
    o_ref = refs[2 * ppt]
    m_ref, l_ref, acc_ref = refs[2 * ppt + 1:]
    b = pl.program_id(0)
    hh = pl.program_id(1)
    j = pl.program_id(2)

    def load_tile(refs_, scale_ref):
        # int8 pools dequantize per page stream at load time (§4.4): the
        # (page, hd) tile is widened to f32 and multiplied by its page's
        # per-kv-head scale, fetched through the same scalar-prefetch path
        # that resolved the physical page id (§4.1)
        if scale_ref is None:
            return jnp.concatenate([r[0, 0] for r in refs_], axis=0)
        tiles = [r[0, 0].astype(jnp.float32)
                 * scale_ref[table_ref[b, j * ppt + i], hh]
                 for i, r in enumerate(refs_)]
        return jnp.concatenate(tiles, axis=0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = starts_ref[b]
    kv_len = start + chunk            # history + the chunk itself
    # structural tile skip (§2.7): tile j covers kpos [k_lo, k_hi]; a tile
    # wholly above the last query position (causal) or wholly behind the
    # earliest query's window is dead before any MXU work
    k_lo = j * ppt * page
    live = k_lo < kv_len
    if window > 0:
        k_hi = k_lo + ppt * page - 1
        live = jnp.logical_and(live, k_hi > start - window)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]                                   # (C*grp, hd)
        k = load_tile(k_refs, k_scale_ref)
        v = load_tile(v_refs, v_scale_ref)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        # row r of the flattened (C*grp) query axis is token r // grp
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // grp
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= qpos               # causal: also hides padded tails
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_tiles - 1)
    def _flush():
        # every query row sees at least its own position, so l > 0
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def prefill_attention_pallas(q: jax.Array, k_pages: jax.Array,
                             v_pages: jax.Array, table: jax.Array,
                             starts: jax.Array,
                             k_scale: jax.Array = None,
                             v_scale: jax.Array = None, *, layer=None,
                             window: int = 0, pages_per_tile: int = 1,
                             interpret: bool = False) -> jax.Array:
    """q (B, C, H, hd); k/v_pages (P, Hkv, page, hd), or (L, P, Hkv,
    page, hd) with the scalar ``layer`` to read; table (B, n_pages);
    starts (B,) page-aligned chunk offsets.  Returns (B, C, H, hd) f32.

    int8 pools additionally take ``k_scale`` / ``v_scale`` (P, Hkv) f32
    per-page per-kv-head scales (one layer's, also for a stacked pool);
    they ride the scalar-prefetch path next to ``table`` and the page
    tiles dequantize at load time."""
    quantized = k_scale is not None
    if k_pages.ndim == 4:
        # an unstacked pool is a stack of one layer (a free reshape)
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    b, c, h, hd = q.shape
    _, _, hkv, page, _ = k_pages.shape
    n_pages = table.shape[1]
    assert h % hkv == 0, (h, hkv)
    grp = h // hkv
    ppt = max(1, min(pages_per_tile, n_pages))
    if n_pages % ppt:
        # pad the logical page axis with page 0; padded positions sit at
        # kpos >= kv_len for every slot and are therefore always masked
        pad = ppt - n_pages % ppt
        table = jnp.pad(table, ((0, 0), (0, pad)))
        n_pages += pad
    n_tiles = n_pages // ppt
    rows = c * grp
    # (B, C, Hkv, grp, hd) -> (B, Hkv, C*grp, hd): one MXU row block per
    # (slot, kv-head) grid cell, query tokens × GQA group flattened
    qg = q.reshape(b, c, hkv, grp, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, rows, hd)

    kernel = functools.partial(
        _prefill_kernel, n_tiles=n_tiles, page=page, ppt=ppt, grp=grp,
        chunk=c, window=window, scale=1.0 / math.sqrt(hd),
        quantized=quantized)

    # int8 pools prefetch two extra scalar operands (the scale tables), so
    # the page index maps take a *prefetch tail of 0 or 2 refs
    def page_spec(i):
        # the i-th page stream of a KV tile: tile j holds logical pages
        # [j*ppt, (j+1)*ppt); the scalar-prefetched table resolves the
        # logical -> physical page id inside the index map (§4.1), and the
        # prefetched layer index the layer of the stack
        return pl.BlockSpec(
            (None, 1, 1, page, hd),
            lambda bb, hh, jj, st, tab, lyr, *_sc, i=i: (
                lyr[0], tab[bb, jj * ppt + i], hh, 0, 0))

    q_spec = pl.BlockSpec((1, 1, rows, hd),
                          lambda bb, hh, jj, *_: (bb, hh, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if quantized else 3,
        grid=(b, hkv, n_tiles),
        in_specs=[
            q_spec,
            *[page_spec(i) for i in range(ppt)],
            *[page_spec(i) for i in range(ppt)],
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda bb, hh, jj, *_: (bb, hh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),     # running max
            pltpu.VMEM((rows, 1), jnp.float32),     # running denom
            pltpu.VMEM((rows, hd), jnp.float32),    # weighted-V acc
        ],
    )
    prefetch = (starts.astype(jnp.int32), table,
                jnp.reshape(layer, (1,)).astype(jnp.int32))
    if quantized:
        prefetch += (k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, hd), jnp.float32),
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, qg, *([k_pages] * ppt), *([v_pages] * ppt))
    return out.reshape(b, hkv, c, grp, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, c, h, hd)
