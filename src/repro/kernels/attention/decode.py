"""Pallas ragged decode attention over a paged KV cache.

The serving hot path: one query token per slot against that slot's
variable-length KV history, stored as fixed-size pages scattered through a
shared pool.  This is the paper's memory stack applied to a cache:

* memory access extraction (§4.1) — the page table turns address
  computation into data: each work item's physical page ids are resolved
  from ``table`` before the call and read, prefetched, by the BlockSpec
  index maps, so the compute kernel only ever sees dense tiles;
* on-chip buffering + oversubscription (§4.2) — each page stream is a
  separately pipelined operand (``pages_per_tile`` of them), so page
  fetches for tile j+1 overlap the online-softmax update for tile j;
* memory banking (§4.3) — the pool's page axis is the bank axis: slots
  grow by grabbing any free page, never by reshaping a rectangle;
* tiled accumulation interleaving (§2.1.2) — the (grp, hd) accumulator in
  VMEM is revisited once per page tile with the usual exp(m_old - m_new)
  correction, exactly the flash recurrence;
* condition flattening (§2.7) — ragged tails are branch-free ``where``
  masks on key positions.

The grid walks the live work, not the shapes.  A step's cost on the chip
is set by its grid steps far more than by the bytes they move, so a grid
of every slot x kv head x KV tile of ``max_len`` spends most of its time
on rows that are idle or tiles past a row's length.  Instead a work
list built from ``lengths`` (and ``window``) before the call
(:func:`decode_work`) holds the live (row, KV tile) pairs, ordered by
row then tile, with first/last flags and each item's physical page ids,
riding scalar prefetch.  It depends on nothing a layer changes, so a
decode step builds it once and every layer's call reads it (``work=``);
a call given none builds its own.  The grid
is (Hkv, n_work), the work axis innermost, so each row's tiles are
consecutive for each kv head: the accumulators reset at a row's first
tile and the output is written at its last.  On the chip ``n_work`` is a
dynamic grid bound, so no step is spent past the live work; interpret
mode takes no dynamic bound and runs the same body over the list's
static capacity (B x n_tiles), skipping items past ``n_work``.  Page
streams past a row's length repeat its last live page, so the pipeline
fetches nothing for them.  Rows of length 0 have no work: their output
is never written by the kernel and is zeroed after the call.

Layout: q (B, H, hd) — one token per slot, GQA-grouped to (B, Hkv, grp,
hd); k_pages / v_pages (P, Hkv, page, hd) — head-major, so one page of
one kv head is a contiguous (page, hd) tile whose BlockSpec (1, 1, page,
hd) satisfies the TPU's (8, 128) tiling on its two minor dims — or the
model's layer-stacked (L, P, Hkv, page, hd) pool plus a ``layer`` index,
which rides scalar prefetch and picks the layer in the page index map, so
the kernel reads one layer's pages straight out of the stack and no
per-layer copy of the pool is ever made; table (B, n_pages) int32 page ids
(row j is the slot's j-th logical page); lengths (B,) int32 valid tokens
per slot (0 = inactive slot -> zero output, no NaNs).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import tpu_compiler_params

FIRST, LAST = 1, 2            # work-item flags: a row's first / last tile


def heuristic_pages_per_tile(n_pages: int, page_size: int) -> int:
    """Default KV-tile geometry: aim for the flash kernel's 512-row KV
    tile, capped at 8 page streams so the spec count stays small."""
    return max(1, min(n_pages, 512 // max(page_size, 1), 8))


def _live_tiles(lengths, n_tiles: int, tile_tokens: int, window: int, xp):
    """Per row: its first live KV tile, and how many it has.  Row b's live
    tiles run from the one holding its oldest visible key (0, or
    ``lengths[b] - window`` under a window) to the one holding
    ``lengths[b] - 1``; a row of length 0 has none."""
    hi = xp.minimum((lengths + tile_tokens - 1) // tile_tokens, n_tiles)
    lo = (xp.maximum(lengths - window, 0) // tile_tokens if window > 0
          else xp.zeros_like(hi))
    return lo, xp.where(lengths > 0, hi - lo, 0).astype(xp.int32)


def decode_work_list(lengths, n_tiles: int, tile_tokens: int,
                     window: int = 0):
    """The live (row, KV tile) pairs of one decode call.

    Returns ``(row, tile, flag, n_work)``: three int32 arrays of the
    static capacity ``B * n_tiles``, ordered by row then tile, whose first
    ``n_work`` items are the live pairs (``flag`` has ``FIRST`` on a row's
    first tile and ``LAST`` on its last) and whose remaining items repeat
    the last live one, so an index map never moves past the live work.
    :func:`decode_work_count` gives ``n_work`` on the host."""
    lengths = jnp.asarray(lengths, jnp.int32)
    b = lengths.shape[0]
    lo, count = _live_tiles(lengths, n_tiles, tile_tokens, window, jnp)
    # compares and row sums only (no scan, no gather): on the chip the
    # whole build is a few fused ops
    r = jnp.arange(b, dtype=jnp.int32)
    end = jnp.sum(jnp.where(r[None, :] <= r[:, None], count[None, :], 0),
                  axis=1, dtype=jnp.int32)      # one past each row's items
    start = end - count
    n_work = jnp.sum(count, dtype=jnp.int32)
    w = jnp.minimum(jnp.arange(b * n_tiles, dtype=jnp.int32),
                    jnp.maximum(n_work - 1, 0))
    # item w belongs to the one row whose items span it
    inside = (w[:, None] >= start[None, :]) & (w[:, None] < end[None, :])

    def pick(per_row):
        return jnp.sum(jnp.where(inside, per_row[None, :], 0), axis=1,
                       dtype=jnp.int32)

    first, last = pick(start), pick(end) - 1
    tile = pick(lo) + w - first
    flag = (jnp.where(w == first, FIRST, 0)
            + jnp.where(w == last, LAST, 0)).astype(jnp.int32)
    return pick(r), tile, flag, n_work


def decode_work(lengths, table, *, page: int, pages_per_tile: int,
                window: int = 0):
    """One decode call's work list with the page ids each item reads.

    Returns ``(row, tile, flag, pages, n_work)``: ``row``, ``tile`` and
    ``flag`` as :func:`decode_work_list` gives them, ``pages`` (B *
    n_tiles * ppt,) int32 the physical page of each item's ppt page
    streams, and ``n_work`` (1,) int32.  A stream past its row's length
    holds the row's last live page, so the pipeline sees an unchanged
    block index and fetches nothing for it.  The list depends only on
    ``lengths``, ``table``, ``window`` and the tile geometry, so a decode
    step builds it once and hands it to every layer's call."""
    lengths = jnp.asarray(lengths, jnp.int32)
    b, n_pages = table.shape
    ppt = max(1, min(pages_per_tile, n_pages))
    if n_pages % ppt:
        # pad the logical page axis; no item reads past its row's last
        # live page, so the padding is never fetched
        table = jnp.pad(table, ((0, 0), (0, ppt - n_pages % ppt)))
    n_tiles = table.shape[1] // ppt
    row, tile, flag, n_work = decode_work_list(lengths, n_tiles, ppt * page,
                                               window)
    # page tile*ppt + i of the item's row (§4.1), resolved here once so
    # that each index map reads one prefetched scalar
    streams = table.reshape(b * n_tiles, ppt)[row * n_tiles + tile]
    held = jnp.minimum(jnp.arange(ppt)[None, :],
                       (jnp.maximum(lengths - 1, 0) // page)[row][:, None]
                       - tile[:, None] * ppt)
    pages = jnp.sum(jnp.where(held[:, :, None] == jnp.arange(ppt),
                              streams[:, None, :], 0), axis=2,
                    dtype=jnp.int32).reshape(-1)
    return row, tile, flag, pages, n_work.reshape(1)


def decode_work_count(lengths, n_pages: int, page: int, pages_per_tile: int,
                      window: int = 0) -> int:
    """``n_work`` of one decode call, on the host: the grid steps per kv
    head that :func:`decode_attention_pallas` runs for these lengths."""
    ppt = max(1, min(pages_per_tile, n_pages))
    _, count = _live_tiles(np.asarray(lengths, np.int64), -(-n_pages // ppt),
                           ppt * page, window, np)
    return int(count.sum())


def _decode_kernel(lengths_ref, layer_ref, n_work_ref, row_ref, tile_ref,
                   flag_ref, pages_ref, *rest, page: int, ppt: int,
                   window: int, scale: float, quantized: bool):
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
    hh = pl.program_id(0)
    w = pl.program_id(1)
    row = row_ref[w]
    tile = tile_ref[w]
    flag = flag_ref[w]
    # items past n_work exist only in interpret mode, whose grid runs the
    # list's whole static capacity
    live = w < n_work_ref[0]

    def load_tile(refs_, scale_ref):
        # int8 pools dequantize per page stream at load time (§4.4): the
        # (page, hd) tile is widened to f32 and multiplied by its page's
        # per-kv-head scale, fetched through the same scalar-prefetch path
        # that resolved the physical page id (§4.1)
        if scale_ref is None:
            return jnp.concatenate([r[0, 0] for r in refs_], axis=0)
        tiles = [r[0, 0].astype(jnp.float32)
                 * scale_ref[pages_ref[w * ppt + i], hh]
                 for i, r in enumerate(refs_)]
        return jnp.concatenate(tiles, axis=0)

    @pl.when(jnp.logical_and(live, (flag & FIRST) != 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        length = lengths_ref[row]
        k_lo = tile * ppt * page                          # tile's first kpos
        q = q_ref[0, 0]                                   # (grp, hd)
        k = load_tile(k_refs, k_scale_ref)
        v = load_tile(v_refs, v_scale_ref)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length                              # ragged tail
        if window > 0:
            mask = jnp.logical_and(mask, kpos >= length - window)
        s = jnp.where(mask, s, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jnp.logical_and(live, (flag & LAST) != 0))
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, table: jax.Array,
                            lengths: jax.Array,
                            k_scale: jax.Array = None,
                            v_scale: jax.Array = None, *, layer=None,
                            window: int = 0, pages_per_tile: int = 1,
                            work=None,
                            interpret: bool = False) -> jax.Array:
    """q (B, H, hd); k/v_pages (P, Hkv, page, hd), or (L, P, Hkv, page,
    hd) with the scalar ``layer`` to read; table (B, n_pages); lengths
    (B,).  Returns (B, H, hd) f32.

    int8 pools additionally take ``k_scale`` / ``v_scale`` (P, Hkv) f32
    per-page per-kv-head scales (one layer's, also for a stacked pool);
    they ride the scalar-prefetch path next to the page ids and the page
    tiles dequantize at load time.

    ``work`` is :func:`decode_work` of the same lengths, table and window,
    built once for all the layers of a step; one built at another tile
    geometry (its shapes say so) is ignored, and so is none: the call then
    builds its own."""
    quantized = k_scale is not None
    if k_pages.ndim == 4:
        # an unstacked pool is a stack of one layer (a free reshape)
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    b, h, hd = q.shape
    _, _, hkv, page, _ = k_pages.shape
    n_pages = table.shape[1]
    assert h % hkv == 0, (h, hkv)
    grp = h // hkv
    ppt = max(1, min(pages_per_tile, n_pages))
    cap = b * -(-n_pages // ppt)                 # the list's static capacity
    lengths = lengths.astype(jnp.int32)
    if work is None or work[0].shape != (cap,) or work[3].shape != (
            cap * ppt,):
        work = decode_work(lengths, table, page=page, pages_per_tile=ppt,
                           window=window)
    row, tile, flag, pages, n_work = work
    qg = q.reshape(b, hkv, grp, hd)

    kernel = functools.partial(
        _decode_kernel, page=page, ppt=ppt, window=window,
        scale=1.0 / math.sqrt(hd), quantized=quantized)

    # index maps take (hh, w) and the prefetched (lengths, layer, n_work,
    # row, tile, flag, pages) plus, for int8 pools, the two scale tables
    def page_spec(i):
        def index(hh, w, lens, lyr, nw, wrow, wtile, wflag, wpages, *_):
            return lyr[0], wpages[w * ppt + i], hh, 0, 0
        return pl.BlockSpec((None, 1, 1, page, hd), index)

    def row_index(hh, w, lens, lyr, nw, wrow, *_):
        return wrow[w], hh, 0, 0

    # the chip's grid stops at n_work; interpret mode runs the capacity
    n_grid = cap if interpret else jnp.maximum(n_work[0], 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9 if quantized else 7,
        grid=(hkv, n_grid),
        in_specs=[
            pl.BlockSpec((1, 1, grp, hd), row_index),
            *[page_spec(i) for i in range(ppt)],
            *[page_spec(i) for i in range(ppt)],
        ],
        out_specs=pl.BlockSpec((1, 1, grp, hd), row_index),
        scratch_shapes=[
            pltpu.VMEM((grp, 1), jnp.float32),     # running max
            pltpu.VMEM((grp, 1), jnp.float32),     # running denom
            pltpu.VMEM((grp, hd), jnp.float32),    # weighted-V acc
        ],
    )
    prefetch = (lengths, jnp.reshape(layer, (1,)).astype(jnp.int32), n_work,
                row, tile, flag, pages)
    if quantized:
        prefetch += (k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, grp, hd), jnp.float32),
        compiler_params=tpu_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, qg, *([k_pages] * ppt), *([v_pages] * ppt))
    # rows without work were never visited: their blocks hold whatever
    # the output buffer held
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.reshape(b, h, hd)
