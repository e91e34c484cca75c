"""Oracle for causal flash attention."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _gather_pages(pages: jax.Array, table: jax.Array,
                  scale: jax.Array, layer=None) -> jax.Array:
    """Gather a (P, Hkv, page, hd) pool's pages per slot into a dense
    (B, n_pages * page, Hkv, hd) view — of a layer-stacked (L, P, Hkv,
    page, hd) pool, those of ``layer``; int8 pools (scale (P, Hkv) f32
    per-page per-kv-head) dequantize to f32 at gather time — the oracle
    twin of the kernels' in-tile dequant."""
    b = table.shape[0]
    hkv, hd = pages.shape[-3], pages.shape[-1]
    g = pages[table] if layer is None else pages[layer, table]
    # g: (B, n_pages, Hkv, page, hd)
    if scale is not None:
        g = g.astype(jnp.float32) * scale[table][:, :, :, None, None]
    return g.transpose(0, 1, 3, 2, 4).reshape(b, -1, hkv, hd)


def decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                         v_pages: jax.Array, table: jax.Array,
                         lengths: jax.Array,
                         k_scale: jax.Array = None,
                         v_scale: jax.Array = None, *,
                         window: int = 0, layer=None) -> jax.Array:
    """Oracle for paged ragged decode: gather pages to a dense (B, S, Hkv,
    hd) view (dequantizing int8 pools through ``k_scale`` / ``v_scale``),
    mask key positions past each slot's length (and older than its
    window), f32 softmax.  A layer-stacked pool is read at ``layer``.
    q (B, H, hd) -> (B, H, hd) f32."""
    b, h, hd = q.shape
    hkv = k_pages.shape[-3]
    grp = h // hkv
    k = _gather_pages(k_pages, table, k_scale, layer)  # (B, n_pages*page, ..)
    v = _gather_pages(v_pages, table, v_scale, layer)
    if grp > 1:                                      # GQA group broadcast
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             k.shape[:3] + (grp, hd)).reshape(b, -1, h, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             v.shape[:3] + (grp, hd)).reshape(b, -1, h, hd)
    scores = jnp.einsum("bhd,bshd->bhs", q, k).astype(jnp.float32) \
        / math.sqrt(hd)
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = kpos < lengths[:, None]
    if window > 0:
        mask &= kpos >= lengths[:, None] - window
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs.astype(v.dtype), v)
    # fully-masked rows (inactive slots, lengths == 0) -> exact zeros
    return jnp.where((lengths > 0)[:, None, None],
                     out.astype(jnp.float32), 0.0)


def prefill_attention_ref(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, table: jax.Array,
                          starts: jax.Array,
                          k_scale: jax.Array = None,
                          v_scale: jax.Array = None, *,
                          window: int = 0, layer=None) -> jax.Array:
    """Oracle for paged ragged multi-token prefill: gather pages to a
    dense (B, S, Hkv, hd) view (dequantizing int8 pools through
    ``k_scale`` / ``v_scale``), mask causally against each chunk's own
    positions (``starts[b] + [0, C)``; the chunk's own keys are already in
    the pool) and by the sliding window, f32 softmax.  A layer-stacked
    pool is read at ``layer``.  q (B, C, H, hd) -> (B, C, H, hd) f32."""
    b, c, h, hd = q.shape
    hkv = k_pages.shape[-3]
    grp = h // hkv
    k = _gather_pages(k_pages, table, k_scale, layer)  # (B, n_pages*page, ..)
    v = _gather_pages(v_pages, table, v_scale, layer)
    if grp > 1:                                      # GQA group broadcast
        k = jnp.broadcast_to(k[:, :, :, None, :],
                             k.shape[:3] + (grp, hd)).reshape(b, -1, h, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :],
                             v.shape[:3] + (grp, hd)).reshape(b, -1, h, hd)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) \
        / math.sqrt(hd)
    qpos = starts[:, None] + jnp.arange(c)[None, :]          # (B, C)
    kpos = jnp.arange(k.shape[1])                            # (S,)
    mask = kpos[None, None, :] <= qpos[:, :, None]           # (B, C, S)
    if window > 0:
        mask &= kpos[None, None, :] > qpos[:, :, None] - window
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v)
    return out.astype(jnp.float32)


def _masked_scores(q: jax.Array, k: jax.Array, causal: bool,
                   window: int) -> jax.Array:
    """Dense (B, H, S, S) f32 scaled scores with the causal/window mask
    applied — the one definition of the mask semantics both the forward
    oracle and the lse residual derive from."""
    s, hd = q.shape[2], q.shape[3]
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    return jnp.where(mask[None, None], scores, -1e30)


def attention_lse_ref(q: jax.Array, k: jax.Array, *, causal: bool = True,
                      window: int = 0) -> jax.Array:
    """Per-row logsumexp of the masked scaled scores: (B, H, S) f32.

    The residual the fused backward consumes, computed the dense way —
    used only when the forward itself ran a T0/T1 reference lowering
    (which already materialized (S, S))."""
    return jax.scipy.special.logsumexp(
        _masked_scores(q, k, causal, window), axis=-1)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0) -> jax.Array:
    """q,k,v: (B, H, S, hd).  f32 softmax; returns (B, H, S, hd) f32."""
    probs = jax.nn.softmax(_masked_scores(q, k, causal, window), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      probs.astype(v.dtype), v).astype(jnp.float32)
