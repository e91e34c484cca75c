"""Symmetric int8 quantization helpers for KV pages and weights (§4.4).

The paper's type-demotion transformation applied to the two dominant
serving residencies:

* **KV pages** — pools quantize per (page, kv-head): one f32 scale per
  (physical page, Hkv) cell, so a page's scale rides the same
  scalar-prefetch path as the page table and the ragged kernels dequantize
  tile loads in-register (``kernels/attention/decode.py`` / ``prefill.py``).
  Prefill writes whole pages (clean abs-max scales); decode appends one
  token at a time with a *running-max rescale*: the page's scale only ever
  grows, existing int8 values are rescaled by ``old_scale / new_scale``
  (a freed page's scale is reset to 0, so the first append into it wipes
  any stale payload — ratio 0 zeroes the ints).
* **Weights** — per-output-channel scales (one f32 per N column), the
  layout ``quantized_matmul`` folds into its MXU loop at the K-flush.

Everything here is pure jnp (models/ may not import kernel families); the
in-kernel dequant lives with the kernels.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# Symmetric int8: x ~= q * scale with q in [-127, 127], scale = amax / 127.
INT8_MAX = 127.0


def _quantize(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Round-to-nearest symmetric quantize at a given (broadcast) scale.
    A zero scale means "this block is all zeros" — guard the divide."""
    safe = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x.astype(jnp.float32) / safe),
                    -INT8_MAX, INT8_MAX).astype(jnp.int8)


def quantize_pages(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Whole-page quantize: x (..., Hkv, page, hd) float ->
    (int8 same-shape, f32 scales (..., Hkv)) with one scale per
    (page, kv-head) — abs-max over the (page, hd) axes."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-2, -1))
    scale = amax / INT8_MAX                       # (..., Hkv)
    q = _quantize(x, scale[..., None, None])
    return q, scale


def append_token_quantized(page_q: jax.Array, page_scale: jax.Array,
                           token: jax.Array, off: jax.Array
                           ) -> Tuple[jax.Array, jax.Array]:
    """Decode append: write one token into slot ``off`` of each gathered
    page with a running-max rescale.

    page_q (B, Hkv, page, hd) int8 — the gathered per-slot pages;
    page_scale (B, Hkv) f32; token (B, Hkv, hd) float; off (B,) int32.
    The scale only grows (new = max(old, token_amax/127)); existing ints
    are rescaled by old/new, so a freshly reset page (scale 0) starts
    clean regardless of its stale payload."""
    b = page_q.shape[0]
    tok_amax = jnp.max(jnp.abs(token.astype(jnp.float32)), axis=-1)
    new_scale = jnp.maximum(page_scale, tok_amax / INT8_MAX)   # (B, Hkv)
    ratio = jnp.where(new_scale > 0, page_scale / jnp.where(
        new_scale > 0, new_scale, 1.0), 0.0)
    page_q = jnp.clip(jnp.round(page_q.astype(jnp.float32)
                                * ratio[:, :, None, None]),
                      -INT8_MAX, INT8_MAX).astype(jnp.int8)
    tok_q = _quantize(token, new_scale[..., None])             # (B, Hkv, hd)
    page_q = page_q.at[jnp.arange(b), :, off].set(tok_q)
    return page_q, new_scale


def quantize_channelwise(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Weight quantize: w (K, N) float -> (int8 (K, N), f32 scales (N,))
    with one scale per output channel — the layout ``quantized_matmul``
    applies once per output column at its K-flush."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    scale = amax / INT8_MAX                       # (N,)
    return _quantize(w, scale[None, :]), scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Reference dequant: broadcast-multiply back to f32."""
    return q.astype(jnp.float32) * scale


def kv_dtype_of(name: str, compute_dtype) -> jnp.dtype:
    """Resolve an ``ArchConfig.kv_dtype`` string ("" = model compute
    dtype) to a concrete jnp dtype."""
    if not name:
        return jnp.dtype(compute_dtype)
    aliases = {"fp32": "float32", "bf16": "bfloat16"}
    return jnp.dtype(aliases.get(name, name))
