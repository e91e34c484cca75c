"""Pallas flash attention — accumulation interleaving (§2.1) flagship.

The softmax reduction over keys is a loop-carried dependency (running max,
running denominator, weighted-value accumulator).  The online-softmax
recurrence is exactly the paper's interleaving: the (bq, hd) accumulator
tile in VMEM is revisited once per KV tile, the correction factor
exp(m_old - m_new) playing the role of the delayed write-back.  Causal
tile-skipping is done with a branch-free `when` (condition flattening §2.7):
skipped tiles never issue MXU work.

Grid: (batch*heads, Sq/bq, Skv/bkv) with the KV axis 'arbitrary'
(sequential — it carries the accumulator) and the rest 'parallel'
(replication §3.2).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import tpu_compiler_params


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  n_kv: int, block_q: int, block_kv: int, causal: bool,
                  window: int, scale: float, with_lse: bool):
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref, (m_ref, l_ref, acc_ref) = None, rest
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal / window tile skip (structural, not data-dependent)
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = kj * block_kv
    k_hi = k_lo + block_kv - 1
    live = True
    if causal:
        live = k_lo <= q_hi
    if window > 0:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _step():
        q = q_ref[0]                      # (bq, hd)
        k = k_ref[0]                      # (bkv, hd)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        if with_lse:
            # per-row logsumexp m + log(l): the only residual the fused
            # backward needs to recompute P tiles (store lse, not P)
            # (block_q, 1) column, stored as-is: the residual keeps a
            # trailing unit axis so its block (1, block_q, 1) tiles on
            # the TPU without an in-kernel relayout
            lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 512, block_kv: int = 512,
                           return_residuals: bool = False,
                           interpret: bool = False):
    """q,k,v: (B, H, S, hd) -> (B, H, S, hd) f32.

    With ``return_residuals`` also emits the per-row logsumexp ``lse``
    (B, H, S) f32 — the only forward state the fused recompute backward
    (``backward.py``) needs beyond q/k/v/o.
    """
    b, h, s, hd = q.shape
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    assert s % block_q == 0 and s % block_kv == 0
    bh = b * h
    n_q = s // block_q
    n_kv = s // block_kv
    qf = q.reshape(bh, s, hd)
    kf = k.reshape(bh, s, hd)
    vf = v.reshape(bh, s, hd)

    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, block_q=block_q, block_kv=block_kv,
        causal=causal, window=window, scale=1.0 / math.sqrt(hd),
        with_lse=return_residuals)
    out_specs = [pl.BlockSpec((1, block_q, hd), lambda g, i, j: (g, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, s, hd), jnp.float32)]
    if return_residuals:
        out_specs.append(pl.BlockSpec((1, block_q, 1),
                                      lambda g, i, j: (g, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, s, 1), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=out_specs if return_residuals else out_specs[0],
        out_shape=out_shape if return_residuals else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),   # weighted-V acc
        ],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    if return_residuals:
        out, lse = outs
        return out.reshape(b, h, s, hd), lse.reshape(b, h, s)
    return outs.reshape(b, h, s, hd)
