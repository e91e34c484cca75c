"""Pallas flash-attention backward — recompute-based training kernels.

The forward stores only the per-row logsumexp (``lse = m + log l``); the
backward recomputes each P tile from (q, k, lse) on the fly and folds the
softmax-gradient correction ``dS = P * (dP - delta)`` (with
``delta = rowsum(dO * O)`` precomputed once, jnp-side) into three output
accumulators — dQ, dK, dV — without ever materializing the (S, S) score
matrix.  This is §2.1 accumulation interleaving applied to the *gradient*
reduction, plus §2.7 masked tails: causal / sliding-window tile skipping is
structural (grid-index arithmetic), so dead tiles issue no MXU work.

Two kernels with independent tile geometry, per the standard TPU
formulation (different iteration orders want different blocks):

* dQ:  grid (B*H, Sq/bq, Skv/bkv), KV sequential inner — the dQ tile is
  the loop-carried accumulator, flushed when the KV sweep ends.
* dKV: grid (B*H, Skv/bkv, Sq/bq), Q sequential inner — dK and dV tiles
  are the carries, sharing one recomputed P tile per grid step, flushed
  when the Q sweep ends.

GQA grouping note: dispatch expands KV heads *before* the custom-VJP
boundary, so the per-group gradient reduction (summing dK/dV over the
query heads of one KV head) happens in the VJP of that broadcast — the
kernels always see matched head counts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import tpu_compiler_params


def _tile_live(qi, kj, block_q: int, block_kv: int, causal: bool,
               window: int):
    """Structural liveness of the (qi, kj) tile — same §2.7 condition
    flattening the forward uses; dead tiles are skipped branch-free."""
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = kj * block_kv
    k_hi = k_lo + block_kv - 1
    live = True
    if causal:
        live = k_lo <= q_hi
    if window > 0:
        live = jnp.logical_and(live, k_hi > q_lo - window)
    return live, q_lo, k_lo


def _p_and_ds(q, k, v, do, lse, di, q_lo, k_lo, *, causal, window, scale):
    """Recompute one P tile from the lse residual and form dS.

    Returns (p, ds), both (bq, bkv) f32: p = exp(scale*qk^T - lse) under
    the causal/window mask, ds = p * (dP - delta) with dP = dO V^T.  The
    shared tile every accumulator update is built from.  ``lse`` and
    ``di`` arrive as (bq, 1) columns, which broadcast along the key axis.
    """
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - di)
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               acc_ref, *, n_kv: int, block_q: int, block_kv: int,
               causal: bool, window: int, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live, q_lo, k_lo = _tile_live(qi, kj, block_q, block_kv, causal, window)

    @pl.when(live)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        _, ds = _p_and_ds(q, k, v_ref[0], do_ref[0], lse_ref[0], di_ref[0],
                          q_lo, k_lo, causal=causal, window=window,
                          scale=scale)
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    @pl.when(kj == n_kv - 1)
    def _flush():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, n_q: int, block_q: int,
                block_kv: int, causal: bool, window: int, scale: float):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live, q_lo, k_lo = _tile_live(qi, kj, block_q, block_kv, causal, window)

    @pl.when(live)
    def _step():
        q = q_ref[0]
        do = do_ref[0]
        p, ds = _p_and_ds(q, k_ref[0], v_ref[0], do, lse_ref[0], di_ref[0],
                          q_lo, k_lo, causal=causal, window=window,
                          scale=scale)
        dv_acc[...] += jnp.dot(p.T.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dk_acc[...] += jnp.dot(ds.T.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                               o: jax.Array, lse: jax.Array, do: jax.Array,
                               *, causal: bool = True, window: int = 0,
                               block_q: int = 256, block_kv: int = 256,
                               interpret: bool = False):
    """Fused recompute backward.  q,k,v: (B, H, S, hd); o, do: (B, H, S,
    hd) f32; lse: (B, H, S) f32.  Returns (dq, dk, dv) as f32 — callers
    cast back to the primal dtypes."""
    b, h, s, hd = q.shape
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    assert s % block_q == 0 and s % block_kv == 0
    bh = b * h
    n_q = s // block_q
    n_kv = s // block_kv
    scale = 1.0 / math.sqrt(hd)

    qf, kf, vf, dof = (t.reshape(bh, s, hd) for t in (q, k, v, do))
    # per-row lse / delta ride as (bh, s, 1) columns: a (1, block_q, 1)
    # block tiles on the TPU, where a (1, block_q) row block does not
    lsef = lse.reshape(bh, s, 1)
    # delta = rowsum(dO * O): O(S*hd) precompute shared by both kernels
    dif = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                  axis=-1).reshape(bh, s, 1)

    q_spec = pl.BlockSpec((1, block_q, hd), lambda g, i, j: (g, i, 0))
    kv_spec = pl.BlockSpec((1, block_kv, hd), lambda g, i, j: (g, j, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda g, i, j: (g, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_kv=n_kv, block_q=block_q,
                          block_kv=block_kv, causal=causal, window=window,
                          scale=scale),
        grid=(bh, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, dif)

    # dKV sweeps Q on the inner (sequential) axis: swap the roles of the
    # index-map grid coordinates so i walks Q tiles for a fixed KV tile
    q_spec_i = pl.BlockSpec((1, block_q, hd), lambda g, j, i: (g, i, 0))
    kv_spec_i = pl.BlockSpec((1, block_kv, hd), lambda g, j, i: (g, j, 0))
    row_spec_i = pl.BlockSpec((1, block_q, 1), lambda g, j, i: (g, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, block_q=block_q,
                          block_kv=block_kv, causal=causal, window=window,
                          scale=scale),
        grid=(bh, n_kv, n_q),
        in_specs=[q_spec_i, kv_spec_i, kv_spec_i, q_spec_i, row_spec_i,
                  row_spec_i],
        out_specs=[kv_spec_i, kv_spec_i],
        out_shape=[jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
                   jax.ShapeDtypeStruct((bh, s, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_kv, hd), jnp.float32),
                        pltpu.VMEM((block_kv, hd), jnp.float32)],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, dif)

    shape = (b, h, s, hd)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)
