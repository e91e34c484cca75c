"""Expert-parallel MoE via shard_map: explicit all-to-all dispatch.

GSPMD cannot partition the global scatter-dispatch of ``moe.moe_apply``
(indexed writes into the expert-sharded buffer force replication — the
dry-run measured ~4000 s/step of collective time on kimi-k2).  This module
is the TPU-native form of the paper's streaming dataflow (§3.3) + striping
(§4.3): every device is a PE:

  1. route the LOCAL token shard (tokens arrive sharded over the data axes
     (batch) and the model axis (sequence, from Megatron-SP));
  2. build per-expert send buffers with branch-free capacity masks (§2.7);
  3. ``all_to_all`` over `model` moves payloads to the expert owners (the
     FIFO channels between PEs);
  4. each device runs its E/n_ep experts on ITS OWN row's slots; expert
     weights are STORED fully sharded — experts over the EP axes, d_expert
     striped over `data` (ZeRO-3, §4.3) — and all-gathered over `data` at
     use (backward reduce-scatters the gradient automatically: grad of
     all_gather is psum_scatter).  Slots never cross the data axis, so no
     partial-sum mixing of different rows' tokens can occur;
  5. reverse all_to_all returns outputs; owners combine with top-k gates.

Capacity is per (device, expert): C = ceil(T_dev * k * cf / E) rounded to
the sublane (§3.1), so expert FLOPs stay proportional to active params.
Experts pad up to a multiple of the model axis (dummies get -inf router
logits; their slots stay empty).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.memory import DtypePolicy
from ..kernels import dispatch as kdispatch
from .layers import mlp_apply
from .moe import MoESpec, _act

Params = Dict[str, jax.Array]


def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    r = 1
    for a in axes:
        r *= mesh.shape[a]
    return r


def _local_dispatch(tokens, logits, s: MoESpec, e_pad: int, cap: int):
    """Route T_dev local tokens -> (E_pad, cap, d) send buffer + combine
    metadata.  Pure local ops (§2.7 branch-free capacity masking)."""
    t_dev, _ = tokens.shape
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E_pad)
    gate, eidx = jax.lax.top_k(probs, s.top_k)
    if s.norm_topk:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    tk = t_dev * s.top_k
    flat_e = eidx.reshape(tk)
    flat_t = jnp.repeat(jnp.arange(t_dev), s.top_k)
    flat_g = gate.reshape(tk)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.bincount(flat_e, length=e_pad)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(tk) - starts[se]
    keep = rank < cap
    safe_rank = jnp.where(keep, rank, cap)
    buf = jnp.zeros((e_pad, cap, tokens.shape[1]), tokens.dtype)
    buf = buf.at[se, safe_rank].set(
        jnp.where(keep[:, None], tokens[st], 0), mode="drop")
    return buf, gate, eidx, se, st, sg, keep, safe_rank


def moe_apply_sharded(p: Params, s: MoESpec, x: jax.Array, dt: DtypePolicy,
                      *, mesh: Mesh, dp_axes: Tuple[str, ...],
                      model_axis: str = "model",
                      ep_axes: Tuple[str, ...] = ("model",)
                      ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) sharded P(dp, model-if-divisible, None).
    Expert weights: wg/wu (E, d, f) P(ep_axes, None, data); wd (E, f, d)
    P(ep_axes, data, None).  ``ep_axes`` is the expert-parallel axis set —
    ("pod", "model") for the trillion-param arch stripes expert state over
    all 512 chips and routes tokens cross-pod (the a2a spans both axes).
    Returns (out like x, aux loss scalar)."""
    cdt = dt.compute
    n_model = mesh.shape[model_axis]
    n_ep = _axes_size(mesh, ep_axes)
    data_axis = "data"
    e_pad = s.e_pad
    assert e_pad % n_ep == 0, (e_pad, n_ep)
    e_loc = e_pad // n_ep
    b, sq, d = x.shape
    dp_sz = _axes_size(mesh, dp_axes)
    batch_ok = b % dp_sz == 0
    seq_ax = model_axis if (sq % n_model == 0 and sq > 1) else None
    t_dev = (b * sq) // ((dp_sz if batch_ok else 1)
                         * (n_model if seq_ax else 1))
    cap = math.ceil(t_dev * s.top_k * s.capacity_factor / s.n_experts)
    cap = max(8, -(-cap // 8) * 8)

    ep = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    x_spec = P(dp_axes if batch_ok else None, seq_ax, None)
    wgu_spec = P(ep, None, data_axis)
    wd_spec = P(ep, data_axis, None)
    red_axes = (*dp_axes, model_axis) if seq_ax else tuple(dp_axes)

    def body(xl, router, wg, wu, wd):
        # ZeRO-3 (§4.3): gather the f-striped expert weights over `data`
        # for this layer's compute; grads reduce-scatter automatically
        # (transpose of all_gather is psum_scatter).
        if mesh.shape[data_axis] > 1:
            wg = jax.lax.all_gather(wg, data_axis, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, data_axis, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, data_axis, axis=1, tiled=True)
        bl, sl, _ = xl.shape
        tokens = xl.reshape(bl * sl, d)
        logits = (tokens.astype(jnp.float32)
                  @ router.astype(jnp.float32))
        if e_pad != s.n_experts:
            logits = jnp.pad(logits, ((0, 0), (0, e_pad - s.n_experts)),
                             constant_values=-1e30)
        buf, gate, eidx, se, st, sg, keep, safe_rank = _local_dispatch(
            tokens.astype(cdt), logits, s, e_pad, cap)

        # load-balance aux loss on true (unpadded) experts
        probs = jax.nn.softmax(logits[:, :s.n_experts], axis=-1)
        me = jax.lax.pmean(probs.mean(axis=0), red_axes)
        ce = jax.lax.pmean(
            jax.nn.one_hot(eidx[:, 0], s.n_experts).mean(axis=0), red_axes)
        aux = s.aux_loss_coef * s.n_experts * jnp.sum(me * ce)

        # ---- dispatch a2a over the EP axes (§3.3 channels) ----
        send = buf.reshape(n_ep, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, ep_axes, 0, 0, tiled=False)
        # recv: (n_ep_src, e_loc, cap, d) -> (e_loc, src*cap, d)
        recv = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_ep * cap, d)

        # ---- expert FFN; d_expert striped over `data` (§4.3); the
        # per-device expert contractions route through dispatch so tuned
        # Pallas plans reach the shard_map path too ----
        gmm = functools.partial(kdispatch.grouped_matmul, policy=s.dispatch)
        g = gmm(recv, wg.astype(cdt))
        if s.activation in ("swiglu", "geglu"):
            u = gmm(recv, wu.astype(cdt))
            h = _act(g, s.activation) * u
        else:
            h = _act(g, s.activation)
        out = gmm(h, wd.astype(cdt))

        # ---- return a2a + local combine ----
        back = out.reshape(e_loc, n_ep, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(back, ep_axes, 0, 0, tiled=False)
        back = back.reshape(e_pad, cap, d)
        per_assign = back[se, safe_rank]
        per_assign = jnp.where(keep[:, None], per_assign, 0)
        per_assign = per_assign * sg[:, None].astype(cdt)
        combined = jnp.zeros((bl * sl, d), cdt).at[st].add(per_assign)
        return combined.reshape(bl, sl, d), aux

    body_sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), wgu_spec, wgu_spec, wd_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    out, aux = body_sm(x, p["router"], p["wg"], p["wu"], p["wd"])
    if s.n_shared_experts:
        out = out + mlp_apply(p["shared"], x.astype(cdt), s.activation, dt)
    return out, aux
