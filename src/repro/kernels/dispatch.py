"""Kernel dispatch: the single entry point models use for hot contractions.

The paper's transformations only pay off when the *whole* dataflow graph
runs through the transformed kernels (FBLAS's module-routing argument): a
tuned Pallas matmul buys nothing while the surrounding projections still
lower through raw einsums.  This module is the routing layer that closes
that gap — ``dispatch.matmul`` / ``dispatch.attention`` /
``dispatch.grouped_matmul`` / ``dispatch.decode_attention`` /
``dispatch.prefill_attention`` route each call to the Pallas kernel or to
the pure-jnp reference lowering based on policy and shape/dtype/backend
eligibility.

Since the registry redesign this module is a *thin facade*: every op is a
declarative :class:`repro.kernels.registry.OpSpec` (reference lowering,
kernel lowering, eligibility predicate, tuned-plan key schema, optional
custom-VJP pair, tune-space hookup — one registration in the op family's
``ops.py``), and every facade below collapses its policy argument and
delegates to ``registry.call`` — the ONE generic code path holding the
exact → nearest → heuristic tuned-plan lookup, the level gate, and the
``(op, route)`` counters that used to be five hand-wired copies.

Policy (the ``DispatchPolicy`` knob threaded through ``configs/base.py``):

  "kernels"   — force the Pallas path whenever structurally possible
                (interpret mode on CPU); used by the differential tests.
                A tuned plan that says "the reference lowering wins at
                this shape" (level <= T1) is overridden: the Pallas
                lowering runs with the tuned tile geometry.
  "reference" — force the einsum reference lowering; bitwise-identical to
                the pre-dispatch model code
  "auto"      — kernels on TPU when eligible, reference otherwise (CPU HLO
                interpretation of a Pallas kernel is never a win); a tuned
                level <= T1 plan is honored as the reference route; the
                ``REPRO_DISPATCH`` env var can override "auto" globally

Eligibility is decided at trace time (shapes are static), so the decision
costs nothing at run time.  Matmul kernel paths carry a ``jax.custom_vjp``
whose backward is the reference contraction; the attention kernel path
pairs the flash forward (which emits per-row logsumexp residuals) with the
fused recompute Pallas backward (``attention/backward.py``) so a
``dispatch="kernels"`` train step never materializes the (S, S) score
matrix in either direction.  Per-route counters (``stats()``, plus
``plan_source_stats()`` tagging each decision with the tuned-plan lookup
route that produced it) let regression tests prove the serve/train graphs
actually flow through dispatch, and the ``forbid_dense_scores()`` scope
turns any dense-score lowering into a trace-time assertion for those
tests.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from . import registry
from .registry import (forbid_dense_scores, plan_source_stats,  # noqa: F401
                       reset_stats, stats, stats_scope)

MODES = ("kernels", "reference", "auto")


@dataclasses.dataclass(frozen=True)
class DispatchPolicy:
    """Routing policy: "kernels" | "reference" | "auto"."""

    mode: str = "auto"

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"dispatch mode must be one of {MODES}, got {self.mode!r}")


PolicyLike = Union[DispatchPolicy, str, None]

# module default consulted when a call site passes policy=None/"auto";
# seeded from the environment so launchers can force a path globally.
_default_mode: Optional[str] = None


def default_mode() -> str:
    global _default_mode
    if _default_mode is None:
        env = os.environ.get("REPRO_DISPATCH", "auto")
        _default_mode = env if env in MODES else "auto"
    return _default_mode


def set_default_mode(mode: str) -> None:
    DispatchPolicy(mode)          # validate
    global _default_mode
    _default_mode = mode


@contextlib.contextmanager
def policy_scope(mode: str):
    """Temporarily force the module-default mode (tests, dry-runs)."""
    prev = default_mode()
    set_default_mode(mode)
    try:
        yield
    finally:
        set_default_mode(prev)


def resolve_mode(policy: PolicyLike) -> str:
    """Collapse a call-site policy to "kernels" | "reference" | "auto"."""
    if policy is None:
        mode = "auto"
    elif isinstance(policy, DispatchPolicy):
        mode = policy.mode
    else:
        mode = str(policy)
        DispatchPolicy(mode)      # validate
    if mode == "auto":
        mode = default_mode()
    return mode


def _kernels_by_default() -> bool:
    """auto-mode backend gate: compiled Pallas on TPU is a win; HLO
    interpretation of the same kernel on CPU/GPU is never one."""
    return jax.default_backend() == "tpu"


def _call(name: str, *args, statics=None, policy: PolicyLike = None,
          tp: Optional[str] = None):
    """Collapse the policy knob and hand off to the registry's one path.

    ``tp`` names the op's declared sharding contract for this call site
    (see ``registry.TPContract``); it only acts inside a
    ``registry.tp_scope`` (the shard_map'd serving region), where the
    registry completes the op with the contract's collective."""
    mode = resolve_mode(policy)
    allow = mode != "reference" and (mode == "kernels"
                                     or _kernels_by_default())
    return registry.call(name, *args, statics=statics, mode=mode,
                         allow_kernels=allow, tp=tp)


def causal_mask(qpos: jax.Array, kpos: jax.Array, window: int,
                causal: bool = True) -> jax.Array:
    """Re-export of the attention family's branch-free causal/window mask
    (condition flattening, §2.7)."""
    from .attention.ops import causal_mask as _causal_mask
    return _causal_mask(qpos, kpos, window, causal)


# ------------------------------------------------------------------ facades
def matmul(x: jax.Array, w: jax.Array, *,
           policy: PolicyLike = None, tp: Optional[str] = None) -> jax.Array:
    """Contract the last axis of ``x`` with the first axis of ``w``.

    x: (..., K); w: (K, N1[, N2, ...]).  Returns x.shape[:-1] + w.shape[1:]
    in the promoted input dtype — the generalized form of every projection
    / dense / head matmul in the models (``bsd,dhk->bshk`` is exactly this
    with w pre-reshaped, so the reference lowering is bit-identical to the
    einsums it replaces).

    ``tp`` tags the call site's sharding contract for shard_map'd serving
    ("col" = output channels device-local, no collective; "row" =
    contraction sharded, all-reduce here); inert outside a tp scope.
    """
    return _call("matmul", x, w, policy=policy, tp=tp)


def grouped_matmul(x: jax.Array, w: jax.Array, *,
                   policy: PolicyLike = None) -> jax.Array:
    """Per-group matmul: x (G, C, K) x w (G, K, N) -> (G, C, N).

    The MoE expert contraction.  The kernel route unrolls the (static)
    group axis into per-expert Pallas matmuls (one shared tuned plan,
    resolved on the per-expert cell); the reference route is the batched
    einsum the MoE layer always used.
    """
    return _call("grouped_matmul", x, w, policy=policy)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int = 0, softcap: float = 0.0,
              mask: Optional[jax.Array] = None,
              accum_dtype: Any = jnp.float32,
              out_dtype: Any = None,
              impl: str = "blockwise",
              block_kv: int = 512, q_splits: int = 4, unroll: bool = False,
              policy: PolicyLike = None) -> jax.Array:
    """Scaled-dot-product attention over model-layout tensors.

    q: (B, Sq, H, hd); k, v: (B, Skv, H, hd), already GQA-expanded.
    Returns (B, Sq, H, hd) in ``out_dtype`` (default: q's dtype).

    ``mask`` (broadcastable to (B, H, Sq, Skv)) overrides the causal/window
    mask — used by the decode path's rolling-cache validity mask, and
    always routed to the reference (the kernel bakes in causal/window
    only).  ``impl`` picks the reference lowering on the reference route:
    "naive" materializes (Sq, Skv); "blockwise" is the tiled XLA
    formulation (with ``block_kv`` / ``q_splits`` / ``unroll``).
    """
    out_dtype = q.dtype if out_dtype is None else out_dtype
    return _call(
        "attention", q, k, v, mask,
        statics=dict(causal=bool(causal), window=int(window),
                     softcap=float(softcap), accum_dtype=accum_dtype,
                     out_dtype=out_dtype, impl=impl, block_kv=block_kv,
                     q_splits=q_splits, unroll=bool(unroll)),
        policy=policy)


def decode_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     table: jax.Array, lengths: jax.Array,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None, *,
                     layer: Optional[jax.Array] = None,
                     window: int = 0, softcap: float = 0.0,
                     accum_dtype: Any = jnp.float32,
                     out_dtype: Any = None,
                     work=None,
                     policy: PolicyLike = None) -> jax.Array:
    """Ragged decode attention over a paged KV cache — the serving hot path.

    q (B, H, hd) one query token per slot; k_pages / v_pages (P, Hkv,
    page, hd) shared pools, or the model's layer-stacked (L, P, Hkv, page,
    hd) pools with the scalar ``layer`` to read (the pool's rank says
    which); table (B, n_pages) logical->physical page ids; lengths (B,)
    valid tokens per slot (0 = inactive -> zero output).  int8 pools
    additionally pass ``k_scale`` / ``v_scale`` (P, Hkv) f32 per-page
    per-kv-head scales of the layer read (both or neither); the kernel
    dequantizes page tiles at load time, the reference at gather time.
    Returns (B, H, hd) in ``out_dtype`` (default q's dtype).  Inference
    only — no custom VJP; the kernel route consults the tuned-plan cache
    for KV-tile geometry (keyed on the POOL dtype).  ``work`` is
    :func:`decode_work` of the same lengths, table and window, built once
    for every layer of a step; the reference route ignores it.
    """
    out_dtype = q.dtype if out_dtype is None else out_dtype
    args = (q, k_pages, v_pages, table, lengths, k_scale, v_scale, layer,
            work)
    # "heads" is the op's single sharding contract: q heads and KV pools
    # device-local, output all-gathered back to full head width so the
    # (replicated) out-projection sees every head.  Inert unsharded.
    return _call(
        "decode_attention", *args,
        statics=dict(window=int(window), softcap=float(softcap),
                     accum_dtype=accum_dtype, out_dtype=out_dtype),
        policy=policy, tp="heads")


def decode_work(lengths: jax.Array, table: jax.Array, *, page: int,
                window: int = 0):
    """The decode kernel's work list for one step's ``lengths`` and
    ``table``: the live (row, KV tile) pairs its grid walks and the page
    ids each reads (``attention.ops.decode_work``)."""
    from .attention.ops import decode_work as _decode_work
    return _decode_work(lengths, table, page=page, window=window)


def decode_work_count(lengths, n_pages: int, page: int,
                      window: int = 0) -> int:
    """The items of :func:`decode_work`, counted on the host: the decode
    kernel's grid steps per kv head for these lengths."""
    from .attention.ops import decode_work_count as _count
    return _count(lengths, n_pages, page, window=window)


def prefill_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      table: jax.Array, starts: jax.Array,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None, *,
                      layer: Optional[jax.Array] = None,
                      window: int = 0, softcap: float = 0.0,
                      accum_dtype: Any = jnp.float32,
                      out_dtype: Any = None,
                      policy: PolicyLike = None) -> jax.Array:
    """Ragged multi-token prefill attention over a paged KV cache.

    q (B, C, H, hd) one chunk of C prompt tokens per slot (already written
    into the pools); table (B, n_pages) page ids; starts (B,) page-aligned
    chunk offsets — slot b's queries sit at positions ``starts[b] +
    [0, C)`` and attend causally over the cached history plus the chunk
    itself (padded tail positions are hidden by causality).  Returns
    (B, C, H, hd) in ``out_dtype`` (default q's dtype).  int8 pools pass
    ``k_scale`` / ``v_scale`` (P, Hkv) f32 scales, and a layer-stacked pool
    its ``layer``, like ``decode_attention``.
    Inference only — no custom VJP; the first op registered end-to-end
    through the registry (kernel, oracle, tune space, plan key: one
    ``OpSpec``).
    """
    out_dtype = q.dtype if out_dtype is None else out_dtype
    args = (q, k_pages, v_pages, table, starts, k_scale, v_scale, layer)
    return _call(
        "prefill_attention", *args,
        statics=dict(window=int(window), softcap=float(softcap),
                     accum_dtype=accum_dtype, out_dtype=out_dtype),
        policy=policy, tp="heads")


def quantized_matmul(x: jax.Array, w_q: jax.Array, w_scale: jax.Array, *,
                     policy: PolicyLike = None,
                     tp: Optional[str] = None) -> jax.Array:
    """Int8-weight matmul with per-output-channel dequant (§4.4 demotion).

    x: (..., K) floating activations; w_q: (K, N) int8 weights; w_scale:
    (N,) f32 per-channel scales (``core.quant.quantize_channelwise``
    layout).  The kernel folds the dequant into the MXU loop — int8
    weights widen in-register and the channel scale is applied ONCE at the
    K-flush (it factors out of the K contraction); the reference lowering
    dequantizes then einsums.  Returns x.shape[:-1] + (N,) f32.  Inference
    only — no custom VJP (the int8 weight is not differentiable).
    """
    return _call("quantized_matmul", x, w_q, w_scale, policy=policy, tp=tp)
