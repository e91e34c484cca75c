"""Core layers: norms, rotary embeddings, attention, MLPs, losses.

Paper tie-ins (DESIGN.md §2):
* blockwise attention = tiled accumulation interleaving (§2.1.2) applied to
  the softmax reduction — the running (max, denom, acc) triple is the
  "accumulation buffer", revisited once per KV tile;
* sliding windows = delay buffering (§2.2);
* all masks are branch-free `where` predication = condition flattening (§2.7);
* dtype policy application = type demotion (§4.4).

Every matmul/attention contraction in this module routes through
``repro.kernels.dispatch`` (the reference lowerings live there too), so
tuned Pallas plans reach the models end-to-end; ``AttnSpec.dispatch`` /
the ``policy`` arguments carry the ``ArchConfig.dispatch`` knob.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import quant
from ..core.memory import DtypePolicy
from ..kernels import dispatch

Params = Dict[str, jax.Array]


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size: Optional[int] = None,
               dtype=jnp.float32) -> jax.Array:
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def embed_init(key, shape, dtype=jnp.float32) -> jax.Array:
    return jax.random.normal(key, shape, dtype)


# --------------------------------------------------------------------------
# normalization
# --------------------------------------------------------------------------

def rmsnorm_init(d: int) -> Params:
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    """Variance in f32; the normalize/scale multiplies stay in the input
    dtype (type demotion §4.4) — this also keeps XLA from materializing a
    full-precision copy of the residual stream per layer."""
    dt = x.dtype
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(dt)
    return x * inv * (1.0 + p["scale"]).astype(dt)


# --------------------------------------------------------------------------
# rotary position embeddings (RoPE + M-RoPE)
# --------------------------------------------------------------------------

def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, *, theta: float = 1e4,
               mrope_sections: Tuple[int, ...] = ()) -> jax.Array:
    """x: (B, S, H, hd). positions: (B, S) int32, or (B, S, 3) for M-RoPE.

    M-RoPE (qwen2-vl): the hd/2 frequency slots are split into
    ``mrope_sections`` groups, each rotated by its own position stream
    (temporal / height / width).
    """
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)                       # (hd/2,)
    if mrope_sections:
        assert positions.ndim == 3 and positions.shape[-1] == len(
            mrope_sections)
        sec_ids = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.asarray(mrope_sections),
            total_repeat_length=hd // 2)                 # (hd/2,)
        # pos_per_freq[b, s, f] = positions[b, s, sec_ids[f]]
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),
            jnp.broadcast_to(sec_ids[None, None, :],
                             positions.shape[:2] + (hd // 2,)),
            axis=-1)                                     # (B, S, hd/2)
        angle = pos * freqs[None, None, :]
    else:
        angle = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,hd/2)
    sin = jnp.sin(angle)[:, :, None, :]
    cos = jnp.cos(angle)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int = 0              # 0 = global causal; >0 = sliding window
    rope_theta: float = 1e4
    mrope_sections: Tuple[int, ...] = ()
    qkv_bias: bool = False
    softcap: float = 0.0
    # kernel-routing policy ("kernels" | "reference" | "auto"), copied from
    # ArchConfig.dispatch by the model builder
    dispatch: str = "auto"
    # "" = float weight GEMMs (dispatch.matmul); "int8" = per-channel
    # quantized projections through dispatch.quantized_matmul (§4.4),
    # copied from ArchConfig.weights_dtype by the model builder
    weights_dtype: str = ""


def project(x: jax.Array, w: jax.Array, *, policy: str = "auto",
            weights_dtype: str = "", tp: Optional[str] = None) -> jax.Array:
    """Contract x (..., K) with w (K, ...) at the configured weight dtype.

    ``"int8"`` quantizes the weight per output channel and routes through
    ``dispatch.quantized_matmul`` (fused in-kernel dequant); under jit the
    quantization is constant-folded against the weight, so the GEMM itself
    streams int8 from HBM.  Anything else is a plain ``dispatch.matmul``.
    ``tp`` names the op's sharding contract ("col"/"row") — inert outside
    an active ``registry.tp_scope`` so model code stays mesh-agnostic.
    """
    if weights_dtype == "int8":
        k = w.shape[0]
        w_q, w_scale = quant.quantize_channelwise(w.reshape(k, -1))
        out = dispatch.quantized_matmul(x, w_q, w_scale, policy=policy,
                                        tp=tp)
        return out.reshape(x.shape[:-1] + w.shape[1:]).astype(x.dtype)
    return dispatch.matmul(x, w, policy=policy, tp=tp)


def attention_init(key, s: AttnSpec) -> Params:
    kq, kk, kv, ko, kb = jax.random.split(key, 5)
    p = {
        "wq": dense_init(kq, (s.d_model, s.n_heads, s.head_dim), s.d_model),
        "wk": dense_init(kk, (s.d_model, s.n_kv_heads, s.head_dim), s.d_model),
        "wv": dense_init(kv, (s.d_model, s.n_kv_heads, s.head_dim), s.d_model),
        "wo": dense_init(ko, (s.n_heads, s.head_dim, s.d_model),
                         s.n_heads * s.head_dim),
    }
    if s.qkv_bias:
        p["bq"] = jnp.zeros((s.n_heads, s.head_dim), jnp.float32)
        p["bk"] = jnp.zeros((s.n_kv_heads, s.head_dim), jnp.float32)
        p["bv"] = jnp.zeros((s.n_kv_heads, s.head_dim), jnp.float32)
    return p


def _qkv(p: Params, s: AttnSpec, x: jax.Array, positions: jax.Array,
         dt: DtypePolicy):
    cdt = dt.compute
    # (b,s,d) x (d,h,k) -> (b,s,h,k): dispatch contracts last-vs-first, so
    # the weight tensors pass through un-reshaped
    # q/k/v are column-parallel under tensor parallelism (heads device-
    # local; MQA pools replicate instead, which "col" degrades to cleanly)
    mm = functools.partial(project, policy=s.dispatch,
                           weights_dtype=s.weights_dtype, tp="col")
    q = mm(x, p["wq"].astype(cdt))
    k = mm(x, p["wk"].astype(cdt))
    v = mm(x, p["wv"].astype(cdt))
    if s.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = apply_rope(q, positions, theta=s.rope_theta,
                   mrope_sections=s.mrope_sections)
    k = apply_rope(k, positions, theta=s.rope_theta,
                   mrope_sections=s.mrope_sections)
    return q, k, v


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """GQA: (B,S,Hkv,hd) -> (B,S,H,hd) by group broadcast."""
    b, sq, hkv, hd = k.shape
    g = n_heads // hkv
    if g == 1:
        return k
    return jnp.broadcast_to(k[:, :, :, None, :], (b, sq, hkv, g, hd)) \
        .reshape(b, sq, n_heads, hd)


def _out_proj(p: Params, s: AttnSpec, out: jax.Array,
              dt: DtypePolicy) -> jax.Array:
    """(B, S, H, hd) -> (B, S, d) via wo (H, hd, d)."""
    b, sq = out.shape[:2]
    wo = p["wo"].astype(dt.compute)
    return project(
        out.reshape(b, sq, s.n_heads * s.head_dim),
        wo.reshape(s.n_heads * s.head_dim, s.d_model),
        policy=s.dispatch, weights_dtype=s.weights_dtype)


def attention_naive(p: Params, s: AttnSpec, x: jax.Array,
                    positions: jax.Array, dt: DtypePolicy) -> jax.Array:
    """T0/T1 reference: materializes the full (S, S) score tensor."""
    q, k, v = _qkv(p, s, x, positions, dt)
    k = _expand_kv(k, s.n_heads)
    v = _expand_kv(v, s.n_heads)
    out = dispatch.attention(
        q, k, v, causal=True, window=s.window, softcap=s.softcap,
        accum_dtype=dt.accum, out_dtype=dt.compute, impl="naive",
        policy=s.dispatch)
    return _out_proj(p, s, out, dt)


def attention_blockwise(p: Params, s: AttnSpec, x: jax.Array,
                        positions: jax.Array, dt: DtypePolicy, *,
                        block_q: int = 512, block_kv: int = 512,
                        unroll: bool = False, q_splits: int = 4,
                        hook=None) -> jax.Array:
    """Blockwise (flash-style) attention.

    The tiled XLA formulation itself (accumulation interleaving §2.1.2 on
    the softmax reduction, q un-blocked for SPMD sanity, ``q_splits``
    static causal quarters) lives in ``dispatch`` as the blockwise
    reference lowering; on the kernel route the same tiling runs as the
    Pallas flash kernel with tuned block geometry.  The ``hook(t, role)``
    lets the runtime constrain q/k/v shardings on either route.
    ``unroll=True`` (dry-run cost compiles) python-unrolls the KV scans so
    ``cost_analysis`` counts every tile with identical math/FLOPs.
    """
    del block_q  # q is not blocked in this formulation
    hook = hook or (lambda t, _role: t)
    q, k, v = _qkv(p, s, x, positions, dt)
    q = hook(q, "q")
    k = hook(k, "kv")
    v = hook(v, "kv")
    k = _expand_kv(k, s.n_heads)
    v = _expand_kv(v, s.n_heads)
    out = dispatch.attention(
        q, k, v, causal=True, window=s.window, softcap=s.softcap,
        accum_dtype=dt.accum, out_dtype=dt.compute, impl="blockwise",
        block_kv=block_kv, q_splits=q_splits, unroll=unroll,
        policy=s.dispatch)
    return _out_proj(p, s, out, dt)


def attention_decode(p: Params, s: AttnSpec, x: jax.Array, pos: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     dt: DtypePolicy,
                     positions_override: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a KV cache.

    x: (B, 1, d).  pos: scalar int32 current position (batch-uniform).
    caches: (B, C, Hkv, hd) where C = S_max (global) or window (rolling —
    the delay-buffer §2.2 layout: slot = pos mod window).
    Returns (out (B,1,d), k_cache, v_cache).
    """
    b = x.shape[0]
    cap = k_cache.shape[1]
    positions = (positions_override if positions_override is not None
                 else jnp.full((b, 1), pos, jnp.int32))
    q, k, v = _qkv(p, s, x, positions, dt)
    slot = pos % cap if s.window > 0 else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), slot, axis=1)

    kk = _expand_kv(k_cache.astype(dt.compute), s.n_heads)
    vv = _expand_kv(v_cache.astype(dt.compute), s.n_heads)
    idx = jnp.arange(cap)
    if s.window > 0:
        # rolling buffer: slot i holds absolute position
        #   pos - ((slot - i) mod cap)
        age = (slot - idx) % cap
        valid = (age >= 0) & (pos - age >= 0) & (age < s.window)
    else:
        valid = idx <= pos
    # the rolling-cache validity mask replaces causal/window, so this
    # always takes the dispatch reference route (no ragged-decode kernel)
    out = dispatch.attention(
        q, kk, vv, softcap=s.softcap, mask=valid[None, None, None, :],
        accum_dtype=dt.accum, out_dtype=dt.compute, impl="naive",
        policy=s.dispatch)
    out = _out_proj(p, s, out, dt)
    return out, k_cache, v_cache


def _at(layer, *idx):
    """Index of a paged-pool leaf: a layer-stacked ([L,] P, ...) leaf is
    addressed at ``layer`` first; an unstacked one (``layer`` None) as is."""
    return idx if layer is None else (layer,) + idx


def _rows_at(layer, pid, off, n_kv_heads: int):
    """Index of one token row per (slot, kv head) — page ``pid[b]``, row
    ``off[b]`` — with every pool dim but hd indexed by a scalar: the
    scatter then has hd alone as its window, so the compiler keeps the
    pool in the row-major layout the attention kernels read.  (Slicing the
    head axis instead, ``[pid, :, off]``, makes the TPU compiler move the
    head axis minor inside the step and copy the whole pool to and from
    that layout around every kernel call.)"""
    heads = jnp.arange(n_kv_heads)[None, :]
    return _at(layer, pid[:, None], heads, off[:, None])


def _layer_scales(k_scale, v_scale, layer):
    """One layer's (P, Hkv) int8 scales for the attention op: only the
    scales are sliced per layer (65 KB at a 4097-page pool), never a
    pool; the kernels prefetch them into SMEM, which cannot hold a stack."""
    if layer is None or k_scale is None:
        return k_scale, v_scale
    return k_scale[layer], v_scale[layer]


def paged_decode_view(lengths: jax.Array, table: jax.Array, page: int,
                      windows) -> Tuple[jax.Array, Dict[int, Any]]:
    """What every attention layer of one paged decode step shares, built
    once a step outside the layer scan: each row's KV length with its new
    token, and the decode kernel's work list (``dispatch.decode_work``)
    for each window in ``windows``.  A ride-along row, whose token lands
    on the trash page (physical page 0), attends to nothing: at KV length
    0 it is no work for the kernel and its (discarded) output is zero."""
    pid = table[jnp.arange(lengths.shape[0]), lengths // page]
    kv_len = jnp.where(pid > 0, lengths + 1, 0)
    return kv_len, {w: dispatch.decode_work(kv_len, table, page=page,
                                            window=w) for w in windows}


def attention_decode_paged(p: Params, s: AttnSpec, x: jax.Array,
                           lengths: jax.Array, table: jax.Array,
                           k_pages: jax.Array, v_pages: jax.Array,
                           dt: DtypePolicy,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           positions_override: Optional[jax.Array] = None,
                           layer: Optional[jax.Array] = None,
                           view: Optional[Tuple[jax.Array, Dict]] = None
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      Optional[jax.Array],
                                      Optional[jax.Array]]:
    """One-token ragged decode against the paged KV cache.

    x: (B, 1, d).  lengths: (B,) int32 tokens already cached per slot —
    the new token lands at position ``lengths[b]`` (the scheduler must
    have a page allocated there; inactive slots point at the trash page,
    and a row whose token lands there attends to nothing).
    table: (B, n_pages) int32 logical->physical page ids into the shared
    (P, Hkv, page, hd) pools.  int8 pools additionally carry ``k_scale`` /
    ``v_scale`` (P, Hkv) f32: the append runs the running-max requantize
    (``core.quant``) and the scales ride into the kernel's scalar-prefetch
    path.  With ``layer`` the pools (and scales) are the model's
    layer-stacked (L, P, Hkv, page, hd) leaves: the token rows are written
    in place at ``[layer, page, head, offset]`` and the op reads that layer
    out of the stack, so no layer's pool is ever copied.
    ``view`` is the step's :func:`paged_decode_view`; without it the
    layer builds its own.
    Returns (out (B,1,d), k_pages, v_pages, k_scale, v_scale).
    """
    b = x.shape[0]
    page = k_pages.shape[-2]
    positions = (positions_override if positions_override is not None
                 else lengths[:, None].astype(jnp.int32))
    q, k, v = _qkv(p, s, x, positions, dt)
    # memory banking (§4.3): the write lands in whatever physical page the
    # slot's table maps position lengths[b] to — no rectangle to reshape
    pid = table[jnp.arange(b), lengths // page]
    off = lengths % page
    if k_scale is not None:
        # quantize-on-write: gather the B target pages, append with the
        # running-max rescale, scatter pages + scales back (slots are
        # distinct; inactive slots all hit the never-read trash page)
        at = _at(layer, pid)
        pk, sk = quant.append_token_quantized(
            k_pages[at], k_scale[at], k[:, 0], off)
        pv, sv = quant.append_token_quantized(
            v_pages[at], v_scale[at], v[:, 0], off)
        k_pages = k_pages.at[at].set(pk)
        v_pages = v_pages.at[at].set(pv)
        k_scale = k_scale.at[at].set(sk)
        v_scale = v_scale.at[at].set(sv)
    else:
        # each slot's token row, across all kv heads
        at = _rows_at(layer, pid, off, k.shape[2])
        k_pages = k_pages.at[at].set(k[:, 0].astype(k_pages.dtype))
        v_pages = v_pages.at[at].set(v[:, 0].astype(v_pages.dtype))
    # GQA grouping happens inside the decode kernel/reference, so the
    # pools stay at Hkv heads end-to-end (no expanded copy in HBM)
    if view is None:
        view = paged_decode_view(lengths, table, page, (s.window,))
    kv_len, work = view
    out = dispatch.decode_attention(
        q[:, 0], k_pages, v_pages, table, kv_len,
        *_layer_scales(k_scale, v_scale, layer), layer=layer,
        window=s.window, softcap=s.softcap, accum_dtype=dt.accum,
        out_dtype=dt.compute, work=work[s.window], policy=s.dispatch)
    return (_out_proj(p, s, out[:, None], dt), k_pages, v_pages,
            k_scale, v_scale)


def attention_prefill_paged(p: Params, s: AttnSpec, x: jax.Array,
                            starts: jax.Array, tables: jax.Array,
                            k_pages: jax.Array, v_pages: jax.Array,
                            dt: DtypePolicy,
                            k_scale: Optional[jax.Array] = None,
                            v_scale: Optional[jax.Array] = None,
                            positions_override: Optional[jax.Array] = None,
                            layer: Optional[jax.Array] = None
                            ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                       Optional[jax.Array],
                                       Optional[jax.Array]]:
    """Chunked prefill: one page-aligned chunk each from B DISTINCT slots.

    x: (B, C, d) with C == page_size (each chunk fills exactly one page;
    the caller pads final partial chunks — padded positions are never
    read back because every later attention masks kpos >= length).
    starts: (B,) int32 page-aligned chunk offsets; tables: (B, n_pages)
    each slot's page ids.  Chunk b's queries sit at ``starts[b] + [0, C)``
    and attend causally over that slot's cached history plus the chunk
    itself.  Slots must be distinct (each chunk writes its own physical
    page).  int8 pools carry ``k_scale`` / ``v_scale`` (P, Hkv) f32: a
    whole-page write gets a clean abs-max scale (``quant.quantize_pages``).
    With ``layer`` the pools are layer-stacked, as in
    ``attention_decode_paged``: each chunk's page is written in place at
    ``[layer, page]``.
    Returns (out (B,C,d), k_pages, v_pages, k_scale, v_scale).
    """
    b, c, _ = x.shape
    page = k_pages.shape[-2]
    positions = (positions_override if positions_override is not None
                 else (starts[:, None] + jnp.arange(c)[None, :]
                       ).astype(jnp.int32))
    q, k, v = _qkv(p, s, x, positions, dt)
    at = _at(layer, tables[jnp.arange(b), starts // page])
    # (B, C=page, Hkv, hd) -> the pools' head-major (B, Hkv, page, hd)
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    if k_scale is not None:
        pk, sk = quant.quantize_pages(kt)
        pv, sv = quant.quantize_pages(vt)
        k_pages = k_pages.at[at].set(pk)
        v_pages = v_pages.at[at].set(pv)
        k_scale = k_scale.at[at].set(sk)
        v_scale = v_scale.at[at].set(sv)
    else:
        k_pages = k_pages.at[at].set(kt.astype(k_pages.dtype))
        v_pages = v_pages.at[at].set(vt.astype(v_pages.dtype))
    # multi-token ragged prefill through dispatch: each chunk's queries
    # attend causally over the cached history plus the chunk itself (just
    # written into its page); GQA grouping happens inside the kernel /
    # reference, so the pools stay at Hkv heads end-to-end
    out = dispatch.prefill_attention(
        q, k_pages, v_pages, tables, starts,
        *_layer_scales(k_scale, v_scale, layer), layer=layer,
        window=s.window, softcap=s.softcap, accum_dtype=dt.accum,
        out_dtype=dt.compute, policy=s.dispatch)
    return _out_proj(p, s, out, dt), k_pages, v_pages, k_scale, v_scale


def attention_verify_paged(p: Params, s: AttnSpec, x: jax.Array,
                           lengths: jax.Array, table: jax.Array,
                           k_pages: jax.Array, v_pages: jax.Array,
                           dt: DtypePolicy,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           positions_override: Optional[jax.Array] = None,
                           layer: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      Optional[jax.Array],
                                      Optional[jax.Array]]:
    """Speculative verify: score W candidate tokens per slot in one pass.

    x: (B, W, d) — slot b's candidate tokens occupy positions
    ``lengths[b] + [0, W)``, which are NOT page-aligned (a draft window
    starts wherever decode left off).  The whole-page write of
    ``attention_prefill_paged`` is therefore unusable here; instead the
    candidates append token-by-token exactly like the decode path (W is a
    static python loop — W is small, typically <= 5).  Appends may span a
    page boundary; the scheduler guarantees pages exist for the full
    window.  The ragged ``prefill_attention`` op then scores all W
    queries causally against history + the window itself — its mask is
    pure position arithmetic (kpos <= qpos), so mid-page ``starts`` are
    legal on kernel and reference routes alike.  Rejected drafts are
    rolled back by the HOST truncating ``lengths``; their stale K/V
    payload (and any int8 running-max scale growth) stays in the pool,
    masked off by every later ``kpos < length`` read.  With ``layer`` the
    pools are layer-stacked, as in ``attention_decode_paged``.
    Returns (out (B,W,d), k_pages, v_pages, k_scale, v_scale).
    """
    b, w, _ = x.shape
    page = k_pages.shape[-2]
    positions = (positions_override if positions_override is not None
                 else (lengths[:, None] + jnp.arange(w)[None, :]
                       ).astype(jnp.int32))
    q, k, v = _qkv(p, s, x, positions, dt)
    n_logical = table.shape[1]
    for t in range(w):
        pos = lengths + t
        # Fixed-width windows mean padded rows can step past a slot's last
        # logical page (e.g. a slot one token from max_len).  Gather would
        # silently clamp the index into the slot's LAST real page; redirect
        # those writes to trash page 0 instead.
        idx = pos // page
        pid = jnp.where(idx < n_logical,
                        table[jnp.arange(b), jnp.minimum(idx, n_logical - 1)],
                        0)
        off = pos % page
        if k_scale is not None:
            at = _at(layer, pid)
            pk, sk = quant.append_token_quantized(
                k_pages[at], k_scale[at], k[:, t], off)
            pv, sv = quant.append_token_quantized(
                v_pages[at], v_scale[at], v[:, t], off)
            k_pages = k_pages.at[at].set(pk)
            v_pages = v_pages.at[at].set(pv)
            k_scale = k_scale.at[at].set(sk)
            v_scale = v_scale.at[at].set(sv)
        else:
            at = _rows_at(layer, pid, off, k.shape[2])
            k_pages = k_pages.at[at].set(k[:, t].astype(k_pages.dtype))
            v_pages = v_pages.at[at].set(v[:, t].astype(v_pages.dtype))
    out = dispatch.prefill_attention(
        q, k_pages, v_pages, table, lengths,
        *_layer_scales(k_scale, v_scale, layer), layer=layer,
        window=s.window, softcap=s.softcap, accum_dtype=dt.accum,
        out_dtype=dt.compute, policy=s.dispatch)
    return _out_proj(p, s, out, dt), k_pages, v_pages, k_scale, v_scale


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def mlp_init(key, d: int, ff: int, activation: str) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if activation in ("swiglu", "geglu"):
        return {"wg": dense_init(k1, (d, ff)),
                "wu": dense_init(k2, (d, ff)),
                "wd": dense_init(k3, (ff, d))}
    return {"wi": dense_init(k1, (d, ff)), "wd": dense_init(k2, (ff, d))}


def mlp_apply(p: Params, x: jax.Array, activation: str,
              dt: DtypePolicy, *, policy: str = "auto",
              weights_dtype: str = "") -> jax.Array:
    cdt = dt.compute
    # Megatron split: up-projections column-parallel (no collective), the
    # down-projection row-parallel (its psum is the block's one all-reduce)
    mm = functools.partial(project, policy=policy,
                           weights_dtype=weights_dtype, tp="col")
    mm_down = functools.partial(project, policy=policy,
                                weights_dtype=weights_dtype, tp="row")
    if activation in ("swiglu", "geglu"):
        g = mm(x, p["wg"].astype(cdt))
        u = mm(x, p["wu"].astype(cdt))
        act = jax.nn.silu(g) if activation == "swiglu" \
            else jax.nn.gelu(g, approximate=True)
        return mm_down(act * u, p["wd"].astype(cdt))
    h = mm(x, p["wi"].astype(cdt))
    h = jax.nn.relu(h) if activation == "relu" \
        else jax.nn.gelu(h, approximate=True)
    return mm_down(h, p["wd"].astype(cdt))


# --------------------------------------------------------------------------
# vocab-parallel cross entropy
# --------------------------------------------------------------------------

def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy.  logits (..., V) f32; labels (...) int32.

    Written max/sum-first so GSPMD turns the vocab reductions into psums
    when V is sharded over the `model` axis (vocab-parallel loss) without
    ever gathering the full logits on one device (striping §4.3).
    """
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    label_logit = jnp.take_along_axis(
        shifted, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - label_logit)


def chunked_xent(x: jax.Array, head: jax.Array, labels: jax.Array, *,
                 n_chunks: int, unroll: bool, remat: bool = True,
                 policy: str = "auto") -> jax.Array:
    """Head matmul + cross entropy, tiled over the sequence (§3.4 tiling).

    The (B, S, V) logits tensor of a 256k-vocab model is the largest
    activation in training by an order of magnitude; computing it one
    sequence-tile at a time (and rematerializing in the backward pass)
    keeps only (B, S/n_chunks, V) alive — the same transformation the
    paper applies to fit on-chip buffers.  x: (B, S, d) post-final-norm.
    """
    b, sq, d = x.shape
    while n_chunks > 1 and sq % n_chunks != 0:
        n_chunks //= 2
    c = sq // n_chunks
    xc = jnp.moveaxis(x.reshape(b, n_chunks, c, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n_chunks, c), 1, 0)

    def chunk(x_c, l_c):
        logits = dispatch.matmul(x_c, head, policy=policy) \
            .astype(jnp.float32)
        m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
        shifted = logits - m
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        label_logit = jnp.take_along_axis(
            shifted, l_c[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - label_logit)

    if remat:
        chunk = jax.checkpoint(chunk)

    if unroll or n_chunks == 1:
        total = jnp.zeros((), jnp.float32)
        for i in range(n_chunks):
            total = total + chunk(xc[i], lc[i])
    else:
        def body(tot, args):
            return tot + chunk(*args), None
        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (b * sq)
