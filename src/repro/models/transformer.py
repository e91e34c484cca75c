"""The composable decoder LM: one implementation, ten architectures.

Layer stacking strategy (paper §2.5 loop flattening): the stack is split into
``prefix`` (unrolled), ``n_periods`` repetitions of the architecture's layer
*pattern* executed under one ``jax.lax.scan`` (compact HLO, one pipeline), and
``tail`` (unrolled remainder).  The scan body holds a whole pattern period so
heterogeneous stacks (gemma3's 5 local : 1 global, recurrentgemma's
2 recurrent : 1 attention) keep their true interleaving.

Execution modes (used by the dry-run; see DESIGN.md §6):
  run  — scanned layers, scanned attention tiles (the real thing)
  mem  — like run; used for the full-depth memory-proof compile
  cost — python-unrolled everything so ``cost_analysis`` counts every tile
         exactly once per execution (XLA does not multiply scan bodies by
         trip count); used on layer-truncated configs only.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, LayerKind
from ..core.memory import BF16_POLICY, DtypePolicy
from . import griffin, layers, moe, moe_sharded, rwkv
from .layers import Params


@dataclasses.dataclass(frozen=True)
class ExecOptions:
    mode: str = "run"              # run | mem | cost
    block_q: int = 512
    block_kv: int = 512
    remat: bool = True
    # "full" = nothing_saveable (recompute everything);
    # "dots" = dots_with_no_batch_dims_saveable (save matmul outputs —
    # trades saved-activation residency against recompute HBM traffic)
    remat_policy: str = "full"
    attn_impl: str = "blockwise"   # blockwise | naive
    # residual-stream sharding constraint (Megatron-SP striping §4.3);
    # injected by the runtime so models stay mesh-agnostic.
    constrain: Optional[Any] = None
    # MoE dispatch-buffer constraint hook (EP striping §4.3)
    moe_constrain: Optional[Any] = None
    # q/k/v sharding hook (SP->TP transition at attention entry)
    attn_constrain: Optional[Any] = None
    # sequence tiles for the head-matmul + xent (§3.4)
    xent_chunks: int = 8
    # expert-parallel MoE: mesh + data axes enable the shard_map all-to-all
    # path (moe_sharded); expert count pads to expert_pad (EP axis size)
    moe_mesh: Optional[Any] = None
    moe_dp_axes: Tuple[str, ...] = ()
    moe_ep_axes: Tuple[str, ...] = ("model",)
    expert_pad: int = 1

    @property
    def unroll_inner(self) -> bool:
        return self.mode == "cost"

    @property
    def scan_layers(self) -> bool:
        return self.mode != "cost"


@dataclasses.dataclass(frozen=True)
class Layout:
    prefix: Tuple[LayerKind, ...]
    period: Tuple[LayerKind, ...]
    n_periods: int
    tail: Tuple[LayerKind, ...]


def make_layout(cfg: ArchConfig) -> Layout:
    kinds = cfg.layer_kinds()
    pre = tuple(cfg.prefix)
    rest = kinds[len(pre):]
    if cfg.pattern and len(rest) >= len(cfg.pattern):
        p = len(cfg.pattern)
        n_periods = len(rest) // p
        tail = rest[n_periods * p:]
        return Layout(pre, tuple(cfg.pattern), n_periods, tail)
    return Layout(kinds, (), 0, ())


# --------------------------------------------------------------------------
# per-layer specs
# --------------------------------------------------------------------------

def _attn_spec(cfg: ArchConfig, mixer: str) -> layers.AttnSpec:
    return layers.AttnSpec(
        d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        window=cfg.window if mixer == "swa" else 0,
        rope_theta=cfg.rope_theta, mrope_sections=cfg.mrope_sections,
        qkv_bias=cfg.qkv_bias, dispatch=cfg.dispatch,
        weights_dtype=cfg.weights_dtype)


def _moe_spec(cfg: ArchConfig, pad_to: int = 1) -> moe.MoESpec:
    return moe.MoESpec(
        d_model=cfg.d_model, n_experts=cfg.n_experts, top_k=cfg.top_k,
        d_expert=cfg.d_expert, n_shared_experts=cfg.n_shared_experts,
        shared_d_expert=cfg.shared_d_expert,
        capacity_factor=cfg.capacity_factor, activation=cfg.activation,
        pad_to=pad_to, dispatch=cfg.dispatch)


def _rwkv_spec(cfg: ArchConfig) -> rwkv.RwkvSpec:
    return rwkv.RwkvSpec(d_model=cfg.d_model, head_dim=cfg.rwkv_head_dim,
                         chunk=cfg.rwkv_chunk, d_ff=cfg.d_ff,
                         intra=cfg.rwkv_intra)


def _griffin_spec(cfg: ArchConfig) -> griffin.GriffinSpec:
    return griffin.GriffinSpec(
        d_model=cfg.d_model, lru_width=cfg.lru_width or cfg.d_model,
        conv_width=cfg.conv_width,
        block_width=min(256, cfg.lru_width or cfg.d_model))


# --------------------------------------------------------------------------
# layer init / apply / decode
# --------------------------------------------------------------------------

def layer_init(key, cfg: ArchConfig, kind: LayerKind,
               expert_pad: int = 1) -> Params:
    mixer, ffn = kind
    k1, k2 = jax.random.split(key)
    p: Params = {"ln1": layers.rmsnorm_init(cfg.d_model),
                 "ln2": layers.rmsnorm_init(cfg.d_model)}
    if mixer in ("attn", "swa"):
        p["attn"] = layers.attention_init(k1, _attn_spec(cfg, mixer))
    elif mixer == "rwkv":
        p["tm"] = rwkv.time_mix_init(k1, _rwkv_spec(cfg))
    elif mixer == "rglru":
        p["rec"] = griffin.rglru_block_init(k1, _griffin_spec(cfg))
    else:
        raise ValueError(mixer)
    if ffn == "mlp":
        p["mlp"] = layers.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.activation)
    elif ffn == "moe":
        p["moe"] = moe.moe_init(k2, _moe_spec(cfg, expert_pad))
    elif ffn == "rwkv_cm":
        p["cm"] = rwkv.channel_mix_init(k2, _rwkv_spec(cfg))
    else:
        raise ValueError(ffn)
    return p


def layer_apply(p: Params, cfg: ArchConfig, kind: LayerKind, x: jax.Array,
                positions: jax.Array, dt: DtypePolicy,
                opts: ExecOptions) -> Tuple[jax.Array, jax.Array]:
    mixer, ffn = kind
    aux = jnp.zeros((), jnp.float32)
    # residual-stream constraints are applied to the BRANCH outputs inside
    # the remat boundary (never to the carry): resharding the scan carry
    # makes XLA save an extra full-precision activation stack per layer.
    con = opts.constrain or (lambda t: t)
    h = layers.rmsnorm(p["ln1"], x)
    if mixer in ("attn", "swa"):
        spec = _attn_spec(cfg, mixer)
        if opts.attn_impl == "naive":
            h = layers.attention_naive(p["attn"], spec, h, positions, dt)
        else:
            h = layers.attention_blockwise(
                p["attn"], spec, h, positions, dt,
                block_q=opts.block_q, block_kv=opts.block_kv,
                unroll=opts.unroll_inner, hook=opts.attn_constrain)
    elif mixer == "rwkv":
        h = rwkv.time_mix_apply(p["tm"], _rwkv_spec(cfg), h, dt,
                                unroll=opts.unroll_inner,
                                hook=opts.attn_constrain)
    elif mixer == "rglru":
        h = griffin.rglru_block_apply(p["rec"], _griffin_spec(cfg), h, dt)
    x = x + con(h)
    h = layers.rmsnorm(p["ln2"], x)
    if ffn == "mlp":
        h = layers.mlp_apply(p["mlp"], h, cfg.activation, dt,
                             policy=cfg.dispatch,
                             weights_dtype=cfg.weights_dtype)
    elif ffn == "moe":
        spec = _moe_spec(cfg, opts.expert_pad)
        if opts.moe_mesh is not None:
            h, aux = moe_sharded.moe_apply_sharded(
                p["moe"], spec, h, dt, mesh=opts.moe_mesh,
                dp_axes=opts.moe_dp_axes, ep_axes=opts.moe_ep_axes)
        else:
            h, aux = moe.moe_apply(p["moe"], spec, h, dt,
                                   hook=opts.moe_constrain)
    elif ffn == "rwkv_cm":
        h = rwkv.channel_mix_apply(p["cm"], _rwkv_spec(cfg), h, dt)
    return x + con(h), aux


def layer_cache_init(cfg: ArchConfig, kind: LayerKind, batch: int,
                     max_len: int, dtype) -> Dict[str, Any]:
    mixer, ffn = kind
    cache: Dict[str, Any] = {}
    if mixer in ("attn", "swa"):
        cap = min(cfg.window, max_len) if mixer == "swa" else max_len
        cache["k"] = jnp.zeros((batch, cap, cfg.n_kv_heads, cfg.head_dim),
                               dtype)
        cache["v"] = jnp.zeros((batch, cap, cfg.n_kv_heads, cfg.head_dim),
                               dtype)
    elif mixer == "rwkv":
        cache.update(rwkv.rwkv_cache_init(batch, _rwkv_spec(cfg), dtype))
    elif mixer == "rglru":
        cache.update(griffin.griffin_cache_init(batch, _griffin_spec(cfg),
                                                dtype))
    if ffn == "rwkv_cm" and "cm_xprev" not in cache:
        cache["cm_xprev"] = jnp.zeros((batch, cfg.d_model), dtype)
    return cache


def layer_decode(p: Params, cfg: ArchConfig, kind: LayerKind, x: jax.Array,
                 cache: Dict[str, Any], pos: jax.Array, dt: DtypePolicy,
                 positions_override=None,
                 opts: Optional[ExecOptions] = None,
                 paged: Optional[Tuple[jax.Array, jax.Array]] = None,
                 layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode token through one layer.  ``paged`` = (lengths, table,
    view), ``view`` the step's ``layers.paged_decode_view``, switches
    attention layers to the paged-KV ragged path (``pos`` is then
    ignored — each slot decodes at its own length); recurrent mixers and
    FFNs are cache-layout-agnostic and run unchanged either way.
    ``layer`` marks the page pools as the layer-stacked leaves of the
    scanned stack (see ``_layer_step``)."""
    mixer, ffn = kind
    new_cache = dict(cache)
    h = layers.rmsnorm(p["ln1"], x)
    if mixer in ("attn", "swa"):
        spec = _attn_spec(cfg, mixer)
        if paged is not None:
            lengths, table, view = paged
            h, kp, vp, ks, vs = layers.attention_decode_paged(
                p["attn"], spec, h, lengths, table,
                cache["k_pages"], cache["v_pages"], dt,
                cache.get("k_scale"), cache.get("v_scale"),
                positions_override=positions_override, layer=layer,
                view=view)
            new_cache["k_pages"], new_cache["v_pages"] = kp, vp
            if ks is not None:
                new_cache["k_scale"], new_cache["v_scale"] = ks, vs
        else:
            h, new_cache["k"], new_cache["v"] = layers.attention_decode(
                p["attn"], spec, h, pos, cache["k"], cache["v"], dt,
                positions_override=positions_override)
    elif mixer == "rwkv":
        h, tm_cache = rwkv.time_mix_decode(p["tm"], _rwkv_spec(cfg), h,
                                           cache, dt)
        new_cache.update(tm_cache)
    elif mixer == "rglru":
        h, rec_cache = griffin.rglru_block_decode(
            p["rec"], _griffin_spec(cfg), h, cache, dt)
        new_cache.update(rec_cache)
    x = x + h
    h = layers.rmsnorm(p["ln2"], x)
    if ffn == "mlp":
        h = layers.mlp_apply(p["mlp"], h, cfg.activation, dt,
                             policy=cfg.dispatch,
                             weights_dtype=cfg.weights_dtype)
    elif ffn == "moe":
        spec = _moe_spec(cfg, opts.expert_pad if opts else 1)
        if opts is not None and opts.moe_mesh is not None:
            h, _ = moe_sharded.moe_apply_sharded(
                p["moe"], spec, h, dt, mesh=opts.moe_mesh,
                dp_axes=opts.moe_dp_axes, ep_axes=opts.moe_ep_axes)
        else:
            h, _ = moe.moe_apply(p["moe"], spec, h, dt)
    elif ffn == "rwkv_cm":
        h = rwkv.channel_mix_apply(p["cm"], _rwkv_spec(cfg), h, dt,
                                   x_prev=cache["cm_xprev"])
        new_cache["cm_xprev"] = x[:, 0].astype(cache["cm_xprev"].dtype)
    return x + h, new_cache


def layer_cache_init_paged(cfg: ArchConfig, kind: LayerKind, slots: int,
                           total_pages: int, page_size: int,
                           dtype) -> Dict[str, Any]:
    """Paged twin of ``layer_cache_init``: attention layers get shared
    (P, Hkv, page, hd) page pools instead of per-slot rectangles;
    recurrent state stays per-slot (it is O(1) per sequence already)."""
    mixer, ffn = kind
    cache: Dict[str, Any] = {}
    if mixer in ("attn", "swa"):
        # head-major: one kv head's page is a contiguous (page, hd) tile,
        # the block the paged kernels DMA (TPU tiling wants the two minor
        # block dims to be (page, hd), never a size-1 head slice)
        shape = (total_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
        cache["k_pages"] = jnp.zeros(shape, dtype)
        cache["v_pages"] = jnp.zeros(shape, dtype)
        if jnp.dtype(dtype) == jnp.int8:
            # per-(page, kv-head) f32 scales ride next to the pools; a
            # zero scale marks a clean page (the running-max append wipes
            # any stale payload on first write — see core.quant)
            cache["k_scale"] = jnp.zeros((total_pages, cfg.n_kv_heads),
                                         jnp.float32)
            cache["v_scale"] = jnp.zeros((total_pages, cfg.n_kv_heads),
                                         jnp.float32)
    elif mixer == "rwkv":
        cache.update(rwkv.rwkv_cache_init(slots, _rwkv_spec(cfg),
                                          _state_dtype(dtype)))
    elif mixer == "rglru":
        cache.update(griffin.griffin_cache_init(slots, _griffin_spec(cfg),
                                                _state_dtype(dtype)))
    if ffn == "rwkv_cm" and "cm_xprev" not in cache:
        cache["cm_xprev"] = jnp.zeros((slots, cfg.d_model),
                                      _state_dtype(dtype))
    return cache


def _state_dtype(pool_dtype):
    """Recurrent carried state never quantizes — int8 pools keep bf16
    state (paged serving requires attention-only stacks anyway, see
    ``paged_supported``)."""
    return jnp.bfloat16 if jnp.dtype(pool_dtype) == jnp.int8 else pool_dtype


def layer_prefill_paged(p: Params, cfg: ArchConfig, kind: LayerKind,
                        x: jax.Array, cache: Dict[str, Any],
                        starts: jax.Array, tables: jax.Array,
                        dt: DtypePolicy, positions_override=None,
                        opts: Optional[ExecOptions] = None,
                        layer: Optional[jax.Array] = None
                        ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One page-aligned prompt chunk each of B distinct slots through one
    layer (x (B, C, d), starts (B,), tables (B, n_pages)).

    Only attention mixers support chunked prefill (recurrent mixers would
    need a carried-state sequence scan — the serve scheduler falls back to
    token-by-token prefill for those archs, see ``paged_supported``).
    """
    mixer, ffn = kind
    new_cache = dict(cache)
    h = layers.rmsnorm(p["ln1"], x)
    if mixer in ("attn", "swa"):
        spec = _attn_spec(cfg, mixer)
        h, kp, vp, ks, vs = layers.attention_prefill_paged(
            p["attn"], spec, h, starts, tables,
            cache["k_pages"], cache["v_pages"], dt,
            cache.get("k_scale"), cache.get("v_scale"),
            positions_override=positions_override, layer=layer)
        new_cache["k_pages"], new_cache["v_pages"] = kp, vp
        if ks is not None:
            new_cache["k_scale"], new_cache["v_scale"] = ks, vs
    else:
        raise ValueError(
            f"paged chunked prefill requires attention mixers, got {mixer}")
    x = x + h
    h = layers.rmsnorm(p["ln2"], x)
    if ffn == "mlp":
        h = layers.mlp_apply(p["mlp"], h, cfg.activation, dt,
                             policy=cfg.dispatch,
                             weights_dtype=cfg.weights_dtype)
    elif ffn == "moe":
        spec = _moe_spec(cfg, opts.expert_pad if opts else 1)
        h, _ = moe.moe_apply(p["moe"], spec, h, dt)
    else:
        raise ValueError(
            f"paged chunked prefill requires stateless FFNs, got {ffn}")
    return x + h, new_cache


def layer_verify_paged(p: Params, cfg: ArchConfig, kind: LayerKind,
                       x: jax.Array, cache: Dict[str, Any],
                       lengths: jax.Array, tables: jax.Array,
                       dt: DtypePolicy, positions_override=None,
                       opts: Optional[ExecOptions] = None,
                       layer: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One speculative verify window of B distinct slots through one layer
    (x (B, W, d), lengths (B,), tables (B, n_pages)).  Same structural
    constraints as chunked prefill (attention mixers, stateless FFNs) —
    ``paged_supported`` gates both."""
    mixer, ffn = kind
    new_cache = dict(cache)
    h = layers.rmsnorm(p["ln1"], x)
    if mixer in ("attn", "swa"):
        spec = _attn_spec(cfg, mixer)
        h, kp, vp, ks, vs = layers.attention_verify_paged(
            p["attn"], spec, h, lengths, tables,
            cache["k_pages"], cache["v_pages"], dt,
            cache.get("k_scale"), cache.get("v_scale"),
            positions_override=positions_override, layer=layer)
        new_cache["k_pages"], new_cache["v_pages"] = kp, vp
        if ks is not None:
            new_cache["k_scale"], new_cache["v_scale"] = ks, vs
    else:
        raise ValueError(
            f"speculative verify requires attention mixers, got {mixer}")
    x = x + h
    h = layers.rmsnorm(p["ln2"], x)
    if ffn == "mlp":
        h = layers.mlp_apply(p["mlp"], h, cfg.activation, dt,
                             policy=cfg.dispatch,
                             weights_dtype=cfg.weights_dtype)
    elif ffn == "moe":
        spec = _moe_spec(cfg, opts.expert_pad if opts else 1)
        h, _ = moe.moe_apply(p["moe"], spec, h, dt)
    else:
        raise ValueError(
            f"speculative verify requires stateless FFNs, got {ffn}")
    return x + h, new_cache


# paged-pool leaves stay layer-stacked through the scanned stack and are
# written in place at the layer index; every other cache leaf (dense KV
# rectangles, recurrent state) is one layer's slice, written back after
_POOL_LEAVES = frozenset(("k_pages", "v_pages", "k_scale", "v_scale"))


def _layer_step(fn, cache: Dict[str, Any], layer):
    """Run ``fn(layer_cache) -> (x, new_layer_cache)`` for one layer of a
    stacked cache dict and return (x, the updated stacked dict).

    The stacked cache is the layer scan's carry, so the in-place writes
    land in the donated buffer: pools pass whole (``fn`` addresses them
    at ``layer``), other leaves are sliced and updated at ``layer``."""
    local = {k: a if k in _POOL_LEAVES else a[layer]
             for k, a in cache.items()}
    x, new = fn(local)
    return x, {k: new[k] if k in _POOL_LEAVES else
               jax.lax.dynamic_update_index_in_dim(a, new[k], layer, 0)
               for k, a in cache.items()}


def paged_supported(cfg: ArchConfig) -> bool:
    """Can this arch serve from a paged KV cache?  Requires every mixer to
    be attention-family and every FFN stateless (chunked prefill has no
    carried-state scan for recurrent layers)."""
    return all(m in ("attn", "swa") and f in ("mlp", "moe")
               for m, f in cfg.layer_kinds())


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ArchConfig, dt: DtypePolicy = BF16_POLICY,
                 opts: ExecOptions = ExecOptions()):
        self.cfg = cfg
        self.dt = dt
        self.opts = opts
        self.layout = make_layout(cfg)

    # ------------------------------ init ------------------------------
    def init(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        lay = self.layout
        pdt = self.dt.param
        ke, kh = jax.random.split(jax.random.fold_in(rng, 0))
        params: Params = {
            "embed": layers.embed_init(
                ke, (cfg.vocab_size, cfg.d_model)).astype(pdt),
            "final_norm": layers.rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["head"] = layers.dense_init(
                kh, (cfg.d_model, cfg.vocab_size), cfg.d_model).astype(pdt)

        def cast(p):
            return jax.tree.map(lambda a: a.astype(pdt), p)

        li = 0
        prefix = []
        for kind in lay.prefix:
            prefix.append(cast(layer_init(
                jax.random.fold_in(rng, 1000 + li), cfg, kind,
                self.opts.expert_pad)))
            li += 1
        params["prefix"] = prefix
        stack = []
        if lay.n_periods:
            for j, kind in enumerate(lay.period):
                idxs = jnp.arange(lay.n_periods) * len(lay.period) \
                    + (1000 + li + j)

                def init_one(i):
                    return cast(layer_init(jax.random.fold_in(rng, i),
                                           cfg, kind,
                                           self.opts.expert_pad))
                stack.append(jax.vmap(init_one)(idxs))
            li += lay.n_periods * len(lay.period)
        params["stack"] = stack
        tail = []
        for kind in lay.tail:
            tail.append(cast(layer_init(
                jax.random.fold_in(rng, 1000 + li), cfg, kind,
                self.opts.expert_pad)))
            li += 1
        params["tail"] = tail
        return params

    def param_specs(self) -> Params:
        return jax.eval_shape(self.init, jax.random.key(0))

    # ------------------------------ forward ---------------------------
    def _embed(self, params: Params, batch: Dict[str, jax.Array]):
        cfg, dt = self.cfg, self.dt
        if cfg.input_mode == "embeddings":
            x = batch["embeddings"].astype(dt.compute)
        else:
            x = params["embed"].astype(dt.compute)[batch["tokens"]]
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, dt.compute)
        return x

    def _positions(self, batch, b, s, offset=0):
        if self.cfg.mrope_sections:
            return batch["positions"]
        return jnp.broadcast_to(jnp.arange(offset, offset + s)[None, :],
                                (b, s)).astype(jnp.int32)

    def _logits(self, params: Params, x: jax.Array) -> jax.Array:
        from ..kernels import dispatch
        x = layers.rmsnorm(params["final_norm"], x)
        head = params["embed"].T if self.cfg.tie_embeddings \
            else params["head"]
        return dispatch.matmul(x, head.astype(self.dt.compute),
                               policy=self.cfg.dispatch)

    def _run_stack(self, params: Params, x: jax.Array,
                   positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
        cfg, dt, opts, lay = self.cfg, self.dt, self.opts, self.layout
        aux_total = jnp.zeros((), jnp.float32)
        con = opts.constrain or (lambda t: t)
        x = con(x)

        def one(p, kind, x):
            base = functools.partial(layer_apply, cfg=cfg, kind=kind,
                                     positions=positions, dt=dt, opts=opts)
            if opts.remat:
                policy = (jax.checkpoint_policies.nothing_saveable
                          if opts.remat_policy == "full" else
                          jax.checkpoint_policies
                          .dots_with_no_batch_dims_saveable)
                fn = jax.checkpoint(
                    lambda p_, x_: base(p_, x=x_), policy=policy)
                return fn(p, x)
            return base(p, x=x)

        for p, kind in zip(params["prefix"], lay.prefix):
            x, aux = one(p, kind, x)
            aux_total += aux

        if lay.n_periods:
            if opts.scan_layers:
                def body(carry, period_params):
                    x, aux_c = carry
                    for j, kind in enumerate(lay.period):
                        x, aux = one(period_params[j], kind, x)
                        aux_c += aux
                    return (x, aux_c), None

                (x, aux_total), _ = jax.lax.scan(
                    body, (x, aux_total), tuple(params["stack"]))
            else:
                for i in range(lay.n_periods):
                    sl = jax.tree.map(lambda a: a[i], tuple(params["stack"]))
                    for j, kind in enumerate(lay.period):
                        x, aux = one(sl[j], kind, x)
                        aux_total += aux

        for p, kind in zip(params["tail"], lay.tail):
            x, aux = one(p, kind, x)
            aux_total += aux
        return x, aux_total

    def _cached_stack(self, one, x, stack_params, stack_cache):
        """The scanned stack of a decode-time step: ``one(p, kind, x,
        layer_cache, layer=) -> (x, new_layer_cache)`` for every layer of
        every period.  Returns (x, the new stacked cache list).

        The stacked cache is the **carry** of the layer scan (the weights
        and the period index are its xs), never xs/ys: a scan that takes
        the cache as xs copies each layer's pool out of the stack and back
        into a fresh ys buffer every step.  Carried, the paged pools are
        written in place at the layer index (``_layer_step``).  Cost mode
        unrolls the same body in a python loop."""
        period, n = self.layout.period, self.layout.n_periods

        def body(carry, pp, layer):
            x, cs = carry
            cs = list(cs)
            for j, kind in enumerate(period):
                x, cs[j] = _layer_step(
                    lambda c: one(pp[j], kind, x, c, layer=layer),
                    cs[j], layer)
            return x, tuple(cs)

        carry = (x, tuple(stack_cache))
        if self.opts.scan_layers:
            carry, _ = jax.lax.scan(
                lambda c, xs: (body(c, *xs), None), carry,
                (tuple(stack_params), jnp.arange(n)))
        else:
            for i in range(n):
                carry = body(
                    carry, jax.tree.map(lambda a: a[i], tuple(stack_params)),
                    i)
        x, cs = carry
        return x, list(cs)

    def _head(self, params: Params) -> jax.Array:
        head = params["embed"].T if self.cfg.tie_embeddings \
            else params["head"]
        return head.astype(self.dt.compute)

    def loss_fn(self, params: Params, batch: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        x = self._embed(params, batch)
        b, s = x.shape[:2]
        positions = self._positions(batch, b, s)
        x, aux = self._run_stack(params, x, positions)
        x = layers.rmsnorm(params["final_norm"], x)
        xent = layers.chunked_xent(
            x, self._head(params), batch["labels"],
            n_chunks=min(self.opts.xent_chunks, s),
            unroll=self.opts.unroll_inner, policy=self.cfg.dispatch)
        loss = xent + aux
        return loss, {"loss": loss, "xent": xent, "aux": aux}

    def forward(self, params: Params, batch) -> jax.Array:
        """Forward returning full logits (small-scale eval / tests)."""
        x = self._embed(params, batch)
        b, s = x.shape[:2]
        positions = self._positions(batch, b, s)
        x, _ = self._run_stack(params, x, positions)
        return self._logits(params, x)

    def prefill(self, params: Params, batch) -> jax.Array:
        """Inference prefill: run the stack, return ONLY the last
        position's logits (B, V) — what batched serving actually needs to
        begin decoding.  Forward-only: no loss, no optimizer state."""
        x = self._embed(params, batch)
        b, s = x.shape[:2]
        positions = self._positions(batch, b, s)
        x, _ = self._run_stack(params, x, positions)
        x_last = jax.lax.slice_in_dim(x, s - 1, s, axis=1)
        return self._logits(params, x_last)[:, 0]

    # ------------------------------ decode ----------------------------
    def init_cache(self, batch: int, max_len: int) -> List[Dict[str, Any]]:
        cfg, lay = self.cfg, self.layout
        out: Dict[str, Any] = {"prefix": [], "stack": [], "tail": []}
        for kind in lay.prefix:
            out["prefix"].append(layer_cache_init(cfg, kind, batch, max_len,
                                                  self.dt.compute))
        if lay.n_periods:
            for kind in lay.period:
                one = layer_cache_init(cfg, kind, batch, max_len,
                                       self.dt.compute)
                out["stack"].append(jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (lay.n_periods,) + a.shape), one))
        for kind in lay.tail:
            out["tail"].append(layer_cache_init(cfg, kind, batch, max_len,
                                                self.dt.compute))
        return out

    def cache_specs(self, batch: int, max_len: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def decode_step(self, params: Params, cache, batch: Dict[str, jax.Array],
                    pos: jax.Array, paged=None):
        """One token for every sequence.  Returns (logits (B, V), cache).

        ``paged`` = (lengths (B,), table (B, n_pages)) switches attention
        layers onto the paged ragged path: every slot decodes at its own
        length (``pos`` is ignored) against the shared page pools.
        """
        cfg, dt, lay, opts = self.cfg, self.dt, self.layout, self.opts
        x = self._embed(params, batch)          # (B, 1, d)
        pos_override = batch.get("positions") if cfg.mrope_sections else None
        if paged is not None:
            paged = (*paged, self._paged_decode_view(cache, *paged))

        new_cache = {"prefix": [], "stack": [], "tail": []}
        for p, kind, c in zip(params["prefix"], lay.prefix, cache["prefix"]):
            x, nc = layer_decode(p, cfg, kind, x, c, pos, dt, pos_override,
                                 opts=opts, paged=paged)
            new_cache["prefix"].append(nc)

        if lay.n_periods:
            def one(p, kind, x, c, layer):
                return layer_decode(p, cfg, kind, x, c, pos, dt,
                                    pos_override, opts=opts, paged=paged,
                                    layer=layer)
            x, new_cache["stack"] = self._cached_stack(
                one, x, params["stack"], cache["stack"])

        for p, kind, c in zip(params["tail"], lay.tail, cache["tail"]):
            x, nc = layer_decode(p, cfg, kind, x, c, pos, dt, pos_override,
                                 opts=opts, paged=paged)
            new_cache["tail"].append(nc)

        logits = self._logits(params, x)[:, 0]
        return logits, new_cache

    def _paged_decode_view(self, cache, lengths, table):
        """The paged decode view every attention layer of the step reads
        (``layers.paged_decode_view``), built once before the layer scan:
        the layers share the lengths and table, so the decode kernel's
        work list is the same for every layer of one window."""
        lay = self.layout
        mixers = {m for m, _ in (*lay.prefix, *lay.period, *lay.tail)
                  if m in ("attn", "swa")}
        if not mixers:
            return None
        page = next(c["k_pages"].shape[-2]
                    for part in ("prefix", "stack", "tail")
                    for c in cache[part] if "k_pages" in c)
        return layers.paged_decode_view(
            lengths, table, page,
            sorted({_attn_spec(self.cfg, m).window for m in mixers}))

    # ------------------------------ paged serving ---------------------
    def init_paged_cache(self, slots: int, max_len: int, page_size: int,
                         total_pages: Optional[int] = None
                         ) -> Dict[str, Any]:
        """Paged KV cache: per-attention-layer (P, Hkv, page, hd) pools,
        stacked to (n_periods, P, Hkv, page, hd) for the scanned layers
        (the steps carry the stack through the layer scan and write it in
        place; see ``_cached_stack``).

        Physical page 0 is the TRASH page — the scheduler points inactive
        slots' tables at it so their (masked, discarded) decode writes
        never land in a live sequence.  ``total_pages`` defaults to full
        capacity (every slot can reach ``max_len``); pass something
        smaller to oversubscribe — serve capacity then scales with the
        page pool, not with slots x longest-sequence.

        The pool storage dtype follows ``cfg.kv_dtype`` ("" = the model
        compute dtype; "int8" adds per-(page, kv-head) f32 scale leaves —
        type demotion §4.4 applied to the dominant serving residency).
        """
        from ..core import quant
        cfg, lay = self.cfg, self.layout
        pool_dtype = quant.kv_dtype_of(cfg.kv_dtype, self.dt.compute)
        if total_pages is None:
            total_pages = 1 + slots * (-(-max_len // page_size))
        out: Dict[str, Any] = {"prefix": [], "stack": [], "tail": []}
        for kind in lay.prefix:
            out["prefix"].append(layer_cache_init_paged(
                cfg, kind, slots, total_pages, page_size, pool_dtype))
        if lay.n_periods:
            for kind in lay.period:
                one = layer_cache_init_paged(
                    cfg, kind, slots, total_pages, page_size, pool_dtype)
                out["stack"].append(jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None], (lay.n_periods,) + a.shape), one))
        for kind in lay.tail:
            out["tail"].append(layer_cache_init_paged(
                cfg, kind, slots, total_pages, page_size, pool_dtype))
        return out

    def prefill_step_paged(self, params: Params, cache,
                           tokens: jax.Array, starts: jax.Array,
                           tables: jax.Array, last_idx: jax.Array):
        """One page-aligned prompt chunk each of B DISTINCT slots through
        the stack — the continuous-batching engine's multi-slot prefill.

        tokens: (B, C) with C == page_size; starts: (B,) int32 chunk
        offsets (page-aligned); tables: (B, n_pages) each slot's page ids;
        last_idx: (B,) index of the last REAL prompt token within each
        chunk (the final, possibly padded, chunk wants its logits).
        The legacy single-slot convention (scalar ``starts``/``last_idx``,
        1-D ``tables``) is normalized to B == 1.
        Returns (logits (B, V) at last_idx, cache).
        """
        cfg, dt, lay, opts = self.cfg, self.dt, self.layout, self.opts
        starts = jnp.asarray(starts)
        tables = jnp.asarray(tables)
        last_idx = jnp.asarray(last_idx)
        if starts.ndim == 0:
            starts = starts[None]
        if tables.ndim == 1:
            tables = tables[None]
        if last_idx.ndim == 0:
            last_idx = last_idx[None]
        b, c = tokens.shape
        x = self._embed(params, {"tokens": tokens})
        pos_override = None
        if cfg.mrope_sections:
            pos_override = jnp.broadcast_to(
                (starts[:, None] + jnp.arange(c)[None, :])[:, :, None],
                (b, c, len(cfg.mrope_sections))).astype(jnp.int32)

        def one(p, kind, x, c_in, layer=None):
            return layer_prefill_paged(p, cfg, kind, x, c_in, starts,
                                       tables, dt, pos_override,
                                       opts=opts, layer=layer)

        new_cache = {"prefix": [], "stack": [], "tail": []}
        for p, kind, cc in zip(params["prefix"], lay.prefix,
                               cache["prefix"]):
            x, nc = one(p, kind, x, cc)
            new_cache["prefix"].append(nc)
        if lay.n_periods:
            x, new_cache["stack"] = self._cached_stack(
                one, x, params["stack"], cache["stack"])
        for p, kind, cc in zip(params["tail"], lay.tail, cache["tail"]):
            x, nc = one(p, kind, x, cc)
            new_cache["tail"].append(nc)

        x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
        return self._logits(params, x_last)[:, 0], new_cache

    def verify_step_paged(self, params: Params, cache, tokens: jax.Array,
                          lengths: jax.Array, tables: jax.Array):
        """Score W candidate tokens each of B distinct slots — the
        speculative-decoding verify forward.

        tokens: (B, W) — slot b's window is ``[last_emitted, d1..d_{W-1}]``
        occupying positions ``lengths[b] + [0, W)`` (NOT page-aligned; the
        scheduler guarantees pages exist for the span).  Unlike prefill,
        the caller needs logits at EVERY window position: row t predicts
        the token at position lengths+t+1, so acceptance compares draft
        t+1 against argmax(row t).  Returns (logits (B, W, V), cache).
        """
        cfg, dt, lay, opts = self.cfg, self.dt, self.layout, self.opts
        lengths = jnp.asarray(lengths)
        tables = jnp.asarray(tables)
        b, w = tokens.shape
        x = self._embed(params, {"tokens": tokens})
        pos_override = None
        if cfg.mrope_sections:
            pos_override = jnp.broadcast_to(
                (lengths[:, None] + jnp.arange(w)[None, :])[:, :, None],
                (b, w, len(cfg.mrope_sections))).astype(jnp.int32)

        def one(p, kind, x, c_in, layer=None):
            return layer_verify_paged(p, cfg, kind, x, c_in, lengths,
                                      tables, dt, pos_override, opts=opts,
                                      layer=layer)

        new_cache = {"prefix": [], "stack": [], "tail": []}
        for p, kind, cc in zip(params["prefix"], lay.prefix,
                               cache["prefix"]):
            x, nc = one(p, kind, x, cc)
            new_cache["prefix"].append(nc)
        if lay.n_periods:
            x, new_cache["stack"] = self._cached_stack(
                one, x, params["stack"], cache["stack"])
        for p, kind, cc in zip(params["tail"], lay.tail, cache["tail"]):
            x, nc = one(p, kind, x, cc)
            new_cache["tail"].append(nc)
        return self._logits(params, x), new_cache


# --------------------------------------------------------------------------
# parameter accounting
# --------------------------------------------------------------------------

def param_counts(cfg: ArchConfig) -> Dict[str, float]:
    """Exact counts from the abstract param tree + MODEL_FLOPS conventions."""
    import math
    m = Model(cfg)
    specs = m.param_specs()
    total = sum(math.prod(l.shape) for l in jax.tree.leaves(specs))
    embed = cfg.vocab_size * cfg.d_model
    # N for 6*N*D: exclude the gather-only input table; the LM-head matmul
    # counts (once, even when tied).
    n_flops = total - (0 if cfg.tie_embeddings else embed)
    n_active = n_flops
    if cfg.n_experts:
        per_total, per_active = moe.moe_param_count(_moe_spec(cfg))
        n_moe_layers = sum(1 for k in cfg.layer_kinds() if k[1] == "moe")
        n_active = n_flops - n_moe_layers * (per_total - per_active)
    return {"total": total, "embed": embed,
            "n_flops": n_flops, "n_active": n_active}
