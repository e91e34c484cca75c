"""Tensor-parallel paged serving: shard_map plumbing around the registry.

The serving TP scheme is the classic Megatron split, specialized to the
paged-KV decode/prefill stack (ROADMAP item 4; the paper's replication +
memory-partitioning transformations applied to attention heads so parallel
units never contend for one KV interface):

* q/k/v projections are **column-parallel** — each device owns a contiguous
  block of heads (``wq`` sharded on its head axis), so the ragged paged
  attention kernels run *unmodified* per shard against a device-local slice
  of the KV page pools.  The per-shard attention output is **all-gathered**
  back to full heads (the block's one gather), and ``wo`` stays replicated —
  which also keeps int8 per-output-channel weight scales bit-exact.
* MLP up-projections (``wg``/``wu``/``wi``) are column-parallel, the
  down-projection ``wd`` is **row-parallel** with a psum — the block's one
  all-reduce (this covers ``quantized_matmul`` too: int8 ``wd`` shards carry
  per-shard local scales).
* Embedding, norms, logits head, and MoE FFN weights stay replicated; the
  residual stream is replicated everywhere outside an attention/MLP interior.
* MQA (``n_kv_heads == 1``): KV pools and ``wk``/``wv`` replicate (every
  device appends identical K/V), only q-heads shard.

The ops themselves declare these contracts on their ``OpSpec.tp`` tables;
call sites in ``models/layers.py`` carry inert ``tp="col"``/``"row"`` tags,
and ``registry.call`` applies the collective only inside an active
``registry.tp_scope`` — which this module opens while tracing the
``shard_map`` body.  ``registry.call`` therefore stays the single routing
path inside the mapped region, and model code stays mesh-agnostic.

Host-side page metadata (``PageAllocator``, prefix trie, CoW stash) is
device-free and shared across shards: every device sees the same tables and
lengths; pages never cross devices.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels import registry


# --------------------------------------------------------------------------
# eligibility
# --------------------------------------------------------------------------

def tp_error(cfg, tp: int) -> Optional[str]:
    """Why this arch can't serve at tensor-parallel degree ``tp``
    (None = supported).  tp == 1 is always supported (degenerate mesh)."""
    if tp <= 1:
        return None
    from ..models.transformer import paged_supported
    if not paged_supported(cfg):
        return f"{cfg.name}: paged serving requires attention-only stacks"
    if cfg.n_heads % tp:
        return f"{cfg.name}: n_heads={cfg.n_heads} not divisible by tp={tp}"
    if cfg.n_kv_heads != 1 and cfg.n_kv_heads % tp:
        return (f"{cfg.name}: n_kv_heads={cfg.n_kv_heads} not divisible by "
                f"tp={tp} (only MQA n_kv_heads=1 replicates)")
    if any(f == "mlp" for _, f in cfg.layer_kinds()) and cfg.d_ff % tp:
        return f"{cfg.name}: d_ff={cfg.d_ff} not divisible by tp={tp}"
    return None


def kv_sharded(cfg, tp: int) -> bool:
    """Do the KV page pools shard over the mesh (False = MQA replication)?"""
    return tp > 1 and cfg.n_kv_heads % tp == 0


# --------------------------------------------------------------------------
# partition-spec derivation (params + paged cache)
# --------------------------------------------------------------------------

def _dim_spec(ndim: int, d: int, axis: str) -> P:
    spec = [None] * ndim
    spec[d] = axis
    return P(*spec)


def _path_names(path) -> list:
    return [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]


def param_pspecs(params, cfg, tp: int, *, axis: str = "model"):
    """PartitionSpec tree for a ``Model.init`` params tree.

    Sharded dims are counted from the *trailing* end so the specs survive
    the scanned stack's extra leading ``n_periods`` axis unchanged:
    ``wq`` (d, H, hd) and bias (H, hd) shard ndim-2; ``wg``/``wu``/``wi``
    (d, ff) shard ndim-1; ``wd`` (ff, d) shards ndim-2.  Everything else
    (embed, norms, head, ``wo``, MoE weights) replicates.
    """
    kv = kv_sharded(cfg, tp)

    def spec(path, leaf):
        names = _path_names(path)
        name = names[-1] if names else ""
        if "attn" in names:
            if name in ("wq", "bq"):
                return _dim_spec(leaf.ndim, leaf.ndim - 2, axis)
            if kv and name in ("wk", "wv", "bk", "bv"):
                return _dim_spec(leaf.ndim, leaf.ndim - 2, axis)
            return P()
        if "mlp" in names:
            if name in ("wg", "wu", "wi"):
                return _dim_spec(leaf.ndim, leaf.ndim - 1, axis)
            if name == "wd":
                return _dim_spec(leaf.ndim, leaf.ndim - 2, axis)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def cache_pspecs(cache, cfg, tp: int, *, axis: str = "model"):
    """PartitionSpec tree for a ``Model.init_paged_cache`` tree: pools
    (P, Hkv, page, hd) shard their kv-head axis (ndim-3), scales (P, Hkv)
    shard ndim-1 — or everything replicates under MQA / tp == 1."""
    kv = kv_sharded(cfg, tp)

    def spec(path, leaf):
        names = _path_names(path)
        name = names[-1] if names else ""
        if kv and name in ("k_pages", "v_pages"):
            return _dim_spec(leaf.ndim, leaf.ndim - 3, axis)
        if kv and name in ("k_scale", "v_scale"):
            return _dim_spec(leaf.ndim, leaf.ndim - 1, axis)
        return P()

    return jax.tree_util.tree_map_with_path(spec, cache)


def shard_tree(tree, specs, mesh):
    """device_put every leaf with its NamedSharding (host->mesh placement)."""
    return jax.tree.map(
        lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
        tree, specs)


# --------------------------------------------------------------------------
# shard_map'd step functions
# --------------------------------------------------------------------------

def sharded_paged_fns(model, mesh, *, axis: str = "model"):
    """(decode_fn, prefill_fn) running the model's paged steps under
    ``jax.shard_map`` with ``registry.tp_scope`` active in the body.

    Both take the same signatures as ``Model.decode_step`` /
    ``Model.prefill_step_paged`` (params and cache pre-sharded via
    ``shard_tree``; everything else replicated) and return replicated
    logits plus the cache in its input sharding.  ``check_vma=False``
    because the replicated outputs come from collectives the rep-checker
    can't prove (psum into residuals, gathered attention heads).
    """
    cfg = model.cfg
    tp = mesh.shape[axis]
    err = tp_error(cfg, tp)
    if err:
        raise ValueError(err)

    def wrap(step, n_rest):
        def run(params, cache, *rest):
            assert len(rest) == n_rest
            p_specs = param_pspecs(params, cfg, tp, axis=axis)
            c_specs = cache_pspecs(cache, cfg, tp, axis=axis)

            def body(params, cache, *rest):
                # the body executes at trace time, so the scope is active
                # exactly while registry.call sites inside the mapped
                # region are being traced — tags become live contracts
                with registry.tp_scope(axis):
                    return step(params, cache, *rest)

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(p_specs, c_specs) + (P(),) * n_rest,
                out_specs=(P(), c_specs),
                check_vma=False,
            )(params, cache, *rest)
        return run

    decode = wrap(model.decode_step, 3)       # batch, pos, paged
    prefill = wrap(model.prefill_step_paged, 4)  # tokens, starts, tables, last
    return decode, prefill
