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

import re
from typing import Dict, List, Optional, Tuple

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
    """device_put every leaf with its NamedSharding (host->mesh placement);
    a leaf already in its sharding is kept as it is, not copied."""
    def put(leaf, spec):
        target = NamedSharding(mesh, spec)
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and sharding.is_equivalent_to(target,
                                                              leaf.ndim):
            return leaf
        return jax.device_put(leaf, target)
    return jax.tree.map(put, tree, specs)


def _sharded_jit(fn, pspecs, mesh, *args):
    """``jax.jit(fn)`` with its outputs laid out in ``pspecs`` of their
    shapes: every device computes and holds only its shard, so no device
    ever holds a whole copy of a sharded leaf."""
    specs = pspecs(jax.eval_shape(fn, *args))
    return jax.jit(fn, out_shardings=jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs))


def params_creator(model, mesh, *, axis: str = "model"):
    """The jitted ``model.init`` whose outputs come out in their
    :func:`param_pspecs` shardings; call it with a key."""
    tp = mesh.shape[axis]
    return _sharded_jit(
        model.init, lambda t: param_pspecs(t, model.cfg, tp, axis=axis),
        mesh, jax.random.key(0))


def init_params_sharded(model, key, mesh, *, axis: str = "model"):
    """``model.init(key)`` drawn directly in its :func:`param_pspecs`
    shardings.  The values are those of the unsharded ``model.init(key)``:
    with ``jax_threefry_partitionable`` (JAX's default) random bits do not
    depend on how the computation is partitioned."""
    key = jax.device_put(key, NamedSharding(mesh, P()))
    return params_creator(model, mesh, axis=axis)(key)


def paged_cache_creator(model, mesh, slots: int, max_len: int,
                        page_size: int, total_pages: int, *,
                        axis: str = "model"):
    """The jitted ``model.init_paged_cache(...)`` whose pools come out in
    their :func:`cache_pspecs` shardings, each device allocating only its
    KV heads' pools; call it with no arguments."""
    tp = mesh.shape[axis]
    return _sharded_jit(
        lambda: model.init_paged_cache(slots, max_len, page_size,
                                       total_pages=total_pages),
        lambda t: cache_pspecs(t, model.cfg, tp, axis=axis), mesh)



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
        # jit names the compiled module after the step it maps
        # (``jit_decode_step``), as in the unsharded path
        run.__name__ = run.__qualname__ = step.__name__
        return run

    decode = wrap(model.decode_step, 3)       # batch, pos, paged
    prefill = wrap(model.prefill_step_paged, 4)  # tokens, starts, tables, last
    return decode, prefill


# --------------------------------------------------------------------------
# what a compiled step's collectives move
# --------------------------------------------------------------------------

# ring traffic per device, as a share of the bytes of the instruction's
# shape: an all-gather's output, an all-reduce's operand (= output)
_RING_SHARE = {
    "all-gather": lambda n: (n - 1) / n,
    "all-reduce": lambda n: 2 * (n - 1) / n,
}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_COLLECTIVE = re.compile(r"^(.*?) (" + "|".join(_RING_SHARE) + r")\(")
_ARRAY = re.compile(r"\b([a-z]+)(\d*)[a-z0-9]*\[([\d,]*)\]")
_CALLEE = re.compile(r"\b(body|calls)=%([^,\s]+)")
_CONSTANT = re.compile(r"^[su]\d+\[\]\S* constant\((-?\d+)\)")
_LESS_THAN = re.compile(r"compare\(%(\S+), %(\S+)\), direction=LT")


def _array_bytes(shape: str) -> List[int]:
    """Bytes of each array of an HLO shape (``bf16[32,4096]{...}``,
    tuples of them); ``pred`` is a byte."""
    out = []
    for name, bits, dims in _ARRAY.findall(shape):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append(n * (int(bits) // 8 if bits else 1))
    return out


def _group_size(line: str, n_devices: int) -> int:
    m = re.search(r"replica_groups=\{\{([\d,]*)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    return int(m.group(2)) if m else n_devices


def _parse(hlo: str) -> Tuple[Dict[str, List[str]], str]:
    comps: Dict[str, List[str]] = {}
    entry, cur = "", None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            if line.startswith("ENTRY"):
                entry = m.group(1)
        elif cur is not None and _INSTRUCTION.match(line):
            cur.append(line)
    if not entry:
        raise ValueError("no ENTRY computation in the HLO text")
    return comps, entry


def _trip_count(comps, cond: str) -> int:
    """The trip count of a counted loop (``i < N`` from 0, as
    ``lax.scan`` and ``fori_loop`` lower), read off its condition."""
    consts, lines = {}, comps.get(cond, [])
    for line in lines:
        name, rest = _INSTRUCTION.match(line).groups()
        m = _CONSTANT.match(rest)
        if m:
            consts[name] = int(m.group(1))
    for line in lines:
        m = _LESS_THAN.search(line)
        if m is None:                    # the compare wrapped in a fusion
            f = re.search(r"fusion\(%(\S+), %(\S+)\).*calls=%([^,\s]+)",
                          line)
            if f and any(_LESS_THAN.search(x)
                         for x in comps.get(f.group(3), [])):
                m = f
        if m and m.group(2) in consts:
            return consts[m.group(2)]
    raise ValueError(f"no counted-loop bound in while condition {cond}")


def collective_bytes(hlo: str, n_devices: int) -> int:
    """Bytes one device sends over the interconnect in one execution of
    a compiled program, from its HLO text (``compiled.as_text()``).

    Every all-gather and all-reduce (the collectives the tensor-parallel
    steps compile to, on the CPU and the TPU alike) counts as a ring over
    its replica group of ``n``: an all-gather moves (n-1)/n of its output,
    an all-reduce 2(n-1)/n of its operand.  An instruction inside a loop
    body counts once per trip."""
    comps, entry = _parse(hlo)
    own: Dict[str, float] = {}
    calls: Dict[str, List[Tuple[str, Optional[str]]]] = {}
    for comp, lines in comps.items():
        own[comp] = 0.0
        calls[comp] = []
        for line in lines:
            rest = _INSTRUCTION.match(line).group(2)
            m = _COLLECTIVE.match(rest)
            if m:
                shape, op = m.groups()
                own[comp] += (sum(_array_bytes(shape)) * _RING_SHARE[op](
                    _group_size(line, n_devices)))
            for kind, callee in _CALLEE.findall(rest):
                cond = re.search(r"condition=%([^,\s]+)", rest) \
                    if kind == "body" else None
                calls[comp].append((callee, cond and cond.group(1)))

    memo: Dict[str, float] = {}

    def total(comp: str) -> float:
        if comp not in memo:
            memo[comp] = own.get(comp, 0.0)
            for callee, cond in calls.get(comp, ()):
                inner = total(callee)
                if inner:      # only loops that hold collectives are read
                    memo[comp] += inner * (_trip_count(comps, cond)
                                           if cond else 1)
        return memo[comp]

    return int(round(total(entry)))
