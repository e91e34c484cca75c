"""Tensor-parallel paged serving (runtime/tp.py + mesh-aware OpSpecs).

Differential discipline for the sharded serving stack:

1. degenerate mesh — a 1-device ("model",) mesh must produce BIT-identical
   token streams to the unsharded scheduler (same params, same requests),
   with ``registry.tp_stats()`` proving every op routed through
   ``registry.call`` inside the shard_map'd region;
2. real mesh — a simulated 2-device mesh (subprocess, forced host device
   count) must match the single-device oracle stream-for-stream, for both
   sharded GQA pools (codeqwen, Hkv % tp == 0) and MQA replication
   (gemma, Hkv == 1), and for int8 KV pools;
3. contract surface — TP tags are inert outside ``registry.tp_scope``,
   unknown tags fail loudly inside one, and ``tp_error`` gates the
   divisibility requirements.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.memory import DtypePolicy
from repro.kernels import dispatch, registry
from repro.launch.loadgen import Request
from repro.launch.mesh import make_serving_mesh
from repro.launch.serve import PagedScheduler
from repro.models.transformer import ExecOptions, Model
from repro.runtime import tp as tp_mod

from helpers import run_multidevice


def _make_model(arch="gemma-2b", **over):
    cfg = get_arch(arch).smoke()
    cfg = dataclasses.replace(cfg, dispatch="kernels", kv_cache="paged",
                              **over)
    return Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
                 opts=ExecOptions(mode="run"))


def _requests(n, vocab, prompt_len=6, max_new=4, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab, prompt_len), max_new)
            for i in range(n)]


def _run_sched(model, params, mesh=None, seed=0):
    sched = PagedScheduler(model, params, slots=2, max_len=64,
                           page_size=16, mesh=mesh, log=None)
    done = sched.run(_requests(3, model.cfg.vocab_size, seed=seed))
    return [list(r.out) for r in sorted(done, key=lambda r: r.rid)]


# --------------------------------------------------------------- tp == 1

def test_tp1_streams_bit_identical():
    """Degenerate 1-device mesh: token streams match the unsharded path
    exactly, and the tp route counters prove registry.call fired inside
    the mapped region."""
    model = _make_model()
    params = model.init(jax.random.key(0))
    with registry.stats_scope():
        base = _run_sched(model, params)
        assert registry.tp_stats() == {}, \
            "unsharded serving must not tick tp counters"
    with registry.stats_scope():
        sharded = _run_sched(model, params, mesh=make_serving_mesh(1))
        tp_routes = registry.tp_stats()
    assert sharded == base
    ops = {op for op, _ in tp_routes}
    assert {"matmul", "decode_attention", "prefill_attention"} <= ops, \
        f"expected the serving ops inside the shard_map region: {tp_routes}"
    # kernels policy: the mapped region must still route to kernels
    assert all(route == "kernel" for _, route in tp_routes), tp_routes


def test_tp1_scheduler_reports_mesh():
    model = _make_model()
    params = model.init(jax.random.key(1))
    sched = PagedScheduler(model, params, slots=2, max_len=64,
                           page_size=16, mesh=make_serving_mesh(1), log=None)
    assert sched.tp == 1 and sched.mesh is not None


# ------------------------------------------------------------ eligibility

def test_tp_error_gates():
    gemma = get_arch("gemma-2b").smoke()       # H=4, Hkv=1 (MQA)
    qwen = get_arch("codeqwen1.5-7b").smoke()  # H=4, Hkv=4
    assert tp_mod.tp_error(gemma, 1) is None
    assert tp_mod.tp_error(qwen, 1) is None
    assert tp_mod.tp_error(gemma, 2) is None          # MQA replicates pools
    assert tp_mod.tp_error(qwen, 2) is None           # GQA pools shard
    assert "n_heads" in tp_mod.tp_error(qwen, 3)      # 4 % 3 != 0
    assert not tp_mod.kv_sharded(gemma, 2)
    assert tp_mod.kv_sharded(qwen, 2)
    rwkv = get_arch("rwkv6-7b").smoke()
    assert "attention-only" in tp_mod.tp_error(rwkv, 2)


def test_pspec_derivation():
    """wq/bias shard the head axis, wo/norms/embed replicate, MLP shards
    col/row, and the stacked scan axis never shifts the sharded dim."""
    model = _make_model("codeqwen1.5-7b")
    cfg = model.cfg
    params = model.param_specs()
    specs = tp_mod.param_pspecs(params, cfg, 2)
    cache = jax.eval_shape(lambda: model.init_paged_cache(2, 64, 16))
    cspecs = tp_mod.cache_pspecs(cache, cfg, 2)

    def axis_of(spec):
        return tuple(spec).index("model") if "model" in tuple(spec) else None

    group = next(g for g in ("stack", "prefix", "tail") if params[g])
    layer = specs[group][0]
    lead = 1 if group == "stack" else 0
    assert axis_of(layer["attn"]["wq"]) == lead + 1      # (d, H, hd) -> H
    assert axis_of(layer["attn"]["wk"]) == lead + 1      # Hkv sharded (GQA)
    assert tuple(layer["attn"]["wo"]) == ()              # replicated
    assert tuple(specs["embed"]) == ()
    assert axis_of(layer["mlp"]["wg"]) == lead + 1       # (d, ff) -> ff
    assert axis_of(layer["mlp"]["wd"]) == lead + 0       # (ff, d) -> ff
    cgroup = next(g for g in ("stack", "prefix", "tail") if cache[g])
    clayer = cspecs[cgroup][0]
    clead = 1 if cgroup == "stack" else 0
    assert axis_of(clayer["k_pages"]) == clead + 1       # (P,Hkv,page,hd)
    # MQA: everything KV replicates
    gemma = _make_model()
    gcache = jax.eval_shape(lambda: gemma.init_paged_cache(2, 64, 16))
    for leaf in jax.tree.leaves(tp_mod.cache_pspecs(gcache, gemma.cfg, 2)):
        assert tuple(leaf) == ()


# ------------------------------------------------------- contract surface

def test_tp_tags_inert_outside_scope():
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 6), jnp.float32)
    base = dispatch.matmul(x, w, policy="reference")
    tagged = dispatch.matmul(x, w, policy="reference", tp="col")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tagged))
    assert registry.tp_axis() is None


def test_unknown_tp_tag_raises_inside_scope():
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 6), jnp.float32)
    with registry.tp_scope("model"):
        with pytest.raises(ValueError, match="no tp contract"):
            registry.call("matmul", x, w, mode="reference", tp="bogus")


def test_opspec_contracts_registered():
    for op, tags in {"matmul": {"col", "row"},
                     "quantized_matmul": {"col", "row"},
                     "decode_attention": {"heads"},
                     "prefill_attention": {"heads"}}.items():
        spec = registry.get(op)
        assert set(spec.tp or {}) == tags, op
    assert registry.get("matmul").tp["row"].collective == "psum"
    assert registry.get("decode_attention").tp["heads"].collective \
        == "all_gather"


# ----------------------------------------------------------- tp == 2 (slow)

_TP2_CODE = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_arch
from repro.core.memory import DtypePolicy
from repro.kernels import registry
from repro.launch.loadgen import Request
from repro.launch.mesh import make_serving_mesh
from repro.launch.serve import PagedScheduler
from repro.models.transformer import ExecOptions, Model

assert len(jax.devices()) == 2, jax.devices()

def run(arch, kv_dtype, mesh):
    cfg = dataclasses.replace(get_arch(arch).smoke(), dispatch="kernels",
                              kv_cache="paged", kv_dtype=kv_dtype)
    model = Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    sched = PagedScheduler(model, params, slots=2, max_len=64,
                           page_size=16, mesh=mesh, log=None)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, 6), 4)
            for i in range(3)]
    done = sched.run(reqs)
    return [list(r.out) for r in sorted(done, key=lambda r: r.rid)]

for arch, kv in (("codeqwen1.5-7b", ""),   # GQA: pools shard 2-way
                 ("gemma-2b", ""),         # MQA: pools replicate
                 ("codeqwen1.5-7b", "int8")):  # scales shard with pools
    oracle = run(arch, kv, None)
    registry.reset_stats()
    sharded = run(arch, kv, make_serving_mesh(2))
    assert sharded == oracle, (arch, kv, sharded, oracle)
    ops = {op for op, _ in registry.tp_stats()}
    assert {"matmul", "decode_attention", "prefill_attention"} <= ops, ops
    print(f"OK {arch} kv={kv or 'compute'}")
print("ALL_MATCH")
"""


@pytest.mark.slow
def test_tp2_matches_single_device_oracle():
    """2-way simulated mesh vs unsharded oracle: identical greedy streams
    for sharded-GQA, replicated-MQA, and int8-KV pools, with the tp route
    counters proving in-region registry.call dispatch."""
    out = run_multidevice(_TP2_CODE, n_devices=2, timeout=900)
    assert "ALL_MATCH" in out, out
