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
import json
from pathlib import Path

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


# ------------------------------------------- tp == 4 at the real GQA ratio

_TP4_CODE = """
import json, re, sys, types
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, BENCH)
from chipbench import program
from reference import qwen2
from repro.core.memory import DtypePolicy
from repro.launch import tracing
from repro.launch.engine import StepExecutor
from repro.launch.loadgen import Request
from repro.launch.mesh import make_serving_mesh
from repro.launch.serve import PagedScheduler
from repro.models.transformer import ExecOptions, Model
from repro.runtime import tp as tp_mod

assert len(jax.devices()) == 4, jax.devices()
# the published GQA ratio at smoke width: 32 query heads over 4 KV heads,
# so each of 4 shards holds one KV head and its 8 query heads
CONFIG = {"name": "tp4.smoke", "hidden_size": 512, "intermediate_size": 256,
          "num_attention_heads": 32, "num_key_value_heads": 4,
          "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 1e6,
          "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
          "program": {"arch": "codeqwen1.5-7b", "smoke": True,
                      "dispatch": "kernels", "param_dtype": "bfloat16"}}
cfg = program.arch_config(CONFIG, d_model=512, n_heads=32, head_dim=16)
model = Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
              opts=ExecOptions(mode="run"))
key = jax.random.key(20260417)
mesh = make_serving_mesh(4)
out = {}

# (a) creation in the shardings
params = tp_mod.init_params_sharded(model, key, mesh)
whole = model.init(key)
out["params_equal"] = all(
    bool(np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32)))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(whole)))
want = tp_mod.param_pspecs(whole, cfg, 4)
out["params_in_pspecs"] = all(
    a.sharding.spec == s for a, s in zip(
        jax.tree.leaves(params), jax.tree.leaves(
            want, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))))
sched = PagedScheduler(model, params, slots=2, max_len=64, page_size=16,
                       mesh=mesh, log=None)
out["params_kept"] = all(a is b for a, b in zip(
    jax.tree.leaves(sched.params), jax.tree.leaves(params)))
specs = tp_mod.cache_pspecs(sched.cache, cfg, 4)
shares = []
for leaf, spec in zip(jax.tree.leaves(sched.cache), jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))):
    per_device = {}
    for sh in leaf.addressable_shards:
        per_device[sh.device] = per_device.get(sh.device, 0) + sh.data.nbytes
    shares.append([leaf.sharding.spec == spec, leaf.nbytes,
                   sorted(per_device.values())])
out["pool"] = shares

# (b) chunked prefill, then decode, against the float32 reference
rng = np.random.default_rng(5)
prompt = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)
assert sched.reserve(Request(0, prompt, 4), 0)
page = sched.page
padded = np.zeros((3 * page,), np.int32)
padded[:40] = prompt
rows, toks = [], list(prompt)
for st in range(0, 40, page):
    last = min(40, st + page) - 1 - st
    logits, sched.cache = sched._prefill(
        sched.params, sched.cache, jnp.asarray(padded[None, st:st + page]),
        jnp.asarray([st], jnp.int32), jnp.asarray(sched.table[[0]]),
        jnp.asarray([last], jnp.int32))
rows.append(np.asarray(logits[0], np.float32))
sched.lengths[0] = 40
for _ in range(3):
    toks.append(int(np.argmax(rows[-1])))
    cur = np.asarray([toks[-1], 0], np.int32)
    lengths = np.asarray([sched.lengths[0], 0], np.int32)
    table = np.where(np.arange(2)[:, None] == 0, sched.table, 0)
    logits, sched.cache = sched._decode(
        sched.params, sched.cache, {"tokens": jnp.asarray(cur)[:, None]},
        jnp.int32(0), (jnp.asarray(lengths), jnp.asarray(table, jnp.int32)))
    rows.append(np.asarray(logits[0], np.float32))
    sched.lengths[0] += 1
s = qwen2.Sizes.of(CONFIG)
e = qwen2.ends(key, s)
seq = jnp.asarray(toks, jnp.int32)[None]
x = e["embed"][seq]
pos = jnp.arange(seq.shape[1])[None]
for i in range(s.layers):
    x = qwen2.block_fwd(qwen2.layer(key, i, s), x, pos, s)
ref = np.asarray(qwen2.logits(e, x[0, 39:43], s))
prog = np.stack(rows)
out["rel_rms"] = float(np.sqrt(np.mean((prog - ref) ** 2) /
                               np.mean(ref ** 2)))
out["gap"] = float(np.max(ref.max(-1) - ref[np.arange(4), prog.argmax(-1)]))

# (c) the counter against the compiled steps' collectives
def ring_bytes(hlo):
    found = {}
    for op, share in (("all-gather", 3 / 4), ("all-reduce", 2 * 3 / 4)):
        for dt, dims in re.findall(
                r"= (\\w+)\\[([\\d,]*)\\]\\S* " + op + r"\\(", hlo):
            n = int(np.prod([int(d) for d in dims.split(",")]))
            bits = int(re.search(r"\\d+", dt).group())
            found.setdefault(op, []).append(
                ([int(d) for d in dims.split(",")], n * bits // 8 * share))
    return found

ex = StepExecutor(sched)
state = types.SimpleNamespace(toks=padded, ln=40)
with tracing.iteration(tracing.new_engine_id(), 0, tp=sched.tp) as rec:
    ex.prefill([(0, 0), (0, page)], [state, None])
out["prefill_counter"] = rec.counters["tp_collective_bytes"]
out["prefill_tp"] = rec.tp
rest = (jnp.zeros((2, page), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, sched.n_slot_pages), jnp.int32),
        jnp.zeros((2,), jnp.int32))
out["prefill_hlo"] = ring_bytes(sched._prefill.lower(
    sched.params, sched.cache, *rest).compile().as_text())
with tracing.iteration(tracing.new_engine_id(), 0, tp=sched.tp) as rec:
    ex.decode(np.zeros((2,), np.int32), [0])
out["decode_counter"] = rec.counters["tp_collective_bytes"]
rest = ({"tokens": jnp.zeros((2, 1), jnp.int32)}, jnp.int32(0),
        (jnp.zeros((2,), jnp.int32), jnp.zeros((2, sched.n_slot_pages),
                                               jnp.int32)))
out["decode_hlo"] = ring_bytes(sched._decode.lower(
    sched.params, sched.cache, *rest).compile().as_text())
out["layers"] = s.layers
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def tp4_smoke():
    """One 4-device subprocess serves the GQA smoke model at tp=4 and
    reports what the three tests below check."""
    bench = str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip")
    out = run_multidevice(f"BENCH = {bench!r}\n" + _TP4_CODE, n_devices=4,
                          timeout=900)
    line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_tp4_creates_params_and_pool_in_their_shardings(tp4_smoke):
    """Params drawn in place equal ``model.init(key)`` leaf by leaf and are
    not copied by the scheduler; every pool leaf comes out in its
    ``cache_pspecs`` sharding, each device holding a quarter of it."""
    r = tp4_smoke
    assert r["params_equal"] and r["params_in_pspecs"] and r["params_kept"]
    assert r["pool"]
    for in_spec, nbytes, per_device in r["pool"]:
        assert in_spec
        assert per_device == [nbytes // 4] * 4


@pytest.mark.slow
def test_tp4_logits_match_float32_reference(tp4_smoke):
    """Chunked prefill (40 tokens in chunks of 16) then 3 decode steps on
    the 4-way mesh, against the float32 reference on the same seeded
    weights.  The weights are the same bf16 values on both sides; the
    program keeps activations, attention and matmul outputs in bf16
    (relative rounding 2^-8 per op, a few dozen ops deep at 2 layers),
    which read 0.006 relative RMS here, so 0.02.  Each served token must
    be the reference's best or within 0.05 of it (the logits' spread is
    ~1, so a wrong token lies far above)."""
    r = tp4_smoke
    assert r["rel_rms"] < 0.02, r["rel_rms"]
    assert r["gap"] < 0.05, r["gap"]


@pytest.mark.slow
def test_tp4_collective_bytes_counter_matches_compiled_hlo(tp4_smoke):
    """``tp_collective_bytes`` of one prefill step (2 chunks) and one
    decode step (2 slot rows) equals the ring traffic that the compiled
    4-device steps' collectives imply: per layer one all-gather of the
    attention heads (3/4 of its output per device) and one all-reduce of
    the MLP output (3/2 of its operand), in the layer loop."""
    r = tp4_smoke
    assert r["prefill_tp"] == 4
    layers, hd, d = r["layers"], 512 // 32, 512
    for kind, tokens in (("prefill", 2 * 16), ("decode", 2)):
        hlo = r[f"{kind}_hlo"]
        (ag_shape, ag), = hlo["all-gather"]
        (ar_shape, ar), = hlo["all-reduce"]
        assert np.prod(ag_shape) == tokens * 32 * hd, ag_shape
        assert np.prod(ar_shape) == tokens * d, ar_shape
        assert r[f"{kind}_counter"] == layers * (ag + ar)
