"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode ignores TPU tiling, so a kernel whose BlockSpecs the chip's
compiler refuses still passes every interpret-mode differential test.
These tests compile each serving/training kernel at the widths of the
codeqwen1.5-7b serve configuration (32 heads, 32 KV heads, head dim 128,
page 64, d_model 4096, d_ff 13440, vocab 92416) with ``interpret=False``
against one device of a described ``v5e:2x2`` topology, and check that
the compiled HLO really holds the Pallas kernel (``tpu_custom_call``).
Nothing runs: the compile needs shapes only.

The topology is described inside a module-scoped fixture, never while
the module is imported: only one process may load the TPU library at a
time, and under pytest-xdist every worker imports every test file.
``plan="heuristic"`` keeps a CPU-tuned plan from routing a shape to the
reference lowering.

The paged serving steps are compiled whole, at the benchmark's GQA widths
(4 KV heads) and two layers, to check that they update the layer-stacked
KV pool in place: their temporaries must stay below one layer's pool.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.archs import ARCHS
from repro.core.memory import DtypePolicy
from repro.kernels.attention import (decode_attention, flash_attention,
                                     flash_attention_bwd, prefill_attention)
from repro.kernels.matmul.ops import matmul, quantized_matmul
from repro.models.transformer import ExecOptions, Model

H, HKV, HD, PAGE = 32, 32, 128, 64
D_MODEL, D_FF, VOCAB = 4096, 13440, 92416
SLOTS, MAX_LEN = 8, 2048
N_PAGES = MAX_LEN // PAGE
POOL = 1 + SLOTS * N_PAGES


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs):
    """Lower and compile ``fn`` for the described chip; returns the HLO."""
    compiled = jax.jit(fn).lower(*specs).compile()
    return compiled.as_text()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _pools(one_chip, dtype):
    pool = _spec(one_chip, (POOL, HKV, PAGE, HD), dtype)
    if dtype == jnp.int8:
        scale = _spec(one_chip, (POOL, HKV), jnp.float32)
        return (pool, pool, scale, scale)
    return (pool, pool)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_decode_attention_compiles(one_chip, dtype):
    q = _spec(one_chip, (SLOTS, H, HD), jnp.bfloat16)
    table = _spec(one_chip, (SLOTS, N_PAGES), jnp.int32)
    lengths = _spec(one_chip, (SLOTS,), jnp.int32)
    kp, vp, *scales = _pools(one_chip, dtype)

    def fn(q, kp, vp, table, lengths, *scales):
        return decode_attention(q, kp, vp, table, lengths, *scales,
                                plan="heuristic", interpret=False)

    assert "tpu_custom_call" in _compile(fn, q, kp, vp, table, lengths,
                                         *scales)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_prefill_attention_compiles(one_chip, dtype):
    b = 4                                   # chunks batched in one prefill
    q = _spec(one_chip, (b, PAGE, H, HD), jnp.bfloat16)
    table = _spec(one_chip, (b, N_PAGES), jnp.int32)
    starts = _spec(one_chip, (b,), jnp.int32)
    kp, vp, *scales = _pools(one_chip, dtype)

    def fn(q, kp, vp, table, starts, *scales):
        return prefill_attention(q, kp, vp, table, starts, *scales,
                                 plan="heuristic", interpret=False)

    assert "tpu_custom_call" in _compile(fn, q, kp, vp, table, starts,
                                         *scales)


@pytest.mark.parametrize("op", ["decode", "prefill"])
def test_tp4_shard_kernels_compile(one_chip, op):
    """One shard of CodeQwen1.5-7B served whole at tensor parallel 4: one
    of the 4 KV heads and its 8 query heads, read from the layer-stacked
    pool of all 32 layers at the serve cells' 4097 pages, at a layer
    index, as the shard_map'd steps call the kernels."""
    slots, n_pages, layers, heads = 32, 8192 // PAGE, 32, H // 4
    pool = _spec(one_chip, (layers, 1 + slots * n_pages, 1, PAGE, HD),
                 jnp.bfloat16)
    layer = _spec(one_chip, (), jnp.int32)
    if op == "decode":
        b, q = slots, _spec(one_chip, (slots, heads, HD), jnp.bfloat16)
        attend = decode_attention
    else:
        b = 8                               # chunks of the 512-token budget
        q = _spec(one_chip, (b, PAGE, heads, HD), jnp.bfloat16)
        attend = prefill_attention
    table = _spec(one_chip, (b, n_pages), jnp.int32)
    rows = _spec(one_chip, (b,), jnp.int32)   # lengths or chunk starts

    def fn(q, kp, vp, table, rows, layer):
        return attend(q, kp, vp, table, rows, layer=layer,
                      plan="heuristic", interpret=False)

    assert "tpu_custom_call" in _compile(fn, q, pool, pool, table, rows,
                                         layer)


def test_flash_forward_with_residuals_compiles(one_chip):
    qkv = _spec(one_chip, (1, H, MAX_LEN, HD), jnp.bfloat16)

    def fn(q, k, v):
        return flash_attention(q, k, v, plan="heuristic",
                               return_residuals=True, interpret=False)

    assert "tpu_custom_call" in _compile(fn, qkv, qkv, qkv)


def test_flash_backward_compiles(one_chip):
    qkv = _spec(one_chip, (1, H, MAX_LEN, HD), jnp.bfloat16)
    o = _spec(one_chip, (1, H, MAX_LEN, HD), jnp.float32)
    lse = _spec(one_chip, (1, H, MAX_LEN), jnp.float32)

    def fn(q, k, v, o, lse, do):
        return flash_attention_bwd(q, k, v, o, lse, do, plan="heuristic",
                                   interpret=False)

    assert "tpu_custom_call" in _compile(fn, qkv, qkv, qkv, o, lse, o)


# tp=4 shards d_ff to 3360, which no 128-multiple tile divides: the
# planner gives that dim one whole-dim block
@pytest.mark.parametrize("m,k,n", [(256, D_MODEL, D_FF),
                                   (SLOTS, D_MODEL, VOCAB),
                                   (SLOTS, D_MODEL, D_FF // 4),
                                   (256, D_FF // 4, D_MODEL),
                                   (5 * PAGE, D_MODEL, D_FF // 4)],
                         ids=["mlp_up", "decode_logits", "tp4_mlp_up",
                              "tp4_mlp_down", "tp4_mlp_up_5_chunks"])
def test_matmul_compiles(one_chip, m, k, n):
    a = _spec(one_chip, (m, k), jnp.bfloat16)
    b = _spec(one_chip, (k, n), jnp.bfloat16)

    def fn(a, b):
        return matmul(a, b, plan="heuristic", interpret=False)

    assert "tpu_custom_call" in _compile(fn, a, b)


def test_quantized_matmul_compiles(one_chip):
    a = _spec(one_chip, (256, D_MODEL), jnp.bfloat16)
    w = _spec(one_chip, (D_MODEL, D_FF), jnp.int8)
    scale = _spec(one_chip, (D_FF,), jnp.float32)

    def fn(a, w, scale):
        return quantized_matmul(a, w, scale, plan="heuristic",
                                interpret=False)

    assert "tpu_custom_call" in _compile(fn, a, w, scale)


@pytest.fixture
def tpu_choices(monkeypatch):
    """Steer the program's backend-dependent choices to the chip's, as a
    run there makes them: compiled kernels rather than interpret mode,
    and tuned plans looked up under the chip's key."""
    from repro.kernels.attention import ops as attention_ops
    from repro.kernels.matmul import ops as matmul_ops
    from repro.tune import cache as plan_cache
    monkeypatch.setattr(attention_ops, "interpret_default", lambda: False)
    monkeypatch.setattr(matmul_ops, "interpret_default", lambda: False)
    monkeypatch.setattr(plan_cache, "_backend_name",
                        lambda backend=None: backend or "tpu")


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_paged_step_updates_pool_in_place(one_chip, tpu_choices, step):
    """A paged serving step, compiled for the chip with its cache donated,
    writes the new K/V rows into the layer-stacked pool in place: its
    temporaries stay below one layer's K pool.  A layer scan that takes
    the pool as xs and gives it back as ys copies every layer's pool out
    of the stack and into a new one, ~1.5 pools of temporaries."""
    cfg = dataclasses.replace(ARCHS["codeqwen1.5-7b"], n_layers=2,
                              n_kv_heads=4, dispatch="kernels")
    model = Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
                  opts=ExecOptions(mode="run"))
    slots, max_len = 8, 4096                # 513 pages of 64
    n_pages = max_len // PAGE

    def place(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)

    def i32(*shape):
        return _spec(one_chip, shape, jnp.int32)

    params = place(jax.eval_shape(model.init, jax.random.key(0)))
    cache = place(jax.eval_shape(
        lambda: model.init_paged_cache(slots, max_len, PAGE)))
    if step == "decode":
        lowered = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
            params, cache, {"tokens": i32(slots, 1)}, i32(),
            (i32(slots), i32(slots, n_pages)))
    else:
        b = 2                               # chunks batched in one prefill
        lowered = jax.jit(model.prefill_step_paged,
                          donate_argnums=(1,)).lower(
            params, cache, i32(b, PAGE), i32(b), i32(b, n_pages), i32(b))
    compiled = lowered.compile()
    pool = cache["stack"][0]["k_pages"]     # (layers, pages, Hkv, page, hd)
    one_layer = pool.size // pool.shape[0] * pool.dtype.itemsize
    assert "tpu_custom_call" in compiled.as_text()
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < one_layer, (temps, one_layer)
