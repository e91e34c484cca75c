"""Compile ``chip_smoke.py``'s steps for a described TPU v5e, on the CPU.

    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/rehearse_chip.py
    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/rehearse_chip.py \\
        --four-chips

The TPU compiler is installed even where no chip is attached: each step is
compiled at its real size against the devices of a described ``v5e:2x2``
topology, from shapes alone (``jax.eval_shape``), and its
``memory_analysis()``, Pallas-kernel count and op routes are printed.  A
refusal here (tiling, VMEM, HBM) or a hot op on its reference route costs
no chip time.  Nothing runs, so this says
nothing about results or speed.

The code under test asks ``jax.default_backend()`` to pick kernels,
interpret mode and the tuned-plan key, and that is the CPU here; this
script steers it to the TPU choices (compiled kernels, "|tpu" plans)
before building the steps.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, SingleDeviceSharding  # noqa: E402

import chip_smoke  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.kernels.attention import ops as attention_ops  # noqa: E402
from repro.kernels.matmul import ops as matmul_ops  # noqa: E402
from repro.tune import cache as plan_cache  # noqa: E402

GiB = 2 ** 30
DECODE_OPS = ("decode_attention", "matmul")
PREFILL_OPS = ("prefill_attention", "matmul")


def _steer_to_tpu() -> None:
    dispatch._kernels_by_default = lambda: True
    # tuned plans are looked up under the chip's key, as on the chip: a
    # "|cpu" entry must not pin a shape to its reference route here
    plan_cache._backend_name = lambda backend=None: backend or "tpu"
    attention_ops.interpret_default = lambda: False
    matmul_ops.interpret_default = lambda: False


def _place(tree, sharding_of):
    """ShapeDtypeStructs of ``tree`` with per-leaf shardings."""
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, sharding_of(tree))


def _compile(name, lower, ops) -> int:
    """Compile ``lower()`` and report it; fail as ``chip_smoke`` would if
    one of ``ops`` took its reference route while tracing."""
    with dispatch.stats_scope() as stats:
        compiled = lower().compile()
        routes = stats()
    print(f"{name}: routes {chip_smoke.format_routes(routes)}")
    chip_smoke.check_routes(routes, ops, name)
    return _report(name, compiled)


def _report(name, compiled) -> int:
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    kernels = compiled.as_text().count("tpu_custom_call")
    print(f"{name}: {need / GiB:.2f} GiB per device (args "
          f"{ma.argument_size_in_bytes / GiB:.2f}, temps "
          f"{ma.temp_size_in_bytes / GiB:.2f}, aliased "
          f"{ma.alias_size_in_bytes / GiB:.2f}); tpu_custom_call x{kernels}")
    return need


def _serve_specs(model, size, n_chunks, sharding_of, total_pages=None):
    key = jax.random.key(0)
    params = _place(jax.eval_shape(model.init, key), sharding_of)
    cache = _place(jax.eval_shape(lambda: model.init_paged_cache(
        size.slots, size.max_len, size.page, total_pages)), sharding_of)
    n_pages = size.max_len // size.page
    i32 = jnp.int32

    def rep(shape):
        return jax.ShapeDtypeStruct(shape, i32, sharding=sharding_of(None))

    decode_args = (params, cache, {"tokens": rep((size.slots, 1))},
                   rep(()), (rep((size.slots,)),
                             rep((size.slots, n_pages))))
    prefill_args = (params, cache, rep((n_chunks, size.page)),
                    rep((n_chunks,)), rep((n_chunks, n_pages)),
                    rep((n_chunks,)))
    return decode_args, prefill_args


def rehearse_one_chip(topo) -> None:
    one = SingleDeviceSharding(topo.devices[0])
    cfg = get_arch(chip_smoke.ARCH)
    size = chip_smoke.ServeSize()
    model = chip_smoke.Model(chip_smoke._cut(cfg, size.layers),
                             dt=chip_smoke.DtypePolicy(param=jnp.bfloat16),
                             opts=chip_smoke.ExecOptions(mode="run"))

    def sharding_of(tree):
        return one if tree is None else jax.tree.map(lambda _: one, tree)

    decode_args, prefill_args = _serve_specs(model, size, size.requests,
                                             sharding_of)
    _compile("serve decode step",
             lambda: jax.jit(model.decode_step, donate_argnums=(1,))
             .lower(*decode_args), DECODE_OPS)
    _compile(f"serve prefill step ({size.requests} chunks)",
             lambda: jax.jit(model.prefill_step_paged, donate_argnums=(1,))
             .lower(*prefill_args), PREFILL_OPS)
    rehearse_train(topo)


def rehearse_train(topo, tsize=chip_smoke.TrainSize()) -> None:
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.optim.adamw import AdamWConfig
    from repro.train.steps import (TrainStepConfig, abstract_train_state,
                                   make_train_step)
    one = SingleDeviceSharding(topo.devices[0])

    def sharding_of(tree):
        return jax.tree.map(lambda _: one, tree)

    tcfg = chip_smoke._cut(get_arch(chip_smoke.ARCH), tsize.layers)
    tmodel = chip_smoke.Model(
        tcfg, dt=chip_smoke.DtypePolicy(),
        opts=chip_smoke.ExecOptions(mode="run",
                                    block_q=min(512, tsize.seq),
                                    block_kv=min(512, tsize.seq),
                                    remat=True))
    ts_cfg = TrainStepConfig(opt=AdamWConfig(warmup_steps=1,
                                             total_steps=tsize.steps))
    params, opt = _place(abstract_train_state(tmodel, ts_cfg), sharding_of)
    batch = SyntheticLM(DataConfig(
        vocab_size=tcfg.vocab_size, seq_len=tsize.seq,
        global_batch=tsize.batch)).batch_at(0)
    batch = {k: jax.ShapeDtypeStruct(v.shape, jnp.asarray(v).dtype,
                                     sharding=one)
             for k, v in batch.items()}
    _compile(f"train step ({tsize.layers} layers, batch {tsize.batch}, "
             f"seq {tsize.seq})",
             lambda: jax.jit(make_train_step(tmodel, ts_cfg),
                             donate_argnums=(0, 1)).lower(params, opt, batch),
             chip_smoke.TRAIN_OPS)


# the benchmark's tp4 cell (benchmarks/chip/configs/codeqwen1.5-7b.tp4.json):
# CodeQwen1.5-7B whole, 32 layers and the published 4 KV heads, over 4 chips
TP4 = chip_smoke.ServeSize(layers=32, slots=32, max_len=8192, page=64,
                           requests=8)
TP4_POOL_PAGES = 4097


def rehearse_four_chips(topo, tp: int = 4, size=TP4) -> None:
    """The whole model at tensor parallel ``tp``: the sharded creation of
    params and pool, the decode step and every prefill width, per chip."""
    import dataclasses
    from repro.launch.mesh import make_mesh
    from repro.runtime import tp as tp_mod
    cfg = dataclasses.replace(
        chip_smoke._cut(get_arch(chip_smoke.ARCH), size.layers),
        n_kv_heads=4)
    model = chip_smoke.Model(cfg,
                             dt=chip_smoke.DtypePolicy(param=jnp.bfloat16),
                             opts=chip_smoke.ExecOptions(mode="run"))
    mesh = make_mesh((tp,), ("model",), devices=topo.devices[:tp])
    replicated = NamedSharding(mesh, jax.sharding.PartitionSpec())
    key = jax.random.key(0)
    _report(f"tp={tp} params drawn in their shardings",
            tp_mod.params_creator(model, mesh).lower(jax.ShapeDtypeStruct(
                key.shape, key.dtype, sharding=replicated)).compile())
    _report(f"tp={tp} pool of {TP4_POOL_PAGES} pages in its shardings",
            tp_mod.paged_cache_creator(model, mesh, size.slots,
                                       size.max_len, size.page,
                                       TP4_POOL_PAGES).lower().compile())

    def sharding_of(tree):
        if tree is None:
            return replicated
        if "embed" in tree:
            specs = tp_mod.param_pspecs(tree, cfg, tp)
        else:
            specs = tp_mod.cache_pspecs(tree, cfg, tp)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    decode, prefill = tp_mod.sharded_paged_fns(model, mesh)
    decode_args, _ = _serve_specs(model, size, 1, sharding_of,
                                  TP4_POOL_PAGES)
    _compile(f"tp={tp} decode step ({size.slots} slots)",
             lambda: jax.jit(decode, donate_argnums=(1,))
             .lower(*decode_args), DECODE_OPS)
    for width in range(1, size.requests + 1):
        _, prefill_args = _serve_specs(model, size, width, sharding_of,
                                       TP4_POOL_PAGES)
        _compile(f"tp={tp} prefill step ({width} chunks)",
                 lambda: jax.jit(prefill, donate_argnums=(1,))
                 .lower(*prefill_args), PREFILL_OPS)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="compile CodeQwen1.5-7B whole at tp=4 on a 2x2 "
                         "mesh: sharded creation, decode, every prefill "
                         "width")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    _steer_to_tpu()
    if args.four_chips:
        rehearse_four_chips(topo)
    else:
        rehearse_one_chip(topo)


if __name__ == "__main__":
    main()
