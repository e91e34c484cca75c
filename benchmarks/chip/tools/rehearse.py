"""Compile a configuration's steps for a described TPU v5e, on the CPU.

    JAX_PLATFORMS=cpu python benchmarks/chip/tools/rehearse.py \\
        codeqwen1.5-7b.serve [--widths 1,8] [--pool-pages N]
    JAX_PLATFORMS=cpu python benchmarks/chip/tools/rehearse.py \\
        codeqwen1.5-7b.train --batch 2

Each step is compiled at its real size from shapes alone and its
``memory_analysis()`` printed, with the number of Pallas kernels and the
op routes.  A refusal here costs no chip time; nothing runs, so this says
nothing about results or speed.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import cells, program  # noqa: E402

GiB = 2 ** 30


def steer_to_tpu() -> None:
    """The program picks kernels and plans by ``jax.default_backend()``,
    which is the CPU here; steer it to its TPU choices."""
    from repro.kernels import dispatch
    from repro.kernels.attention import ops as attention_ops
    from repro.kernels.matmul import ops as matmul_ops
    from repro.tune import cache as plan_cache
    dispatch._kernels_by_default = lambda: True
    plan_cache._backend_name = lambda backend=None: backend or "tpu"
    attention_ops.interpret_default = lambda: False
    matmul_ops.interpret_default = lambda: False


def report(name, lower) -> int:
    from repro.kernels import dispatch
    with dispatch.stats_scope() as stats:
        compiled = lower().compile()
        routes = stats()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"{name}: {need / GiB:.2f} GiB (args "
          f"{ma.argument_size_in_bytes / GiB:.2f}, temps "
          f"{ma.temp_size_in_bytes / GiB:.2f}, aliased "
          f"{ma.alias_size_in_bytes / GiB:.2f}); tpu_custom_call x"
          f"{compiled.as_text().count('tpu_custom_call')}; routes "
          f"{dict(sorted(routes.items()))}", flush=True)
    return need


def place(tree, one):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)


def serve(config, one, widths, pool_pages):
    from repro.core.memory import DtypePolicy
    from repro.models.transformer import ExecOptions, Model
    sv = config["serve"]
    model = Model(program.arch_config(config),
                  dt=DtypePolicy(param=program.dtype(
                      config["program"]["param_dtype"])),
                  opts=ExecOptions(mode="run"))
    pages = pool_pages or sv["pool_pages"]
    params = place(jax.eval_shape(model.init, jax.random.key(0)), one)
    cache = place(jax.eval_shape(lambda: model.init_paged_cache(
        sv["slots"], sv["max_len"], sv["page"], total_pages=pages)), one)
    n_pages = sv["max_len"] // sv["page"]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    print(f"pool pages {pages}", flush=True)
    report("decode step", lambda: jax.jit(
        model.decode_step, donate_argnums=(1,)).lower(
        params, cache, {"tokens": i32(sv["slots"], 1)}, i32(),
        (i32(sv["slots"]), i32(sv["slots"], n_pages))))
    for b in widths:
        report(f"prefill step x{b}", lambda: jax.jit(
            model.prefill_step_paged, donate_argnums=(1,)).lower(
            params, cache, i32(b, sv["page"]), i32(b), i32(b, n_pages),
            i32(b)))


def train(config, mix, one, batch):
    from repro.core.memory import DtypePolicy
    from repro.models.transformer import ExecOptions, Model
    from repro.optim.adamw import AdamWConfig
    from repro.train.steps import (TrainStepConfig, abstract_train_state,
                                   make_train_step)
    tr = config["train"]
    model = Model(program.arch_config(config), dt=DtypePolicy(),
                  opts=ExecOptions(mode="run", block_q=tr["block_q"],
                                   block_kv=tr["block_kv"],
                                   remat=tr["remat"]))
    ts = TrainStepConfig(opt=AdamWConfig(**tr["optimizer"]))
    params, opt = place(abstract_train_state(model, ts), one)
    b = batch or mix["batch"]
    tok = jax.ShapeDtypeStruct((b, mix["seq"]), jnp.int32, sharding=one)
    report(f"train step batch {b} seq {mix['seq']}", lambda: jax.jit(
        make_train_step(model, ts), donate_argnums=(0, 1)).lower(
        params, opt, {"tokens": tok, "labels": tok}))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--widths", default="1,8")
    ap.add_argument("--pool-pages", type=int, default=0)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--mix", default="seq4k")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    steer_to_tpu()
    config = cells.load_json(cells.BENCH_DIR / "configs"
                             / f"{args.config}.json")
    if "serve" in config:
        serve(config, one, [int(w) for w in args.widths.split(",")],
              args.pool_pages)
    else:
        train(config, cells.load_json(cells.BENCH_DIR / "traffic"
                                      / f"{args.mix}.json"), one, args.batch)


if __name__ == "__main__":
    main()
