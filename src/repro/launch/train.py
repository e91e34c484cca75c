"""End-to-end training driver.

Runs REAL training (any arch at its smoke or a custom reduced size on CPU;
full size on a TPU cluster) with the production stack: sharded step,
AdamW (+optional int8 moments / gradient compression), deterministic data
pipeline, atomic checkpoints, supervised restart, straggler watch.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \\
      --steps 100 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \\
      --smoke --steps 50 --inject-failures 17,31   # proves restore path
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import SHAPES, get_arch
from ..core.memory import DtypePolicy
from ..data.pipeline import DataConfig, SyntheticLM
from ..checkpoint.checkpoint import CheckpointManager
from ..models.transformer import ExecOptions, Model
from ..optim.adamw import AdamWConfig
from ..optim.compress import CompressorConfig
from ..runtime.fault_tolerance import FailureInjector, Supervisor
from ..runtime.sharding import make_rules, tree_shardings
from ..train.steps import TrainStepConfig, init_train_state, make_train_step
from .mesh import make_host_mesh


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--int8-moments", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--inject-failures", default="",
                    help="comma-separated steps to fail at (tests restore)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (with --smoke)")
    ap.add_argument("--dispatch", default="auto",
                    choices=("auto", "kernels", "reference"),
                    help="kernel routing for every hot matmul/attention "
                         "(repro.kernels.dispatch)")
    args = ap.parse_args(argv)

    from ..runtime.compile_cache import enable_compile_cache
    from ..tune.cache import preload as preload_tuned
    print(f"[compile-cache] {enable_compile_cache()}")
    preload_tuned(log=print)
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
        if args.d_model:
            cfg = dataclasses.replace(
                cfg, d_model=args.d_model, d_ff=4 * args.d_model)
    cfg = dataclasses.replace(cfg, dispatch=args.dispatch)
    print(f"[dispatch] policy={args.dispatch}")
    mesh = make_host_mesh()
    rules = make_rules(mesh, fsdp=True)
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.name} "
          f"({cfg.param_counts()['total']/1e6:.1f}M params)")

    opts = ExecOptions(mode="run", block_q=min(512, args.seq),
                       block_kv=min(512, args.seq), remat=True)
    model = Model(cfg, dt=DtypePolicy(), opts=opts)
    ts_cfg = TrainStepConfig(
        opt=AdamWConfig(lr=args.lr, int8_moments=args.int8_moments,
                        warmup_steps=max(10, args.steps // 20),
                        total_steps=args.steps),
        microbatches=args.microbatches,
        compress=CompressorConfig() if args.compress_grads else None)
    step_fn_raw = make_train_step(model, ts_cfg)

    params, opt = init_train_state(model, ts_cfg, jax.random.key(0))
    p_sh = tree_shardings(rules, params)
    o_sh = tree_shardings(rules, opt)
    params = jax.device_put(params, p_sh)
    opt = jax.device_put(opt, o_sh)
    jitted = jax.jit(step_fn_raw, donate_argnums=(0, 1))

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch,
                          input_mode=cfg.input_mode, d_model=cfg.d_model)
    data = SyntheticLM(data_cfg)

    ckpt = CheckpointManager(args.ckpt_dir, keep=3, async_save=False)
    injector = FailureInjector(
        [int(s) for s in args.inject_failures.split(",") if s]) \
        if args.inject_failures else None
    sup = Supervisor(ckpt, save_every=args.save_every, injector=injector)

    losses = []

    def one_step(state, step):
        params, opt = state
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        if cfg.mrope_sections:
            b, s = batch["labels"].shape
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(s)[None, :, None],
                (b, s, len(cfg.mrope_sections))).astype(jnp.int32)
        params, opt, metrics = jitted(params, opt, batch)
        return (params, opt), metrics

    def on_metrics(step, metrics):
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0:
            print(f"step {step:5d}  loss {loss:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}")

    from ..kernels import dispatch
    dispatch.reset_stats()
    t0 = time.time()
    (params, opt), final = sup.run((params, opt), one_step, args.steps,
                                   on_metrics=on_metrics)
    dt = time.time() - t0
    tok_s = args.steps * args.batch * args.seq / dt
    print(f"done: {final} steps in {dt:.1f}s ({tok_s:,.0f} tok/s); "
          f"loss {losses[0]:.3f} -> {np.mean(losses[-5:]):.3f}; "
          f"restarts={sup.restarts} stragglers={len(sup.stragglers.flags)}")
    # route probe: counters are trace-time, so one jit compile of the step
    # is enough to prove which lowerings the train graph flowed through
    routes = dispatch.stats()
    print("[dispatch] routes: "
          + (", ".join(f"{op}/{r}={n}" for (op, r), n in sorted(
              routes.items())) or "none"))
    if args.dispatch == "kernels" and routes.get(("attention", "kernel"), 0):
        assert routes.get(("attention_bwd", "kernel"), 0) > 0, (
            "dispatch=kernels train step did not route the attention "
            f"backward through the fused Pallas kernel: {routes}")
    return losses


if __name__ == "__main__":
    main()
