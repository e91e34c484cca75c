"""Train entry: the jitted ``make_train_step``, steps back to back.

Set-up builds one object, the compiled step with its state (f32 weights
and AdamW moments drawn on the device from the seed), and drives it
through its first ``check.steps`` steps on rows of its own, reading what
the check compares: each step's loss, the gradient the optimizer took at
step 1 (its first moment over ``1 - b1``), and the weights' change after
the last of them.  The window then runs the same object on, one step per
call, fresh rows every step, each step waited for.  The window ends when
the first step ending past ``--seconds`` completes.

After the window the float32 reference (``reference/train_check.py``)
repeats the first steps from the seed and the three gaps are compared.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import program, traffic
from chipbench import trace as tracing
from chipbench.device import key_for, memory_peak_bytes
from chipbench.harness import Check, Run, span
from reference import train_check

HOT_OPS = ("attention", "attention_bwd", "matmul", "matmul_bwd")


def build(run: Run):
    """The model, the step's config and its freshly drawn state."""
    from repro.core.memory import DtypePolicy
    from repro.models.transformer import ExecOptions, Model
    from repro.optim.adamw import AdamWConfig
    from repro.train.steps import TrainStepConfig, init_train_state
    cfg = run.cell.config
    tr = cfg["train"]
    model = Model(program.arch_config(cfg),
                  dt=DtypePolicy(param=program.dtype(
                      cfg["program"]["param_dtype"])),
                  opts=ExecOptions(mode="run", block_q=tr["block_q"],
                                   block_kv=tr["block_kv"],
                                   remat=tr["remat"]))
    ts = TrainStepConfig(opt=AdamWConfig(**tr["optimizer"]))
    params, opt = jax.jit(lambda k: init_train_state(model, ts, k))(
        key_for(run.seed))
    return model, ts, params, opt


def rows(run: Run, step: int) -> np.ndarray:
    return traffic.train_rows(run.cell.traffic, run.seed, step,
                              run.cell.config["vocab_size"])


def feed(run: Run, step: int) -> Dict[str, jax.Array]:
    r = jnp.asarray(rows(run, step))
    return {"tokens": r[:, :-1], "labels": r[:, 1:]}


def leaves(params) -> Dict[str, jax.Array]:
    """The program's tree under the reference's leaf names, each layer's
    matrices apart (the stacked layers' leading axis is the layer)."""
    out = {"ends.embed": params["embed"], "ends.head": params["head"],
           "ends.final_norm": params["final_norm"]["scale"]}
    stack = params["stack"][0]
    flat = {"ln1": stack["ln1"]["scale"], "ln2": stack["ln2"]["scale"],
            **stack["attn"], **stack["mlp"]}
    for name, a in flat.items():
        for i in range(a.shape[0]):
            out[f"layer{i}.{name}"] = a[i]
    return out


def norms(tree: Dict[str, jax.Array]) -> Dict[str, float]:
    got = jax.device_get({k: jnp.linalg.norm(v.reshape(-1).astype(
        jnp.float32)) for k, v in tree.items()})
    return {k: float(v) for k, v in got.items()}


def check_routes(routes) -> None:
    ref = {op: n for (op, r), n in routes.items()
           if r == "reference" and op in HOT_OPS}
    missing = [op for op in HOT_OPS if not routes.get((op, "kernel"))]
    if ref or missing:
        raise RuntimeError(f"hot ops off their kernels: reference {ref}, "
                           f"no kernel {missing}")


def setup(run: Run):
    """Compile the step and drive it through the checked steps.  Returns
    (step, params, opt, readings)."""
    from repro.kernels import dispatch
    from repro.train import steps
    model, ts, params, opt = build(run)
    with dispatch.stats_scope() as stats:
        step = jax.jit(steps.make_train_step(model, ts),
                       donate_argnums=(0, 1)).lower(
            params, opt, feed(run, 0)).compile()
        routes = stats()
    check_routes(routes)
    n = run.cell.traffic["check"]["steps"]
    read = {"losses": []}
    for i in range(n):
        params, opt, m = step(params, opt, feed(run, i))
        read["losses"].append(float(m["loss"]))
        if i == 0:
            b1 = ts.opt.b1
            read["grad_norms"] = {k: v / (1 - b1) for k, v in
                                  norms(leaves(opt.m)).items()}
    start = jax.jit(model.init)(key_for(run.seed))
    read["change"] = norms(jax.tree.map(
        jnp.subtract, leaves(params), leaves(start)))
    del start
    return step, params, opt, read


def window(run: Run, step, params, opt) -> None:
    """Steps back to back for ``--seconds``; each waited for."""
    mix = run.cell.traffic
    i = mix["check"]["steps"]
    done, traced = 0, 0
    if run.trace:
        jax.profiler.start_trace(run.record["trace_dir"])
        win = span("window")
        win.__enter__()
    t0 = time.perf_counter()
    while True:
        with span("train.step"):
            params, opt, m = step(params, opt, feed(run, i))
            loss = float(m["loss"])
        i += 1
        done += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= run.seconds:
            break
    if run.trace:
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced = done
    run.window_s = elapsed
    run.record.update(train_steps=done, traced_steps=traced,
                      train_tokens=done * mix["batch"] * mix["seq"])
    run.attempted = done
    run.failed = 0 if np.isfinite(loss) else 1
    run.memory_peak_bytes = memory_peak_bytes(run.devices)


def check(run: Run, read: Dict) -> None:
    mix = run.cell.traffic
    lim = mix["check"]
    n = lim["steps"]
    ref = train_check.run(key_for(run.seed), run.cell.config,
                          [rows(run, i) for i in range(n)], steps=n)
    g = train_check.gaps(read, ref)
    run.record["check"] = {"program": read, "reference": ref, "gaps": g}
    for k in ("loss_gap", "grad_norm_gap", "change_gap"):
        run.checks.append(Check(k, g[k], lim[k]))
    run.notes.append(f"losses program {read['losses']} reference "
                     f"{ref['losses']}; left out of the change: "
                     f"{g['left_out']}")


def drive(run: Run) -> None:
    """The entry point: set-up, window, check, and the trace if asked."""
    step, params, opt, read = setup(run)
    jax.block_until_ready(params)
    run.mark_setup_done()
    window(run, step, params, opt)
    del step, params, opt
    gc.collect()
    check(run, read)
    if run.trace:
        run.record["trace"] = tracing.load(run.record["trace_dir"])
