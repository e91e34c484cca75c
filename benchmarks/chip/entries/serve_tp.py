"""Serve entry on a tensor-parallel mesh: the whole model over the cell's
chips, through the same engine loop, window and check as ``serve``.

Set-up makes a ``("model",)`` mesh over the run's chips, draws the weights
from the seed directly in their shardings (``tp.init_params_sharded``:
each chip draws only its shard, with the values of the unsharded draw) and
builds ``PagedScheduler(mesh=...)``, which creates the KV pool sharded by
KV head.  No chip ever holds a whole copy of either.  Then, as the serve
entry does, it compiles the decode step and every prefill width, checks
that no hot op took its reference route, and reads the bytes that each
step's collectives move (``tp_collective_bytes``) before the window.

The window, the latencies, the sample and the check against the float32
reference are the serve entry's own; the reference runs on one chip once
the engine is freed.
"""
from __future__ import annotations

import gc
from pathlib import Path

import jax
import jax.numpy as jnp

from chipbench import program, traffic
from chipbench import trace as tracing
from chipbench.cells import load_module
from chipbench.device import CompileCounter, key_for, memory_peak_bytes
from chipbench.harness import Run

serve = load_module(Path(__file__).with_name("serve.py"))


def build(run: Run, **overrides):
    """The program at the configuration's sizes on a mesh of the run's
    chips, weights from the seed."""
    from repro.core.memory import DtypePolicy
    from repro.launch.engine import ContinuousEngine
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import PagedScheduler
    from repro.models.transformer import ExecOptions, Model
    from repro.runtime.tp import init_params_sharded
    cfg = run.cell.config
    sv = cfg["serve"]
    if sv["tp"] != len(run.devices):
        raise ValueError(f"{cfg['name']} serves at tp={sv['tp']} but the "
                         f"run has {len(run.devices)} chips")
    model = Model(program.arch_config(cfg, **overrides),
                  dt=DtypePolicy(param=program.dtype(
                      cfg["program"]["param_dtype"])),
                  opts=ExecOptions(mode="run"))
    mesh = make_mesh((sv["tp"],), ("model",), devices=run.devices)
    params = init_params_sharded(model, key_for(run.seed), mesh)
    sched = PagedScheduler(model, params, slots=sv["slots"],
                           max_len=sv["max_len"], page_size=sv["page"],
                           total_pages=sv["pool_pages"],
                           prefix_cache=sv["prefix_cache"], mesh=mesh,
                           log=None)
    return ContinuousEngine(sched, token_budget=sv["token_budget"],
                            clock="wall", log=None)


def read_collectives(engine) -> None:
    """Read each prefill width's collective bytes off its compiled step
    (the decode step's was read in its warm-up call), so that no step of
    the window lowers anything."""
    sched = engine.sched
    for b in serve.prefill_widths(engine):
        sched.collective_bytes(
            "prefill", jnp.zeros((b, sched.page), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, sched.n_slot_pages), jnp.int32),
            jnp.zeros((b,), jnp.int32))


def setup(run: Run, **overrides):
    """Build and warm the engine; no hot op may take its reference
    route."""
    from repro.kernels import dispatch
    with dispatch.stats_scope() as stats:
        engine = build(run, **overrides)
        serve.warm(engine)
        routes = stats()
    serve.check_routes(routes)
    read_collectives(engine)
    return engine


def drive(run: Run) -> None:
    """The entry point: set-up, window, check, and the trace if asked."""
    mix = run.cell.traffic
    compiles = CompileCounter()
    engine = setup(run)
    stream = traffic.serve_stream(mix, run.seed, run.seconds,
                                  run.cell.config["vocab_size"],
                                  horizon=run.seconds + mix["drain_cap_s"])
    run.mark_setup_done()
    w = serve.serve_window(run, engine, stream, compiles)
    run.memory_peak_bytes = memory_peak_bytes(run.devices)
    serve.summarize(run, stream, w)
    seqs = serve.sample(run, w)
    del engine, w
    gc.collect()
    serve.check(run, seqs)
    if run.trace:
        run.record["trace"] = tracing.load(run.record["trace_dir"])
