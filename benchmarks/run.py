"""Benchmark harness — one section per paper table/figure.

The paper's evaluation (Fig. 7) is a staged-transformation progression for
three kernels (stencil, matmul, N-body).  This harness reproduces that
structure on the TPU-adapted kernels:

* ``us_per_call`` — measured wall time of each stage's lowering on THIS
  host (single-core XLA-CPU; Pallas stages in interpret mode time their
  pure-jnp lowering instead, since interpret mode measures the Python
  emulator, not the kernel).  Measured numbers order the stages; absolute
  values are CPU numbers.
* ``derived`` — the §1.2 pipeline model + roofline terms evaluated for
  TPU v5e (DESIGN.md §7): derived_us = max(compute, memory) time for one
  call at that stage's parallelism.  This is the column comparable to the
  paper's FPGA numbers.

Output: ``name,us_per_call,derived`` CSV rows (assignment contract).

``--tune`` mode instead sweeps the repro.tune design space for all five
Pallas kernels (two problem shapes each by default), persists the winners
in the JSON plan cache (``results/tuned_plans.json``, or ``--tune-cache``),
and emits ``kernel,shape,dtype,backend,heuristic_us,tuned_us,speedup,plan``
CSV rows plus a full report at ``--tune-out`` (default
``results/BENCH_tune.json``).  Because the heuristic plan is always
candidate 0 of each sweep, tuned_us <= heuristic_us within a sweep's own
measurements — the tuned column never regresses beyond timer noise.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.model import TPU_V5E, PipelineModel
from repro.core.plan import Level
from repro.kernels.attention import flash_attention
from repro.kernels.histogram import histogram
from repro.kernels.matmul import matmul
from repro.kernels.nbody import nbody_accel
from repro.kernels.stencil import jacobi4
from repro.runtime.compile_cache import enable_compile_cache

HW = TPU_V5E
ROWS = []


def _time(fn: Callable, *args, reps: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def emit(name: str, us: float, derived: float):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived:.3f}", flush=True)


# ------------------------------------------------------------------ derived
def derived_matmul_us(n, k, m, level: Level) -> float:
    flops = 2.0 * n * k * m
    bytes_ = 2.0 * (n * k + k * m + n * m)
    if level == Level.T0_NAIVE:
        # loop-carried dependency: I = L_acc cycles per MAC on one unit
        l_acc = 6
        return PipelineModel(64, l_acc, flops / 2).seconds(HW.clock_hz) * 1e6
    if level == Level.T1_PIPELINED:
        macs_per_cycle = 1.0          # I=1, one MAC pipeline
    elif level == Level.T2_VECTORIZED:
        macs_per_cycle = 8 * 128      # full VPU (§3.1)
    else:
        macs_per_cycle = HW.peak_flops / 2 / HW.clock_hz  # MXUs (§3.2)
    compute = PipelineModel(
        128, 1, flops / 2 / macs_per_cycle).seconds(HW.clock_hz)
    memory = bytes_ / HW.hbm_bw
    return max(compute, memory) * 1e6


def derived_stencil_us(rows, cols, level: Level) -> float:
    cells = float(rows) * cols
    flops = 4.0 * cells
    if level == Level.T0_NAIVE:
        bytes_ = 6 * 4.0 * cells      # no reuse: 5 reads + 1 write (§6.1)
        compute = PipelineModel(32, 4, cells).seconds(HW.clock_hz)
    elif level in (Level.T1_PIPELINED, Level.T2_VECTORIZED):
        bytes_ = 2 * 4.0 * cells      # delay buffer (§2.2): 1R + 1W
        compute = flops / (2 * 8 * 128 * HW.clock_hz)
    else:
        # T3: P=32 timesteps fused through VMEM (§3.3 systolic replication)
        bytes_ = 2 * 4.0 * cells / 32
        compute = flops / (2 * 8 * 128 * HW.clock_hz)
    memory = bytes_ / HW.hbm_bw
    return max(compute, memory) * 1e6


def derived_nbody_us(n, level: Level) -> float:
    pairs = float(n) * n
    flops_per_pair = 20.0
    if level == Level.T0_NAIVE:
        # serial FLOPs per pair + L_acc-cycle accumulate dependency
        t = PipelineModel(64, flops_per_pair / 2 + 6,
                          pairs).seconds(HW.clock_hz)
        return max(t, pairs * 16 / HW.hbm_bw) * 1e6   # (N,N) spills
    if level == Level.T1_PIPELINED:
        lanes = 1.0
    elif level == Level.T2_VECTORIZED:
        lanes = 8 * 128 / 4.0          # rsqrt limits vector issue
    else:
        lanes = 8 * 128                # resident targets (§3.2) full VPU
    compute = PipelineModel(
        128, 1, pairs * flops_per_pair / (2 * lanes)).seconds(HW.clock_hz)
    memory = 16.0 * n / HW.hbm_bw      # positions+masses stream once
    return max(compute, memory) * 1e6


# --------------------------------------------------------------- benchmarks
def bench_matmul():
    n = k = m = 256
    a = jax.random.normal(jax.random.key(0), (n, k), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (k, m), jnp.float32)
    for level in (Level.T0_NAIVE, Level.T1_PIPELINED, Level.T2_VECTORIZED,
                  Level.T3_REPLICATED):
        if level in (Level.T2_VECTORIZED, Level.T3_REPLICATED):
            us = _time(lambda: matmul(a, b, level=Level.T1_PIPELINED))
        else:
            us = _time(lambda: matmul(a, b, level=level), reps=3)
        emit(f"matmul_{level.name}", us,
             derived_matmul_us(8192, 8192, 8192, level))


def bench_stencil():
    x = jax.random.normal(jax.random.key(0), (256, 512), jnp.float32)
    for level in (Level.T0_NAIVE, Level.T1_PIPELINED, Level.T3_REPLICATED):
        us = _time(lambda: jacobi4(
            x, steps=1,
            level=Level.T1_PIPELINED if level != Level.T0_NAIVE
            else Level.T0_NAIVE))
        emit(f"stencil_{level.name}", us,
             derived_stencil_us(8192, 8192, level))


def bench_nbody():
    n = 512
    pos = jax.random.normal(jax.random.key(0), (3, n), jnp.float32)
    mass = jax.random.uniform(jax.random.key(1), (n,)) + 0.1
    for level in (Level.T0_NAIVE, Level.T1_PIPELINED, Level.T3_REPLICATED):
        us = _time(lambda: nbody_accel(pos, mass,
                                       level=Level.T1_PIPELINED), reps=3)
        emit(f"nbody_{level.name}", us, derived_nbody_us(16128, level))


def bench_histogram():
    vals = jax.random.randint(jax.random.key(0), (1 << 16,), 0, 256,
                              jnp.int32)
    us = _time(lambda: histogram(vals, 256, level=Level.T1_PIPELINED))
    n = float(1 << 20)
    derived = max(n * 4 / HW.hbm_bw,
                  n * 256 * 2 / HW.peak_flops) * 1e6     # one-hot MXU
    emit("histogram_onehot_mxu", us, derived)


def bench_flash_attention():
    b, h, s, hd = 1, 4, 256, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, hd), jnp.bfloat16)
               for kk in ks)
    us = _time(lambda: flash_attention(q, k, v, level=Level.T1_PIPELINED))
    S, HD, H = 4096, 128, 32
    flops = 2 * 2 * H * (S * S / 2) * HD
    derived = max(flops / HW.peak_flops,
                  (3 * S * H * HD * 2) / HW.hbm_bw) * 1e6
    emit("flash_attention_causal_4k", us, derived)


def bench_lm_train_step():
    from repro.configs import get_arch
    from repro.models.transformer import ExecOptions, Model
    from repro.optim.adamw import AdamWConfig
    from repro.train.steps import (TrainStepConfig, init_train_state,
                                   make_train_step)
    for arch in ("gemma-2b", "qwen2-moe-a2.7b", "rwkv6-7b"):
        cfg = get_arch(arch).smoke()
        model = Model(cfg, opts=ExecOptions(mode="run", block_q=32,
                                            block_kv=32))
        ts = TrainStepConfig(opt=AdamWConfig())
        params, opt = init_train_state(model, ts, jax.random.key(0))
        step = jax.jit(make_train_step(model, ts))
        batch = {"labels": jax.random.randint(jax.random.key(2), (2, 64), 0,
                                              cfg.vocab_size)}
        if cfg.input_mode == "embeddings":
            batch["embeddings"] = jax.random.normal(
                jax.random.key(1), (2, 64, cfg.d_model), jnp.bfloat16)
        else:
            batch["tokens"] = jax.random.randint(
                jax.random.key(1), (2, 64), 0, cfg.vocab_size)
        if cfg.mrope_sections:
            batch["positions"] = jnp.zeros(
                (2, 64, len(cfg.mrope_sections)), jnp.int32)

        def run(p, o):
            p2, o2, m = step(p, o, batch)
            return m["loss"]

        us = _time(run, params, opt, reps=3)
        emit(f"lm_train_step_{arch}-smoke", us, float("nan"))


def run_tune(args) -> None:
    """--tune: sweep the transformation design space, persist best plans."""
    from repro.tune import DEFAULT_SHAPES, Harness, PlanCache, tune

    cache = PlanCache(args.tune_cache).load()
    harness = Harness(reps=args.tune_reps, warmup=1)
    results = []
    print("kernel,shape,dtype,backend,heuristic_us,tuned_us,speedup,plan")
    for kernel, shapes in DEFAULT_SHAPES.items():
        for shape in shapes:
            res = tune(kernel, shape, cache=cache, harness=harness)
            results.append(res.to_dict())
            shape_s = "x".join(map(str, shape))
            plan_s = ";".join(f"{k}={v}" for k, v in sorted(
                res.best.items()))
            print(f"{kernel},{shape_s},{res.dtype},{res.backend},"
                  f"{res.heuristic_us:.1f},{res.best_us:.1f},"
                  f"{res.speedup:.2f},{plan_s}", flush=True)
    path = cache.save()
    out = Path(args.tune_out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"cache": str(path), "results": results}, indent=2) + "\n")
    print(f"# plan cache: {path} ({len(cache)} entries)")
    print(f"# report: {out}")


def run_train_grad(args) -> None:
    """--train-grad: attention-backward timing rows, fused vs reference.

    Times ``flash_attention_bwd`` on fixed (q, k, v, o, lse, do) cells for
    both schedules: the dense reference VJP (level T1 — the stash
    schedule) and the fused recompute Pallas kernels (level T3).  On this
    CPU host the fused column times the interpret-mode emulator, so the
    rows order the *lowerings*; re-run on TPU for real trajectories.
    """
    from repro.core.plan import Level
    from repro.kernels.attention import flash_attention, flash_attention_bwd

    rows = []
    print("shape,dtype,reference_us,fused_us,ratio")
    for shape in ((1, 2, 128, 64), (1, 4, 256, 64)):
        for dtype in (jnp.float32, jnp.bfloat16):
            ks = jax.random.split(jax.random.key(0), 4)
            q, k, v = (jax.random.normal(kk, shape, dtype) for kk in ks[:3])
            do = jax.random.normal(ks[3], shape, jnp.float32)
            o, lse = flash_attention(q, k, v, level=Level.T1_PIPELINED,
                                     plan=None, return_residuals=True)
            ref_us = _time(lambda: flash_attention_bwd(
                q, k, v, o, lse, do, plan={"level": 1}), reps=3)
            s = shape[2]
            fused_us = _time(lambda: flash_attention_bwd(
                q, k, v, o, lse, do,
                plan={"level": 3, "block_q": min(128, s),
                      "block_kv": min(128, s)}), reps=3)
            shape_s = "x".join(map(str, shape))
            dname = jnp.dtype(dtype).name
            print(f"{shape_s},{dname},{ref_us:.1f},{fused_us:.1f},"
                  f"{ref_us / max(fused_us, 1e-9):.3f}", flush=True)
            rows.append({"shape": list(shape), "dtype": dname,
                         "reference_us": round(ref_us, 1),
                         "fused_us": round(fused_us, 1),
                         "backend": jax.default_backend()})
    out = Path(args.train_grad_out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"rows": rows}, indent=2) + "\n")
    print(f"# report: {out}")


def run_prefill(args) -> None:
    """--prefill: ragged multi-token prefill attention timing rows.

    Times ``prefill_attention`` on fixed paged-KV cells for both
    lowerings: the gather-and-mask reference (level T1) and the Pallas
    ragged kernel (level T3, heuristic KV-tile geometry).  On this CPU
    host the kernel column times the interpret-mode emulator, so the rows
    order the *lowerings*; re-run on TPU for real trajectories.
    """
    from repro.kernels import registry
    from repro.kernels.attention import prefill_attention

    spec = registry.get("prefill_attention")
    rows = []
    print("shape,dtype,reference_us,kernel_us,ratio")
    for shape in spec.tune.default_shapes:
        for dtype in (jnp.float32, jnp.bfloat16):
            args_ = spec.tune.make_inputs(tuple(shape), dtype)
            ref_us = _time(lambda: prefill_attention(
                *args_, plan={"level": 1}), reps=3)
            kern_us = _time(lambda: prefill_attention(
                *args_, plan={"level": 3}), reps=3)
            shape_s = "x".join(map(str, shape))
            dname = jnp.dtype(dtype).name
            print(f"{shape_s},{dname},{ref_us:.1f},{kern_us:.1f},"
                  f"{ref_us / max(kern_us, 1e-9):.3f}", flush=True)
            rows.append({"shape": list(shape), "dtype": dname,
                         "reference_us": round(ref_us, 1),
                         "kernel_us": round(kern_us, 1),
                         "backend": jax.default_backend()})
    out = Path(args.prefill_out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"rows": rows}, indent=2) + "\n")
    print(f"# report: {out}")


def _merge_serve_rows(path, new_rows) -> None:
    """Merge rows into the serve report keyed by (arch, cache, schedule),
    so --serve and --serve-continuous co-own one file: a re-run replaces
    its own keys and leaves the other mode's rows alone.  Legacy rows
    without a schedule field are the phased (--serve) rows."""
    def key(r):
        return (r.get("arch"), r.get("cache"), r.get("schedule", "phased"))
    out = Path(path)
    rows = []
    if out.exists():
        rows = json.loads(out.read_text()).get("rows", [])
    fresh = {key(r) for r in new_rows}
    rows = [r for r in rows if key(r) not in fresh] + new_rows
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"rows": rows}, indent=2) + "\n")
    print(f"# report: {out}")


def run_serve(args) -> None:
    """--serve: decode-throughput rows for the serving runtime.

    Times a small smoke-config workload on this host for both cache
    layouts: ``paged`` separates the prefill phase (chunked, one page per
    forward) from the decode phase (batched ragged steps through
    ``dispatch.decode_attention``); ``dense`` teacher-forces prompts
    through the decode step, so its tok/s column absorbs the prompt
    replay — the comparison the paged refactor exists to win.  Absolute
    numbers are CPU-interpret numbers; the row structure is what carries
    to TPU.
    """
    import numpy as np

    from repro.configs import get_arch
    from repro.core.memory import DtypePolicy
    from repro.kernels import dispatch
    from repro.launch.serve import PagedScheduler, Request, Server
    from repro.models.transformer import ExecOptions, Model
    from repro.tune.cache import preload as preload_tuned

    preload_tuned()
    cfg = get_arch(args.serve_arch).smoke()
    cfg = dataclasses.replace(cfg, dispatch=args.serve_dispatch)
    model = Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    slots, prompt_len, max_new, max_len = 2, 12, 8, 64

    def requests():
        rng = np.random.default_rng(0)
        return [Request(i, rng.integers(0, cfg.vocab_size, prompt_len),
                        max_new) for i in range(slots)]

    def warmup_request():
        rng = np.random.default_rng(99)
        return Request(-1, rng.integers(0, cfg.vocab_size, 4), 2)

    rows = []
    print("arch,cache,dispatch,slots,page_size,"
          "prefill_tok_s,decode_tok_s,decode_route")
    for kind in ("paged", "dense"):
        dispatch.reset_stats()
        if kind == "paged":
            sched = PagedScheduler(model, params, slots=slots,
                                   max_len=max_len,
                                   page_size=args.serve_page_size,
                                   log=None)
            # warmup: compile prefill_step_paged + decode_step on this
            # scheduler instance outside the timed regions
            sched.run([warmup_request()])
            sched.prefill_tokens = sched.decode_tokens = 0
            sched.decode_steps = 0
            reqs = requests()
            t0 = time.perf_counter()
            for i, r in enumerate(reqs):
                if not sched.try_admit(r, i):
                    raise RuntimeError(f"admission failed for request {i}")
            t_prefill = time.perf_counter() - t0
            t0 = time.perf_counter()
            done = sched.run([])
            t_decode = time.perf_counter() - t0
            page = sched.page
            prefill_tok_s = sched.prefill_tokens / max(t_prefill, 1e-9)
            decode_tok_s = sched.decode_tokens / max(t_decode, 1e-9)
        else:
            server = Server(model, params, slots=slots, max_len=max_len,
                            log=None)
            server.run([warmup_request()])     # compile decode_step
            reqs = requests()
            t0 = time.perf_counter()
            done = server.run(reqs)
            t_total = time.perf_counter() - t0
            page = 0
            prefill_tok_s = None           # prompts replay through decode
            decode_tok_s = sum(len(r.out) for r in done) \
                / max(t_total, 1e-9)
        if len(done) != slots:
            raise RuntimeError(
                f"{kind} serve finished {len(done)}/{slots} requests")
        routes = dispatch.stats()
        # dense never calls dispatch.decode_attention at all — report n/a
        # rather than conflating "not exercised" with "reference taken"
        if kind == "dense":
            decode_route = "n/a"
        else:
            decode_route = ("kernel" if routes.get(("decode_attention",
                                                    "kernel"), 0) else
                            "reference")
        row = {"arch": cfg.name, "cache": kind, "schedule": "phased",
               "dispatch": args.serve_dispatch, "slots": slots,
               "page_size": page,
               "prefill_tok_s": None if prefill_tok_s is None
               else round(prefill_tok_s, 2),
               "decode_tok_s": round(decode_tok_s, 2),
               "decode_route": decode_route,
               "backend": jax.default_backend()}
        rows.append(row)
        pf = "" if prefill_tok_s is None else f"{prefill_tok_s:.2f}"
        print(f"{cfg.name},{kind},{args.serve_dispatch},{slots},{page},"
              f"{pf},{decode_tok_s:.2f},{decode_route}", flush=True)
    _merge_serve_rows(args.serve_out, rows)


def run_serve_continuous(args) -> None:
    """--serve-continuous: continuous-batching engine rows vs the static
    run-to-completion schedule.

    Drives the layered engine (loadgen -> policy -> executor -> metrics)
    on a seeded request stream and reports the serving-latency trio the
    engine exists to improve: TTFT p50/p99, per-token latency p50/p99
    (both on the wall virtual clock, in seconds), and decode throughput.
    A second leg replays the SAME stream through ``PagedScheduler.run``
    (schedule=static) so the rows carry a like-for-like total-throughput
    comparison; the continuous row's ``max_prefill_batch`` +
    ``prefill_route`` prove a multi-slot (B > 1) batched
    ``prefill_attention`` kernel forward actually fired.  Absolute
    numbers are CPU-interpret numbers; the row structure carries to TPU.
    """
    import numpy as np

    from repro.configs import get_arch
    from repro.core.memory import DtypePolicy
    from repro.kernels import dispatch
    from repro.launch.engine import ContinuousEngine
    from repro.launch.loadgen import Request, poisson_stream
    from repro.launch.serve import PagedScheduler
    from repro.models.transformer import ExecOptions, Model
    from repro.tune.cache import preload as preload_tuned

    preload_tuned()
    cfg = get_arch(args.serve_arch).smoke()
    cfg = dataclasses.replace(cfg, dispatch=args.serve_dispatch)
    model = Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    slots, prompt_len, max_new, max_len = 2, 12, 8, 64
    n_req, rate = args.serve_requests, args.serve_rate

    def stream():
        return poisson_stream(n_req, rate=rate, vocab_size=cfg.vocab_size,
                              prompt_len=prompt_len, max_new=max_new,
                              seed=0)

    def r6(v):
        return None if v is None else round(v, 6)

    def route(routes, op):
        return "kernel" if routes.get((op, "kernel"), 0) else "reference"

    # -------------------------------------------------- continuous leg
    sched = PagedScheduler(model, params, slots=slots, max_len=max_len,
                           page_size=args.serve_page_size, log=None)
    engine = ContinuousEngine(sched, token_budget=args.serve_token_budget,
                              clock="wall", log=None)
    dispatch.reset_stats()       # trace-time counters: count from warmup
    engine.warmup()
    t0 = time.perf_counter()
    done = engine.run(stream())
    dt = time.perf_counter() - t0
    if len(done) != n_req:
        raise RuntimeError(
            f"continuous serve finished {len(done)}/{n_req} requests")
    s = engine.metrics.summary()
    ex = engine.executor
    routes = dispatch.stats()
    total_new = sum(len(r.out) for r in done)
    cont_tok_s = total_new / max(dt, 1e-9)
    cont_row = {
        "arch": cfg.name, "cache": "paged", "schedule": "continuous",
        "dispatch": args.serve_dispatch, "slots": slots,
        "page_size": sched.page, "requests": n_req, "rate": rate,
        "token_budget": engine.policy.token_budget,
        "decode_tok_s": round(
            sched.decode_tokens / max(ex.t_decode, 1e-9), 2),
        "total_tok_s": round(cont_tok_s, 2),
        "ttft_p50_s": r6(s["ttft_p50"]),
        "ttft_p99_s": r6(s["ttft_p99"]),
        "tok_latency_p50_s": r6(s["tok_latency_p50"]),
        "tok_latency_p99_s": r6(s["tok_latency_p99"]),
        "max_prefill_batch": ex.max_prefill_batch,
        "prefill_route": route(routes, "prefill_attention"),
        "decode_route": route(routes, "decode_attention"),
        "rejected": sched.rejected,
        "backend": jax.default_backend(),
    }

    # ------------------------------------------------------ static leg
    sched2 = PagedScheduler(model, params, slots=slots, max_len=max_len,
                            page_size=args.serve_page_size, log=None)
    rng = np.random.default_rng(99)
    sched2.run([Request(-1, rng.integers(0, cfg.vocab_size, 4), 2)])
    sched2.prefill_tokens = sched2.decode_tokens = sched2.decode_steps = 0
    t0 = time.perf_counter()
    done2 = sched2.run(stream())      # arrivals ignored: admit-at-once
    dt2 = time.perf_counter() - t0
    if len(done2) != n_req:
        raise RuntimeError(
            f"static serve finished {len(done2)}/{n_req} requests")
    static_tok_s = sum(len(r.out) for r in done2) / max(dt2, 1e-9)
    static_row = {
        "arch": cfg.name, "cache": "paged", "schedule": "static",
        "dispatch": args.serve_dispatch, "slots": slots,
        "page_size": sched2.page, "requests": n_req,
        "total_tok_s": round(static_tok_s, 2),
        "backend": jax.default_backend(),
    }
    cont_row["speedup_vs_static"] = round(cont_tok_s / static_tok_s, 3)

    print("arch,schedule,dispatch,total_tok_s,decode_tok_s,"
          "ttft_p99_s,tok_latency_p99_s,max_prefill_batch,prefill_route")
    print(f"{cfg.name},continuous,{args.serve_dispatch},"
          f"{cont_row['total_tok_s']},{cont_row['decode_tok_s']},"
          f"{cont_row['ttft_p99_s']},{cont_row['tok_latency_p99_s']},"
          f"{cont_row['max_prefill_batch']},{cont_row['prefill_route']}",
          flush=True)
    print(f"{cfg.name},static,{args.serve_dispatch},"
          f"{static_row['total_tok_s']},,,,,", flush=True)
    print(f"# continuous/static total throughput: "
          f"{cont_row['speedup_vs_static']:.3f}x")

    # ------------------------------------- shared-prefix scenarios
    # Cross-request KV reuse is the capacity lever prefix sharing exists
    # for, so it gets its own designed workload: a 4-slot engine over an
    # OVERSUBSCRIBED pool (11 pages vs 4 requests x 4 pages resident)
    # where each request is 24 prompt tokens, the sharing ones opening
    # with a common 16-token (2-page) prefix.  Swept at 0/50/95% sharing:
    # the 0% row is the capacity/throughput floor, and check_bench
    # requires the 95% row to beat it on BOTH requests-resident
    # (max_resident) and effective prefill throughput
    # (prompt tokens served / prefill wall time — skipped chunks are
    # served work that cost no compute).
    def share_run(model_, params_, frac, tag, kv_dtype):
        sh = PagedScheduler(model_, params_, slots=4, max_len=64,
                            page_size=8, total_pages=11,
                            prefix_cache=True, log=None)
        eng = ContinuousEngine(sh, clock="wall", log=None)
        eng.warmup()
        reqs = poisson_stream(12, rate=0.0, vocab_size=cfg.vocab_size,
                              prompt_len=24, max_new=8, seed=0,
                              shared_prefix_len=16, shared_frac=frac)
        prompt_tokens = sum(len(r.prompt) for r in reqs)
        t0 = time.perf_counter()
        sdone = eng.run(reqs)
        sdt = time.perf_counter() - t0
        if len(sdone) != 12:
            raise RuntimeError(
                f"{tag} finished {len(sdone)}/12 requests")
        sh.check_page_accounting()
        sm = eng.metrics.summary()
        eff = prompt_tokens / max(eng.executor.t_prefill, 1e-9)
        row = {
            "arch": cfg.name, "cache": "paged", "schedule": tag,
            "dispatch": args.serve_dispatch, "slots": 4, "page_size": 8,
            "total_pages": 11, "requests": 12, "shared_frac": frac,
            "shared_prefix_len": 16, "kv_dtype": kv_dtype,
            "decode_tok_s": round(
                sh.decode_tokens / max(eng.executor.t_decode, 1e-9), 2),
            "total_tok_s": round(
                sum(len(r.out) for r in sdone) / max(sdt, 1e-9), 2),
            "prefill_tok_s_effective": round(eff, 2),
            "max_resident": eng.max_resident,
            "max_resident_kv_bytes": eng.max_resident_kv_bytes,
            "shared_tokens": sh.shared_tokens_total,
            "cow_copies": sh.cow_copies,
            "prefix_hits": sh.prefix.hits,
            "ttft_p50_s": r6(sm["ttft_p50"]),
            "ttft_p99_s": r6(sm["ttft_p99"]),
            "tok_latency_p50_s": r6(sm["tok_latency_p50"]),
            "tok_latency_p99_s": r6(sm["tok_latency_p99"]),
            "rejected": sh.rejected, "truncated": sh.truncated,
            "backend": jax.default_backend(),
        }
        print(f"{cfg.name},{tag},{frac},{row['max_resident']},"
              f"{row['max_resident_kv_bytes']},"
              f"{row['prefill_tok_s_effective']},{row['shared_tokens']},"
              f"{row['cow_copies']},{row['total_tok_s']}", flush=True)
        return row

    share_rows = []
    print("\narch,schedule,shared_frac,max_resident,max_resident_kv_bytes,"
          "prefill_tok_s_effective,shared_tokens,cow_copies,total_tok_s")
    for frac in (0.0, 0.5, 0.95):
        share_rows.append(share_run(
            model, params, frac, f"continuous-share{int(frac * 100)}",
            cfg.kv_dtype or "compute"))
    hi = share_rows[-1]
    lo = share_rows[0]
    print(f"# share95/share0: resident {lo['max_resident']} -> "
          f"{hi['max_resident']}, effective prefill "
          f"{hi['prefill_tok_s_effective'] / max(lo['prefill_tok_s_effective'], 1e-9):.2f}x")

    # ------------------------------------- quantized-KV scenarios
    # Same oversubscribed shared-prefix workload with the pool stored as
    # int8 (per-(page, kv-head) f32 scales, in-kernel dequant): the
    # capacity lever is BYTES, so the rows carry max_resident_kv_bytes
    # and check_bench gates int8-share0 strictly below share0 on bytes
    # while holding decode throughput within tolerance.  Params are the
    # same tree — kv_dtype only changes cache storage, which is exactly
    # why the rows are comparable.
    cfg8 = dataclasses.replace(cfg, kv_dtype="int8")
    model8 = Model(cfg8, dt=DtypePolicy(param=jnp.bfloat16),
                   opts=ExecOptions(mode="run"))
    int8_rows = [share_run(model8, params, frac,
                           f"continuous-int8-share{int(frac * 100)}", "int8")
                 for frac in (0.0, 0.95)]
    b0, b8 = share_rows[0], int8_rows[0]
    print(f"# int8-share0/share0: kv bytes {b0['max_resident_kv_bytes']} "
          f"-> {b8['max_resident_kv_bytes']} "
          f"({b8['max_resident_kv_bytes'] / max(b0['max_resident_kv_bytes'], 1): .2f}x), "
          f"decode {b0['decode_tok_s']} -> {b8['decode_tok_s']} tok/s")
    _merge_serve_rows(args.serve_out,
                      [cont_row, static_row] + share_rows + int8_rows)


def run_serve_speculative(args) -> None:
    """--serve-speculative: speculative-decoding rows (continuous-spec*).

    Differential-first, like the TP rows: the headline field is
    ``tokens_match_baseline`` — greedy streams from the speculative
    engine compared token-for-token against a plain continuous engine on
    the identically regenerated seeded stream.  One baseline leg, then
    one leg per drafter (model-free n-gram; small-model early-exit
    sibling sharing the target's leading layers), each on a FRESH
    scheduler so no KV state leaks between legs.  Rows carry decode
    throughput vs baseline, the acceptance rate, and emitted tokens per
    verify step; ``scripts/check_bench.py compare_spec`` gates on them
    without a stored-baseline file.  Absolute numbers are CPU-interpret
    numbers — on real accelerators the verify step's extra width is
    nearly free next to its weight traffic (the paper's §2.1.4
    cross-input pipelining argument), which is the speedup lever.
    """
    from repro.configs import get_arch
    from repro.core.memory import DtypePolicy
    from repro.launch.engine import ContinuousEngine
    from repro.launch.loadgen import poisson_stream
    from repro.launch.serve import PagedScheduler
    from repro.launch.speculative import make_drafter
    from repro.models.transformer import ExecOptions, Model
    from repro.tune.cache import preload as preload_tuned

    preload_tuned()
    cfg = get_arch(args.serve_arch).smoke()
    cfg = dataclasses.replace(cfg, dispatch=args.serve_dispatch)
    model = Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
                  opts=ExecOptions(mode="run"))
    params = model.init(jax.random.key(0))
    slots, prompt_len, max_new, max_len = 2, 12, 8, 64
    n_req, draft = args.serve_requests, args.serve_draft_tokens

    def leg(drafter):
        sched = PagedScheduler(model, params, slots=slots, max_len=max_len,
                               page_size=args.serve_page_size, log=None)
        eng = ContinuousEngine(sched, clock="wall", drafter=drafter,
                               log=None)
        eng.warmup()
        reqs = poisson_stream(n_req, rate=args.serve_rate,
                              vocab_size=cfg.vocab_size,
                              prompt_len=prompt_len, max_new=max_new,
                              seed=0)
        done = eng.run(reqs)
        if len(done) != n_req:
            raise RuntimeError(
                f"speculative serve finished {len(done)}/{n_req} requests")
        streams = {r.rid: list(r.out) for r in done}
        emitted = (sched.spec_emitted if drafter is not None
                   else sched.decode_tokens)
        return streams, round(emitted / max(eng.executor.t_decode, 1e-9),
                              2), sched

    base_streams, base_tok_s, _ = leg(None)
    rows = []
    print("arch,schedule,drafter,decode_tok_s,baseline_decode_tok_s,"
          "accept_rate,toks_per_step,tokens_match_baseline")
    for kind in ("ngram", "model"):
        drafter = make_drafter(
            kind, cfg, max_draft=draft,
            dt=DtypePolicy(param=jnp.bfloat16), rng_key=jax.random.key(0),
            pad_to=max_len + draft, batch_pad=slots)
        streams, tok_s, sched = leg(drafter)
        match = streams == base_streams
        rows.append({
            "arch": cfg.name, "cache": "paged",
            "schedule": f"continuous-spec{kind}",
            "dispatch": args.serve_dispatch, "slots": slots,
            "page_size": sched.page, "requests": n_req,
            "drafter": kind, "draft_tokens": draft,
            "decode_tok_s": tok_s,
            "baseline_decode_tok_s": base_tok_s,
            "speedup_vs_baseline": round(tok_s / max(base_tok_s, 1e-9), 3),
            "acceptance_rate": round(
                sched.spec_accepted / max(sched.spec_drafted, 1), 4),
            "accepted_per_step": round(
                sched.spec_emitted / max(sched.verify_steps, 1), 3),
            "verify_steps": sched.verify_steps,
            "tokens_match_baseline": match,
            "backend": jax.default_backend(),
        })
        r = rows[-1]
        print(f"{cfg.name},{r['schedule']},{kind},{tok_s},{base_tok_s},"
              f"{r['acceptance_rate']},{r['accepted_per_step']},{match}",
              flush=True)
        if not match:
            raise RuntimeError(
                f"{kind} speculative streams diverged from baseline")
    print(f"# spec vs baseline decode: "
          f"ngram {rows[0]['speedup_vs_baseline']:.3f}x "
          f"(accept {rows[0]['acceptance_rate']:.2f}), "
          f"model {rows[1]['speedup_vs_baseline']:.3f}x "
          f"(accept {rows[1]['acceptance_rate']:.2f})")
    _merge_serve_rows(args.serve_out, rows)


def run_serve_sharded(args) -> None:
    """--serve-sharded: tensor-parallel serving rows (continuous-tp{1,2}).

    Differential-first: every row's headline field is
    ``tokens_match_oracle`` — the sharded continuous engine's greedy
    streams compared token-for-token against the unsharded single-device
    oracle on the same seeded request stream.  tp=1 runs on a degenerate
    1-device mesh (must be BIT-identical); tp=2 runs when >= 2 devices are
    visible (``XLA_FLAGS=--xla_force_host_platform_device_count=2`` on
    CPU) and additionally carries ``kernels_match_reference`` (the same
    sharded mesh with ``--dispatch reference`` produces the same tokens —
    the collectives are dispatch-route-invariant) and ``tp_ops_in_region``
    (distinct ops the tp route counters saw inside the shard_map body).
    ``scripts/check_bench.py compare_tp`` gates these fields baseline-free.
    Throughput columns are CPU-interpret numbers; the verdicts carry.
    """
    from repro.configs import get_arch
    from repro.core.memory import DtypePolicy
    from repro.kernels import dispatch, registry
    from repro.launch.engine import ContinuousEngine
    from repro.launch.loadgen import poisson_stream
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import PagedScheduler
    from repro.models.transformer import ExecOptions, Model
    from repro.runtime import tp as tp_mod
    from repro.tune.cache import preload as preload_tuned

    preload_tuned()
    base_cfg = get_arch(args.serve_arch).smoke()
    slots, prompt_len, max_new, max_len = 2, 12, 8, 64
    n_req = args.serve_requests

    def build(dispatch_policy):
        cfg = dataclasses.replace(base_cfg, dispatch=dispatch_policy)
        model = Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
                      opts=ExecOptions(mode="run"))
        return cfg, model, model.init(jax.random.key(0))

    def stream(vocab):
        return poisson_stream(n_req, rate=0.0, vocab_size=vocab,
                              prompt_len=prompt_len, max_new=max_new,
                              seed=0)

    def drive(model, params, mesh):
        """Run the continuous engine once; return (streams, row core)."""
        sched = PagedScheduler(model, params, slots=slots, max_len=max_len,
                               page_size=args.serve_page_size, mesh=mesh,
                               log=None)
        engine = ContinuousEngine(sched, clock="wall", log=None)
        dispatch.reset_stats()
        engine.warmup()
        t0 = time.perf_counter()
        done = engine.run(stream(model.cfg.vocab_size))
        dt = time.perf_counter() - t0
        if len(done) != n_req:
            raise RuntimeError(
                f"sharded serve finished {len(done)}/{n_req} requests")
        streams = [list(r.out)
                   for r in sorted(done, key=lambda r: r.rid)]
        core = {
            "decode_tok_s": round(
                sched.decode_tokens
                / max(engine.executor.t_decode, 1e-9), 2),
            "total_tok_s": round(
                sum(len(s) for s in streams) / max(dt, 1e-9), 2),
            "tp_ops_in_region": len({op for op, _
                                     in registry.tp_stats()}),
        }
        return streams, core

    cfgk, modelk, paramsk = build(args.serve_dispatch)
    n_dev = len(jax.devices())
    print(f"# {cfgk.name}: n_heads={cfgk.n_heads} "
          f"n_kv_heads={cfgk.n_kv_heads}, {n_dev} device(s) visible")
    oracle, _ = drive(modelk, paramsk, None)

    rows = []
    print("arch,schedule,tp,dispatch,tokens_match_oracle,"
          "kernels_match_reference,tp_ops_in_region,total_tok_s")
    tps = [1] + ([2] if n_dev >= 2 else [])
    if n_dev < 2:
        print("# only 1 device visible: skipping the tp=2 row (set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    for tp in tps:
        mesh = make_serving_mesh(tp)
        streams, core = drive(modelk, paramsk, mesh)
        row = {
            "arch": cfgk.name, "cache": "paged",
            "schedule": f"continuous-tp{tp}",
            "dispatch": args.serve_dispatch, "slots": slots,
            "page_size": args.serve_page_size, "requests": n_req,
            "tp": tp, "devices": n_dev,
            "kv_sharded": tp_mod.kv_sharded(cfgk, tp),
            "tokens_match_oracle": streams == oracle,
            "backend": jax.default_backend(),
            **core,
        }
        if tp >= 2 and args.serve_dispatch != "reference":
            # route-invariance on the mesh itself: reference lowerings
            # under the SAME shard_map + collectives give the same tokens
            _, modelr, paramsr = build("reference")
            ref_streams, _ = drive(modelr, paramsr, mesh)
            row["kernels_match_reference"] = streams == ref_streams
        rows.append(row)
        print(f"{cfgk.name},continuous-tp{tp},{tp},{args.serve_dispatch},"
              f"{row['tokens_match_oracle']},"
              f"{row.get('kernels_match_reference', '')},"
              f"{row['tp_ops_in_region']},{row['total_tok_s']}",
              flush=True)
    _merge_serve_rows(args.serve_out, rows)


def run_progression() -> None:
    print("name,us_per_call,derived")
    bench_stencil()
    bench_matmul()
    bench_nbody()
    bench_histogram()
    bench_flash_attention()
    bench_lm_train_step()
    # staged-progression summary (the Fig. 7 shape): cumulative derived
    # speedup of each stage over the naive one
    print("\n# derived TPU staged speedups (paper Fig. 7 analogue)")
    by = {}
    for name, us, derived in ROWS:
        for kern in ("stencil", "matmul", "nbody"):
            if name.startswith(kern):
                by.setdefault(kern, []).append((name, derived))
    for kern, stages in by.items():
        base = stages[0][1]
        prog = " | ".join(f"{n.split('_', 1)[1]}: {base / d:,.0f}x"
                          for n, d in stages)
        print(f"# {kern}: {prog}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tune", action="store_true",
                    help="sweep the repro.tune design space instead of the "
                         "Fig. 7 progression")
    ap.add_argument("--tune-cache", default=None,
                    help="plan-cache JSON path (default: "
                         "results/tuned_plans.json or $REPRO_TUNE_CACHE)")
    ap.add_argument("--tune-out", default="results/BENCH_tune.json",
                    help="tuned-vs-heuristic report JSON path")
    ap.add_argument("--tune-reps", type=int, default=3,
                    help="timing reps per candidate (median taken)")
    ap.add_argument("--train-grad", action="store_true",
                    help="attention-backward timing rows "
                         "(fused recompute kernel vs reference VJP)")
    ap.add_argument("--train-grad-out",
                    default="results/BENCH_train_grad.json",
                    help="backward-timing report JSON path")
    ap.add_argument("--prefill", action="store_true",
                    help="ragged prefill-attention timing rows "
                         "(Pallas kernel vs gather-and-mask reference)")
    ap.add_argument("--prefill-out", default="results/BENCH_prefill.json",
                    help="prefill-timing report JSON path")
    ap.add_argument("--serve", action="store_true",
                    help="serving-runtime decode-throughput rows "
                         "(paged vs dense cache)")
    ap.add_argument("--serve-arch", default="gemma-2b")
    ap.add_argument("--serve-dispatch", default="auto",
                    choices=("auto", "kernels", "reference"))
    ap.add_argument("--serve-page-size", type=int, default=8,
                    help="paged layout page size for the smoke workload "
                         "(0 = tuned-plan pick)")
    ap.add_argument("--serve-out", default="results/BENCH_serve.json",
                    help="serve-throughput report JSON path")
    ap.add_argument("--serve-continuous", action="store_true",
                    help="continuous-batching engine rows (TTFT + "
                         "per-token latency percentiles) vs the static "
                         "run-to-completion schedule")
    ap.add_argument("--serve-requests", type=int, default=6,
                    help="continuous workload size (requests)")
    ap.add_argument("--serve-rate", type=float, default=0.0,
                    help="continuous Poisson arrival rate "
                         "(0 = burst at t=0, deterministic)")
    ap.add_argument("--serve-token-budget", type=int, default=0,
                    help="continuous per-iteration token budget "
                         "(0 = slots x page_size)")
    ap.add_argument("--serve-speculative", action="store_true",
                    help="speculative-decoding rows: continuous-spec{ngram,"
                         "model} vs a plain continuous baseline on the "
                         "same seeded stream (streams must match exactly)")
    ap.add_argument("--serve-draft-tokens", type=int, default=3,
                    help="draft tokens per verify step (window = draft+1)")
    ap.add_argument("--serve-sharded", action="store_true",
                    help="tensor-parallel serving rows: continuous-tp1 "
                         "(degenerate mesh, bit-identical) and, with >= 2 "
                         "visible devices, continuous-tp2 (sharded heads + "
                         "KV pools vs the single-device oracle)")
    args = ap.parse_args(argv)
    print(f"[compile-cache] {enable_compile_cache()}")
    if args.tune:
        run_tune(args)
    elif args.train_grad:
        run_train_grad(args)
    elif args.prefill:
        run_prefill(args)
    elif args.serve:
        run_serve(args)
    elif args.serve_continuous:
        run_serve_continuous(args)
    elif args.serve_speculative:
        run_serve_speculative(args)
    elif args.serve_sharded:
        run_serve_sharded(args)
    else:
        run_progression()


if __name__ == "__main__":
    main()
