"""Serve entry: ``ContinuousEngine.step`` in a loop, on the harness's clock.

Set-up draws the weights on the device from the seed (one jitted call),
builds ``PagedScheduler`` + ``ContinuousEngine`` at the configuration's
sizes, and compiles only the shapes the mix uses: the decode step and the
prefill step at 1 .. token_budget / page chunks.

The window is an open loop on the real clock.  A request joins the engine's
queue once its due time has passed; before each ``engine.step()`` the
engine's clock is set to the real elapsed time; an idle engine sleeps until
the next request is due.  Every token is stamped when the step that made
it returns, and every latency is timed from the request's due time.  Load
stays on after the window closes until every request sent in it has
finished or ``drain_cap_s`` has passed.

Then the served tokens of a sample of the finished window requests are
checked against the float32 reference (``reference/serve_check.py``).  A
request still unfinished when the drain cap ends counts in ``failed``; it
is late, not wrong, so it does not decide ``correct``.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import program, traffic
from chipbench import trace as tracing
from chipbench.device import CompileCounter, key_for, memory_peak_bytes
from chipbench.harness import Check, Run, span
from reference import serve_check

HOT_OPS = ("decode_attention", "prefill_attention", "matmul")


def build(run: Run, **overrides):
    """The program at the configuration's sizes, weights from the seed."""
    from repro.core.memory import DtypePolicy
    from repro.launch.engine import ContinuousEngine
    from repro.launch.serve import PagedScheduler
    from repro.models.transformer import ExecOptions, Model
    cfg = run.cell.config
    sv = cfg["serve"]
    model = Model(program.arch_config(cfg, **overrides),
                  dt=DtypePolicy(param=program.dtype(
                      cfg["program"]["param_dtype"])),
                  opts=ExecOptions(mode="run"))
    params = jax.jit(model.init)(key_for(run.seed))
    sched = PagedScheduler(model, params, slots=sv["slots"],
                           max_len=sv["max_len"], page_size=sv["page"],
                           total_pages=sv["pool_pages"],
                           prefix_cache=sv["prefix_cache"], log=None)
    return ContinuousEngine(sched, token_budget=sv["token_budget"],
                            clock="wall", log=None)


def prefill_widths(engine) -> range:
    sched = engine.sched
    return range(1, min(sched.slots,
                        engine.policy.token_budget // sched.page) + 1)


def warm(engine) -> None:
    """Compile the decode step and each prefill width the budget allows;
    every write lands on the trash page."""
    sched = engine.sched
    for b in prefill_widths(engine):
        _, sched.cache = sched._prefill(
            sched.params, sched.cache, jnp.zeros((b, sched.page), jnp.int32),
            jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, sched.n_slot_pages), jnp.int32),
            jnp.full((b,), sched.page - 1, jnp.int32))
    zeros = np.zeros((sched.slots,), np.int32)
    sched.step(zeros, view=(zeros, np.zeros_like(sched.table)))
    jax.block_until_ready(sched.cache)
    sched.decode_steps = sched.decode_tokens = 0


def check_routes(routes) -> None:
    ref = {op: n for (op, r), n in routes.items()
           if r == "reference" and op in HOT_OPS}
    missing = [op for op in HOT_OPS if not routes.get((op, "kernel"))]
    if ref or missing:
        raise RuntimeError(f"hot ops off their kernels: reference {ref}, "
                           f"no kernel {missing}")


@dataclasses.dataclass
class Step:
    t0: float                 # seconds after the window opened
    t1: float
    prefill_s: float          # the executor's own host-clock step times
    decode_s: float
    decode_lengths: np.ndarray   # cached tokens of each decode row
    chunks: List[tuple]          # (start, real tokens) of each prefill chunk


class Recorder:
    """Wraps the engine's batch policy to see each step's plan."""

    def __init__(self, engine):
        self.lengths = None
        self.chunks = None
        inner = engine.policy.compose

        def compose(running, prefilling, drafts=None):
            plan = inner(running, prefilling, drafts=drafts)
            sched, states = engine.sched, engine.states
            self.lengths = sched.lengths[plan.decode].copy()
            self.chunks = [(st, min(states[s].ln, st + sched.page) - st)
                           for s, st in plan.prefill]
            return plan

        engine.policy.compose = compose
        ex = engine.executor
        for name in ("prefill", "decode"):
            setattr(ex, name, self._spanned(f"execute.{name}",
                                            getattr(ex, name)))

    @staticmethod
    def _spanned(name, fn):
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call


def serve_window(run: Run, engine, stream, compiles: CompileCounter
                 ) -> Dict:
    """Drive the engine over the stream; returns what the run recorded."""
    from repro.launch.loadgen import Request
    mix = run.cell.traffic
    seconds, cap = run.seconds, run.seconds + mix["drain_cap_s"]
    sched, ex = engine.sched, engine.executor
    rec = Recorder(engine)
    reqs: Dict[int, Request] = {}
    times: Dict[int, List[float]] = {}
    admitted: Dict[int, float] = {}
    live: Dict[int, Request] = {}
    steps: List[Step] = []
    window_rids = [r.rid for r in stream if r.due < seconds]
    pending = set(window_rids)
    nxt, n_admit, n_done, late = 0, 0, 0, 0.0
    compiles_before = compiles.count
    win = None
    if run.trace:
        jax.profiler.start_trace(run.record["trace_dir"])
        win = span("window")
        win.__enter__()
    t_zero = time.perf_counter()
    now = 0.0
    while True:
        now = time.perf_counter() - t_zero
        if win is not None and now >= seconds:
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            win = None
        if (now >= seconds and not pending) or now >= cap:
            break
        while nxt < len(stream) and stream[nxt].due <= now:
            s = stream[nxt]
            r = Request(s.rid, s.prompt, s.max_new, arrival=s.due)
            reqs[s.rid] = r
            engine.waiting.append(r)
            nxt += 1
        if not engine.waiting and all(a is None for a in sched.active):
            if nxt >= len(stream):
                break
            with span("wait"):
                time.sleep(max(0.0, stream[nxt].due - now))
            late = max(late, time.perf_counter() - t_zero - stream[nxt].due)
            continue
        engine.clock = now
        p0, d0 = ex.t_prefill, ex.t_decode
        with span("engine.step"):
            engine.step()
        t1 = time.perf_counter() - t_zero
        steps.append(Step(now, t1, ex.t_prefill - p0, ex.t_decode - d0,
                          rec.lengths, rec.chunks))
        for rid in engine.admission_order[n_admit:]:
            live[rid] = reqs[rid]
            admitted[rid] = engine.metrics.timelines[rid].admitted
        n_admit = len(engine.admission_order)
        for r in engine.done[n_done:]:
            live.setdefault(r.rid, r)
        for rid, r in list(live.items()):
            got = times.setdefault(rid, [])
            got += [t1] * (len(r.out) - len(got))
            if r.done:
                del live[rid]
                pending.discard(rid)
        n_done = len(engine.done)
    if win is not None:
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return {"reqs": reqs, "times": times, "admitted": admitted,
            "steps": steps, "window_rids": window_rids, "end": now,
            "late_s": late, "compiles": compiles.count - compiles_before,
            "truncated": sched.truncated, "rejected": sched.rejected}


def summarize(run: Run, stream, w: Dict) -> None:
    """Latencies of every request sent in the window, from its due time."""
    due = {s.rid: s.due for s in stream}
    ttft, itl, queue = [], [], []
    ok = 0
    for rid in w["window_rids"]:
        r, ts = w["reqs"].get(rid), w["times"].get(rid, [])
        # a request with no token yet has waited at least until the end
        ttft.append((ts[0] if ts else w["end"]) - due[rid])
        itl += [b - a for a, b in zip(ts, ts[1:])]
        queue.append(w["admitted"].get(rid, w["end"]) - due[rid])
        if r is not None and r.done and len(r.out) == r.max_new:
            ok += 1
    run.attempted = len(w["window_rids"])
    run.failed = run.attempted - ok
    run.window_s = run.seconds
    run.record.update(
        ttft_s=ttft, itl_s=itl, queue_wait_s=queue,
        tokens_in_window=sum(t <= run.seconds for ts in w["times"].values()
                             for t in ts),
        window_steps=[s for s in w["steps"] if s.t0 < run.seconds],
        slots=run.cell.config["serve"]["slots"])
    run.notes.append(
        f"requests {run.attempted} in the window, {ok} finished; "
        f"steps {len(w['steps'])}; compiles in the loop {w['compiles']}; "
        f"generator late by at most {w['late_s'] * 1e3:.1f} ms; "
        f"truncated {w['truncated']}, rejected {w['rejected']}")


def sample(run: Run, w: Dict) -> List[tuple]:
    """(prompt, served tokens) of the requests the check compares: the
    one with the most served tokens, and others drawn from the seed."""
    done = [w["reqs"][rid] for rid in w["window_rids"]
            if rid in w["reqs"] and w["reqs"][rid].done]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.out), len(r.prompt), -r.rid))
    longest, rest = done[-1], done[:-1]
    rows = run.cell.traffic["check"]["rows"]
    rng = traffic.rng_for(run.seed, 3)
    pick = rng.choice(len(rest), size=min(rows - 1, len(rest)),
                      replace=False) if rest else []
    return [(r.prompt, list(r.out)) for r in [longest] +
            [rest[i] for i in sorted(pick)]]


def check_shapes(mix: Dict):
    rows = mix["check"]["rows"]
    span_ = mix["output_len"]["max"]
    return rows, mix["prompt_len"]["max"] + span_, span_


def check(run: Run, seqs) -> None:
    """Compare the sampled requests' served tokens with the reference."""
    mix = run.cell.traffic
    if not seqs:
        run.checks.append(Check("max_gap", float("inf"),
                                mix["check"]["max_gap"]))
        return
    rows, length, span_ = check_shapes(mix)
    tokens, start, served, mask = serve_check.pad_batch(seqs, rows, length,
                                                        span_)
    gaps, _, _ = serve_check.forward(key_for(run.seed), run.cell.config,
                                     tokens, start, served)
    run.notes.append(f"check compared {int(mask.sum())} served tokens of "
                     f"{len(seqs)} requests")
    run.checks.append(Check("max_gap", serve_check.widest_gap(gaps, mask),
                            mix["check"]["max_gap"]))


def setup(run: Run, **overrides):
    """Build and warm the engine; no hot op may take its reference route.
    ``overrides`` (calibration only) switch the program's precision."""
    from repro.kernels import dispatch
    with dispatch.stats_scope() as stats:
        engine = build(run, **overrides)
        warm(engine)
        routes = stats()
    check_routes(routes)
    return engine


def drive(run: Run) -> None:
    """The entry point: set-up, window, check, and the trace if asked."""
    mix = run.cell.traffic
    compiles = CompileCounter()
    engine = setup(run)
    vocab = run.cell.config["vocab_size"]
    stream = traffic.serve_stream(mix, run.seed, run.seconds, vocab,
                                  horizon=run.seconds + mix["drain_cap_s"])
    run.mark_setup_done()
    w = serve_window(run, engine, stream, compiles)
    run.memory_peak_bytes = memory_peak_bytes(run.devices)
    summarize(run, stream, w)
    seqs = sample(run, w)
    del engine, w
    gc.collect()
    check(run, seqs)
    if run.trace:
        run.record["trace"] = tracing.load(run.record["trace_dir"])
