#!/usr/bin/env python3
"""Bring-up check: paged serving and a train step on a TPU chip.

    python chip_smoke.py               # one chip: serve phase, train phase
    python chip_smoke.py --four-chips  # tp=4 paged serving vs tp=1, only

Serve phase.  codeqwen1.5-7b at its published widths (d_model 4096, 32
heads, 32 KV heads, head dim 128, d_ff 13440, vocab 92416), cut to 8
layers, bf16 weights drawn from a seed.  Paged KV for 8 slots of 2048
tokens at page 64.  A few Poisson requests run through ``PagedScheduler``
+ ``ContinuousEngine``, the path of ``repro.launch.serve --schedule
continuous``.  Then one prompt's prefill-then-decode logits on the kernel
route are compared with ``dispatch="reference"`` on the same chip.

Train phase.  A few AdamW steps at the same widths through the step of
``repro.launch.train`` (flash forward, fused flash backward), cut in
depth, batch and sequence to fit one chip.

Every phase fails the run if a hot op took its ``reference`` route.  The
lines before the last are set-up facts (device, route counts, tokens,
compile seconds, memory), never speeds.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  Any failure exits non-zero,
and a run that finds no TPU stops before it prints a result.  One
process drives the chip; nothing here starts another.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core.memory import DtypePolicy  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch.engine import ContinuousEngine  # noqa: E402
from repro.launch.loadgen import Request, poisson_stream  # noqa: E402
from repro.launch.serve import PagedScheduler  # noqa: E402
from repro.models.transformer import ExecOptions, Model  # noqa: E402

ARCH = "codeqwen1.5-7b"


@dataclasses.dataclass(frozen=True)
class ServeSize:
    layers: int = 8              # of 32 published
    slots: int = 8
    max_len: int = 2048
    page: int = 64
    requests: int = 4
    prompt_len: int = 320
    max_new: int = 32
    rate: float = 0.25           # arrivals per engine iteration (tick clock)
    check_steps: int = 8         # decode steps in the logits comparison
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class TrainSize:
    # one layer is one whole period of the (attn, mlp) stack.  Of the
    # 0.99B parameters left, the 92416-row embedding and head hold 0.76B;
    # their f32 weights and Adam moments take 11.1 GiB, and the compiled
    # step 14.7 GiB of the 15.75 the compiler allows (rehearsal in
    # scripts/rehearse_chip.py).  Two layers do not fit.
    layers: int = 1              # of 32 published
    batch: int = 2
    seq: int = 1024
    steps: int = 3
    seed: int = 0


# Kernel route vs reference route: both take bf16 inputs and accumulate
# in f32; they differ in which intermediates they round to bf16 (the Pallas
# attention kernels keep scores and the softmax accumulator in f32, the
# reference rounds scores and probabilities).  One bf16 rounding (unit
# roundoff 2^-8) moves a value by ~2e-3 RMS, and the two routes' roundings
# add like a random walk over the ~6 per layer: ~1.5e-2 of the logits' RMS
# for 8 layers, which is what 8 layers at the smoke width measure on the
# CPU.  5e-2 leaves a 3x margin, while one fp8 rounding per layer (unit
# roundoff 2^-4) would already give ~0.1.
LOGITS_REL_RMS_TOL = 5e-2

SERVE_OPS = ("decode_attention", "prefill_attention", "matmul")
TRAIN_OPS = ("attention", "attention_bwd", "matmul", "matmul_bwd")


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def _compile_seconds():
    """Running total of JAX trace + lower + backend-compile seconds."""
    total = [0.0]

    def listen(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _cut(cfg, layers: int):
    return dataclasses.replace(cfg, n_layers=layers)


def check_routes(routes, ops, what: str) -> None:
    """Fail if any of ``ops`` ran its reference lowering, or none ran its
    kernel."""
    ref = {op: routes.get((op, "reference"), 0) for op in ops}
    if any(ref.values()):
        raise SmokeFailure(f"{what}: reference routes taken: {ref}")
    missing = [op for op in ops if not routes.get((op, "kernel"), 0)]
    if missing:
        raise SmokeFailure(f"{what}: no kernel route for {missing}")


def format_routes(routes) -> str:
    return ", ".join(f"{op}/{r}={n}"
                     for (op, r), n in sorted(routes.items())) or "none"


def make_requests(cfg, size: ServeSize):
    return poisson_stream(size.requests, rate=size.rate,
                          vocab_size=cfg.vocab_size,
                          prompt_len=size.prompt_len,
                          max_new=size.max_new, seed=size.seed)


def serve(model, params, reqs, size: ServeSize, *, mesh=None):
    """Run ``reqs`` through PagedScheduler + ContinuousEngine.  Returns
    (scheduler, finished requests, route counts of the run)."""
    sched = PagedScheduler(model, params, slots=size.slots,
                           max_len=size.max_len, page_size=size.page,
                           mesh=mesh, log=None)
    engine = ContinuousEngine(sched, clock="tick", log=None)
    reqs = [Request(r.rid, r.prompt, r.max_new, arrival=r.arrival)
            for r in reqs]
    with dispatch.stats_scope() as stats:
        done = engine.run(reqs)
        routes = stats()
    want = sum(r.max_new for r in reqs)
    got = sum(len(r.out) for r in done)
    if len(done) != len(reqs) or got != want or sched.rejected:
        raise SmokeFailure(
            f"served {len(done)}/{len(reqs)} requests, {got}/{want} tokens, "
            f"rejected={sched.rejected}")
    return sched, sorted(done, key=lambda r: r.rid), routes


def prefill_decode_logits(sched, prompt, steps: int, forced=None):
    """Prefill ``prompt`` into slot 0 one page-sized chunk at a time, then
    decode ``steps`` tokens: greedily, or feeding ``forced`` tokens.
    Returns (tokens fed, logits (steps + 1, V) f32): row 0 predicts the
    first new token, row i the token after the i-th fed one."""
    r = Request(-1, np.asarray(prompt, np.int32), steps + 1)
    if not sched.reserve(r, 0):
        raise SmokeFailure("logits check: slot 0 could not reserve pages")
    page, ln = sched.page, len(prompt)
    toks = np.zeros((-(-ln // page) * page,), np.int32)
    toks[:ln] = prompt
    for t0 in range(0, ln, page):
        logits, sched.cache = sched._prefill(
            sched.params, sched.cache, jnp.asarray(toks[None, t0:t0 + page]),
            jnp.asarray([t0], jnp.int32), jnp.asarray(sched.table[:1]),
            jnp.asarray([min(ln, t0 + page) - 1 - t0], jnp.int32))
    rows = [np.asarray(logits[0], np.float32)]
    sched.lengths[0] = ln
    fed = []
    for i in range(steps):
        tok = int(forced[i]) if forced is not None else int(np.argmax(rows[-1]))
        fed.append(tok)
        cur = np.zeros((sched.slots,), np.int32)
        cur[0] = tok
        lengths = np.zeros((sched.slots,), np.int32)
        lengths[0] = sched.lengths[0]
        table = np.zeros_like(sched.table)
        table[0] = sched.table[0]
        logits, sched.cache = sched._decode(
            sched.params, sched.cache, sched._feed_batch(cur, lengths),
            jnp.int32(0), (jnp.asarray(lengths), jnp.asarray(table)))
        rows.append(np.asarray(logits[0], np.float32))
        sched.lengths[0] += 1
    sched._recycle(0)
    return fed, np.stack(rows)


def compare_logits(got, want, what: str, tol: float = LOGITS_REL_RMS_TOL):
    """Per row (one prefill or decode position): RMS of the difference
    over RMS of ``want``.  Fails if any row exceeds ``tol``."""
    diff = np.sqrt(np.mean((got - want) ** 2, axis=-1))
    scale = np.sqrt(np.mean(want ** 2, axis=-1))
    rel = diff / np.maximum(scale, 1e-30)
    agree = int(np.sum(np.argmax(got, -1) == np.argmax(want, -1)))
    finite = bool(np.all(np.isfinite(got)) and np.all(np.isfinite(want)))
    out = {"rows": int(len(rel)), "worst_rel_rms": float(rel.max()),
           "max_abs": float(np.abs(got - want).max()),
           "argmax_agree": agree, "tol": tol}
    if not finite or rel.max() > tol:
        raise SmokeFailure(f"{what}: logits disagree: {out}")
    return out


def build(cfg, seed: int):
    model = Model(cfg, dt=DtypePolicy(param=jnp.bfloat16),
                  opts=ExecOptions(mode="run"))
    params = jax.jit(model.init)(jax.random.key(seed))
    return model, params


def serve_phase(cfg, size: ServeSize = ServeSize(), log=print):
    """One-chip serve phase: engine run on ``cfg``'s dispatch policy, then
    the kernel-vs-reference logits comparison.  Returns a summary."""
    compile_s = _compile_seconds()
    model, params = build(cfg, size.seed)
    reqs = make_requests(cfg, size)
    t0 = time.perf_counter()
    sched, done, routes = serve(model, params, reqs, size)
    wall = time.perf_counter() - t0
    log(f"[serve] routes: {format_routes(routes)}")
    check_routes(routes, SERVE_OPS, "serve")
    tokens = sum(len(r.out) for r in done)
    log(f"[serve] requests={len(done)} new_tokens={tokens} "
        f"prompt_tokens={sum(len(r.prompt) for r in done)} "
        f"page_bytes={sched._page_bytes} pool_pages={sched.alloc.total} "
        f"setup_s(compile)={compile_s():.1f} run_s(incl. compile)="
        f"{wall:.1f} peak_bytes={_peak_bytes()}")
    prompt = reqs[0].prompt
    fed, got = prefill_decode_logits(sched, prompt, size.check_steps)
    del sched
    ref_cfg = dataclasses.replace(cfg, dispatch="reference")
    ref_model = Model(ref_cfg, dt=model.dt, opts=model.opts)
    ref_sched = PagedScheduler(ref_model, params, slots=1,
                               max_len=size.max_len, page_size=size.page,
                               log=None)
    _, want = prefill_decode_logits(ref_sched, prompt, size.check_steps,
                                    forced=fed)
    cmp = compare_logits(got, want, "kernel vs reference route")
    log(f"[serve] logits kernel vs reference: {json.dumps(cmp)}")
    return {"routes": routes, "tokens": tokens, "logits": cmp}


def four_chip_phase(cfg, size: ServeSize = ServeSize(), tp: int = 4,
                    log=print):
    """Tensor-parallel paged serving at ``tp`` over ``make_serving_mesh``
    against the same requests unsharded: greedy streams are compared, and
    one prompt's teacher-forced logits must agree within tolerance."""
    from repro.launch.mesh import make_serving_mesh
    model, params = build(cfg, size.seed)
    reqs = make_requests(cfg, size)
    sched1, done1, routes1 = serve(model, params, reqs, size)
    check_routes(routes1, SERVE_OPS, "serve tp=1")
    prompt = reqs[0].prompt
    fed, want = prefill_decode_logits(sched1, prompt, size.check_steps)
    del sched1
    sched4, done4, routes4 = serve(model, params, reqs, size,
                                   mesh=make_serving_mesh(tp))
    log(f"[tp{tp}] routes: {format_routes(routes4)}")
    check_routes(routes4, SERVE_OPS, f"serve tp={tp}")
    _, got = prefill_decode_logits(sched4, prompt, size.check_steps,
                                   forced=fed)
    same = sum(a.out == b.out for a, b in zip(done1, done4))
    log(f"[tp{tp}] greedy streams identical to tp=1: {same}/{len(done1)} "
        f"requests, {sum(len(r.out) for r in done4)} new tokens")
    cmp = compare_logits(got, want, f"tp={tp} vs tp=1")
    log(f"[tp{tp}] logits tp={tp} vs tp=1: {json.dumps(cmp)} "
        f"peak_bytes={_peak_bytes()}")
    return {"streams_identical": same, "logits": cmp}


def train_phase(cfg, size: TrainSize = TrainSize(), log=print):
    """A few optimizer steps of ``repro.launch.train``'s step, cut to fit
    one chip.  The compiled step's memory analysis must fit the chip."""
    from repro.core.model import device_hardware
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.optim.adamw import AdamWConfig
    from repro.train.steps import (TrainStepConfig, init_train_state,
                                   make_train_step)
    log(f"[train] cuts: layers {cfg.n_layers}->{size.layers}, batch "
        f"{size.batch}, seq {size.seq} (published widths kept)")
    cfg = _cut(cfg, size.layers)
    opts = ExecOptions(mode="run", block_q=min(512, size.seq),
                       block_kv=min(512, size.seq), remat=True)
    model = Model(cfg, dt=DtypePolicy(), opts=opts)
    ts_cfg = TrainStepConfig(opt=AdamWConfig(lr=1e-4, warmup_steps=1,
                                             total_steps=size.steps))
    params, opt = jax.jit(lambda k: init_train_state(model, ts_cfg, k))(
        jax.random.key(size.seed))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=size.seq, global_batch=size.batch,
                                  input_mode=cfg.input_mode,
                                  d_model=cfg.d_model))

    def batch_at(i):
        return {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}

    t0 = time.perf_counter()
    with dispatch.stats_scope() as stats:
        step = jax.jit(make_train_step(model, ts_cfg),
                       donate_argnums=(0, 1)).lower(
            params, opt, batch_at(0)).compile()
        routes = stats()
    compile_s = time.perf_counter() - t0
    ma = step.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    hbm = device_hardware().hbm_bytes
    log(f"[train] routes: {format_routes(routes)}")
    log(f"[train] compiled step: {need / 2**30:.2f} GiB of "
        f"{hbm / 2**30:.0f} GiB (args {ma.argument_size_in_bytes}, temps "
        f"{ma.temp_size_in_bytes}, aliased {ma.alias_size_in_bytes}); "
        f"setup_s(compile)={compile_s:.1f}")
    check_routes(routes, TRAIN_OPS, "train")
    if need > hbm:
        raise SmokeFailure(f"train step needs {need} bytes > {hbm}")
    losses = []
    for i in range(size.steps):
        params, opt, metrics = step(params, opt, batch_at(i))
        losses.append(float(metrics["loss"]))
    log(f"[train] steps={size.steps} tokens/step={size.batch * size.seq} "
        f"losses={losses} peak_bytes={_peak_bytes()}")
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"train: non-finite loss {losses}")
    return {"routes": routes, "losses": losses}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only tensor-parallel serving at tp=4 and "
                         "its tp=1 comparison (needs 4 chips)")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    from repro.runtime.compile_cache import enable_compile_cache
    print(f"[setup] compile cache: {enable_compile_cache()}")
    count = len(jax.devices())
    print(f"[setup] device kind={dev.device_kind!r} count={count} "
          f"jax={jax.__version__}")
    cfg = get_arch(ARCH)
    serve_cfg = _cut(cfg, ServeSize().layers)
    print(f"[setup] {ARCH} cut to {serve_cfg.n_layers}/{cfg.n_layers} "
          f"layers: {serve_cfg.param_counts()['total'] / 1e9:.2f}B "
          f"parameters, bf16")
    if args.four_chips:
        if count < 4:
            print(f"chip_smoke: --four-chips needs 4 chips, found {count}",
                  file=sys.stderr)
            return 2
        four_chip_phase(serve_cfg)
        count = 4
    else:
        serve_phase(serve_cfg)
        train_phase(cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
