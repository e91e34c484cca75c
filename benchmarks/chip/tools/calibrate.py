"""Readings that a cell's limits are set from, for many seeds in one
process: the program's numbers (the lower readings), the control's and
the planted faults' (the upper readings).

    python3 benchmarks/chip/tools/calibrate.py --workload <cell> \\
        --seeds 101-112 --control-seeds 101-103 --seconds 10

Serve cells: per seed, new weights in the same compiled engine, a window
of ``--seconds`` at the cell's load, then the reference over the sample
(the number ``max_gap``) and the control: the token an fp8 reference puts
first at each served position, read under the float32 reference
(``control_fp8_gap``).  On the control seeds the program also runs with
its own lower-precision switches on (``--program-control``, by default
int8 weights and int8 KV; kind ``program_int8``).

Train cells: per seed, the program's readings after its first steps
against the float32 reference's (``loss_gap``, ``grad_norm_gap``,
``change_gap``); on the control seeds the same gaps of an fp8 reference
and of a reference that leaves half the batch out.

One JSON line per seed and kind.  Nothing here is a benchmark run.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import cells, device, harness, traffic  # noqa: E402


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def emit(**kw):
    print(json.dumps(kw), flush=True)


def serve_gaps(cell, run, seqs, control: bool):
    from reference import serve_check
    rows, length, span = cell.entry.check_shapes(cell.traffic)
    tokens, start, served, mask = serve_check.pad_batch(seqs, rows, length,
                                                        span)
    key = device.key_for(run.seed)
    if control:
        ref_served, ref_low = serve_check.control_gaps(
            key, cell.config, tokens, start, served)
        return (serve_check.widest_gap(ref_served, mask),
                serve_check.widest_gap(ref_low, mask), int(mask.sum()))
    gaps, _, _ = serve_check.forward(key, cell.config, tokens, start, served)
    return serve_check.widest_gap(gaps, mask), None, int(mask.sum())


def serve_seed(cell, devs, engine_for, seed, seconds, control, label,
               **overrides):
    import jax
    from repro.launch.engine import ContinuousEngine
    serve = cell.entry
    run = harness.Run(cell, seed, seconds, False, devs, T_START)
    engine = engine_for(run)
    sched = engine.sched
    sv = cell.config["serve"]
    for slot in range(sched.slots):        # the last seed's leftovers
        if sched.active[slot] is not None:
            sched._recycle(slot)
    sched.params = jax.jit(sched.model.init)(device.key_for(seed))
    if sched.cache is None:
        sched.cache = sched.model.init_paged_cache(
            sv["slots"], sv["max_len"], sv["page"],
            total_pages=sv["pool_pages"])
    eng = ContinuousEngine(sched, token_budget=sv["token_budget"],
                           clock="wall", log=None)
    mix = cell.traffic
    stream = traffic.serve_stream(mix, seed, seconds,
                                  cell.config["vocab_size"],
                                  seconds + mix["drain_cap_s"])
    w = serve.serve_window(run, eng, stream, device.CompileCounter())
    serve.summarize(run, stream, w)
    seqs = serve.sample(run, w)
    sched.params = sched.cache = None
    del eng, w
    gc.collect()
    gap, ctl, n = serve_gaps(cell, run, seqs, control)
    emit(kind=label, seed=seed, max_gap=gap, control_fp8_gap=ctl,
         compared_tokens=n, failed=run.failed, attempted=run.attempted)


def serve(cell, devs, args):
    serve_mod = cell.entry
    engines = {}

    def engine_for(overrides):
        def get(run):
            key = tuple(sorted(overrides.items()))
            if key not in engines:
                engines.clear()
                gc.collect()
                engines[key] = serve_mod.setup(run, **overrides)
            return engines[key]
        return get

    ctl = set(seeds(args.control_seeds))
    for s in seeds(args.seeds) if args.seeds else []:
        serve_seed(cell, devs, engine_for({}), s, args.seconds, s in ctl,
                   "program")
    int8 = json.loads(args.program_control)
    for s in sorted(ctl) if int8 else []:
        serve_seed(cell, devs, engine_for(int8), s, args.seconds, False,
                   "program_int8")


def train(cell, devs, args):
    from reference import train_check
    tr = cell.entry
    ctl = set(seeds(args.control_seeds))
    n = cell.traffic["check"]["steps"]
    for s in seeds(args.seeds):
        run = harness.Run(cell, s, 0, False, devs, T_START)
        step, params, opt, read = tr.setup(run)
        del step, params, opt
        gc.collect()
        batches = [tr.rows(run, i) for i in range(n)]
        key = device.key_for(s)
        ref = train_check.run(key, cell.config, batches, steps=n)
        g = train_check.gaps(read, ref)
        emit(kind="program", seed=s, losses=read["losses"],
             ref_losses=ref["losses"], **g)
        if s in ctl:
            b = cell.traffic["batch"]
            for kind, kw in (("control_fp8", {"lowp": "fp8"}),
                             ("half_batch", {"rows": (b + 1) // 2})):
                bad = train_check.run(key, cell.config, batches, steps=n,
                                      **kw)
                emit(kind=kind, seed=s, **train_check.gaps(bad, ref))
        gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="101-112")
    ap.add_argument("--control-seeds", default="101-103")
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--program-control",
                    default='{"weights_dtype": "int8", "kv_dtype": "int8"}',
                    help="the program's own lower-precision switches (JSON)")
    ap.add_argument("--drain", type=float, default=0,
                    help="override the mix's drain cap (seconds)")
    args = ap.parse_args(argv)
    cell = cells.find_cell(args.workload)
    if args.drain:
        cell = dataclasses.replace(cell, traffic={
            **cell.traffic, "drain_cap_s": args.drain})
    devs = device.require_chips(cell.chips)
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if cell.traffic["entry"] == "serve":
        serve(cell, devs, args)
    else:
        train(cell, devs, args)


if __name__ == "__main__":
    main()
