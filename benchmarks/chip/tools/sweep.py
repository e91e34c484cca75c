"""Find a serving cell's knee: the same set-up, one window per rate.

    python3 benchmarks/chip/tools/sweep.py --workload <cell> --seed <n> \\
        --seconds 20 --rates 2,3,4,5,6

One process sets the cell up once and offers each rate in turn for
``--seconds`` (the cell's own mix with ``rate_per_s`` replaced), draining
in between.  For each rate it prints one JSON line: the tails, tokens per
second, and the backlog at the window's close.  A rate is sustained while
the backlog stays near empty and time to first token does not grow with
the window.  The cell's rate is then set in its mix by hand.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import cells, device, harness, traffic  # noqa: E402
from chipbench.stats import percentile  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=20,
                    help="seconds of load after each window")
    args = ap.parse_args(argv)
    cell = cells.find_cell(args.workload)
    devs = device.require_chips(cell.chips)
    import jax
    from repro.launch.engine import ContinuousEngine
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    serve = cell.entry
    base = harness.Run(cell, args.seed, args.seconds, False, devs, T_START)
    compiles = device.CompileCounter()
    engine = serve.setup(base)
    sched = engine.sched
    sv = cell.config["serve"]
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = {**cell.traffic, "rate_per_s": rate, "drain_cap_s": args.drain}
        run = harness.Run(dataclasses.replace(cell, traffic=mix), args.seed,
                          args.seconds, False, devs, T_START)
        for slot in range(sched.slots):    # the last rate's leftovers
            if sched.active[slot] is not None:
                sched._recycle(slot)
        eng = ContinuousEngine(sched, token_budget=sv["token_budget"],
                               clock="wall", log=None)
        stream = traffic.serve_stream(mix, args.seed, args.seconds,
                                      cell.config["vocab_size"],
                                      args.seconds + mix["drain_cap_s"])
        w = serve.serve_window(run, eng, stream, compiles)
        serve.summarize(run, stream, w)
        rec = run.record
        at_close = [s for s in w["steps"] if s.t0 < args.seconds]
        print(json.dumps({
            "rate_per_s": rate, "requests": run.attempted,
            "failed": run.failed,
            "ttft_p50_ms": percentile(rec["ttft_s"], 50) * 1e3,
            "ttft_p95_ms": percentile(rec["ttft_s"], 95) * 1e3,
            "itl_p50_ms": percentile(rec["itl_s"], 50) * 1e3,
            "itl_p95_ms": percentile(rec["itl_s"], 95) * 1e3,
            "queue_p95_ms": percentile(rec["queue_wait_s"], 95) * 1e3,
            "serve_tok_s": rec["tokens_in_window"] / args.seconds,
            "drain_s": w["end"] - args.seconds,
            "steps_in_window": len(at_close),
            "note": run.notes[-1]}), flush=True)


if __name__ == "__main__":
    main()
