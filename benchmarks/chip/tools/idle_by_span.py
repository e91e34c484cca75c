"""Put each idle gap of a traced serve run under the program's own span.

    python3 benchmarks/chip/tools/idle_by_span.py --workload <cell> \\
        --seed <n> --seconds <s> [--out <dir>]

Runs the cell once with the profiler on, as ``run.py --trace 1`` does,
then reads the trace's host spans of both kinds: the program's
``repro.*`` and the benchmark's ``bench.*``.

* idle by program span: every idle gap of the device inside the window,
  cut where a program span opens or closes, each piece put under the
  innermost ``repro.*`` span open over it (``outside program spans``
  where none is);
* nesting: how many of the window's program spans lie outside a
  ``bench.engine.step`` span, and how many ``repro.prefill`` /
  ``repro.decode`` outside ``bench.execute.prefill`` /
  ``bench.execute.decode``;
* the host check: seconds of the window's program spans that are host
  work before or after the device's part (``engine.admit``,
  ``engine.compose``, ``engine.account``, each step's ``prepare`` and
  ``launch``), beside the idle the run's breakdown puts under
  ``engine.step`` + ``execute.prefill`` + ``execute.decode``.

Prints one JSON object, also written to ``<out>/<cell>.<seed>.json``:
the above, the run's end-to-end metrics, the seconds ``stop_trace``
took (the engine stands still meanwhile, so a request in flight when
the window closes waits that long more), and the run's result line.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run as bench  # noqa: E402
from chipbench import cells, device, harness  # noqa: E402
from chipbench import trace as tracing  # noqa: E402

OUTSIDE = "outside program spans"
HOST_WORK = ("engine.admit", "engine.compose", "engine.account",
             "prefill.prepare", "prefill.launch", "decode.prepare",
             "decode.launch", "verify.prepare", "verify.launch")
BENCH_HOST = ("engine.step", "execute.prefill", "execute.decode")


def host_spans(trace_dir: str):
    """[(name, start_ns, end_ns)] of every ``repro.*`` and ``bench.*``
    host event, by start (outer first at equal starts)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("repro.", "bench.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def innermost(spans, lo: float, hi: float):
    """[(a, b, name)] over [lo, hi]: the innermost of the nested
    ``spans`` open over each piece, ``OUTSIDE`` where none is."""
    out, stack, cur = [], [], lo

    def upto(t):
        nonlocal cur
        a, b = max(cur, lo), min(t, hi)
        if b > a:
            out.append((a, b, stack[-1][0] if stack else OUTSIDE))
        cur = max(cur, t)

    for s in spans:
        while stack and stack[-1][2] <= s[1]:
            upto(stack[-1][2])
            stack.pop()
        upto(s[1])
        stack.append(s)
    while stack:
        upto(stack[-1][2])
        stack.pop()
    upto(hi)
    return out


def idle_by_span(tr, spans):
    if not tr["devices"]:
        return []
    lo, hi = tracing.window(tr)
    dev = tr["devices"][sorted(tr["devices"])[0]]
    busy = tracing.busy_intervals(dev, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    prog = [(n[len("repro."):], a, b) for n, a, b in spans
            if n.startswith("repro.")]
    tot = defaultdict(float)
    segs = innermost(prog, lo, hi)
    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            sa, sb, name = segs[k]
            tot[name] += (min(b, sb) - max(a, sa)) / 1e9
            k += 1
    return sorted(tot.items(), key=lambda kv: -kv[1])


def nesting(spans, lo: float, hi: float):
    """Program spans of the window outside the benchmark's spans."""
    by = defaultdict(list)
    for n, a, b in spans:
        if n.startswith("bench."):
            by[n].append((a, b))

    def inside(a, b, name):
        ivs = by[name]
        i = bisect.bisect_right(ivs, (a, float("inf"))) - 1
        return i >= 0 and ivs[i][0] <= a and b <= ivs[i][1]

    out = {"program_spans": 0, "outside_engine_step": 0,
           "prefill_outside_execute": 0, "decode_outside_execute": 0}
    for n, a, b in spans:
        if not n.startswith("repro.") or a < lo or b > hi:
            continue
        out["program_spans"] += 1
        out["outside_engine_step"] += not inside(a, b, "bench.engine.step")
        for kind in ("prefill", "decode"):
            if n == f"repro.{kind}":
                out[f"{kind}_outside_execute"] += not inside(
                    a, b, f"bench.execute.{kind}")
    return out


def host_work_s(spans, lo: float, hi: float) -> float:
    names = {f"repro.{n}" for n in HOST_WORK}
    return sum(max(0, min(b, hi) - max(a, lo)) for n, a, b in spans
               if n in names) / 1e9


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50)
    ap.add_argument("--out", default="chiprun_out/idle_by_span")
    args = ap.parse_args(argv)
    cell = cells.find_cell(args.workload)
    devs = device.require_chips(cell.chips)
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = harness.Run(cell, args.seed, args.seconds, True, devs, T_START)
    # the serve entry ends the trace inside its loop, so the engine stands
    # still while the trace is written: time that, to read the run's
    # end-to-end metrics by
    stops = []
    stop = jax.profiler.stop_trace

    def timed_stop():
        t0 = time.perf_counter()
        stop()
        stops.append(time.perf_counter() - t0)

    jax.profiler.stop_trace = timed_stop
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        run.record["trace_dir"] = tdir
        cell.entry.drive(run)
        spans = host_spans(tdir)
    line = bench.result(run)
    tr = run.record["trace"]
    lo, hi = tracing.window(tr)
    bench_idle = dict(line["breakdown"]["idle_gaps"])
    out = {
        "workload": args.workload, "seed": args.seed,
        "end_to_end": {m.name: m.reader.read(run) for m in cell.end_to_end},
        "stop_trace_s": stops,
        "idle_by_program_span": idle_by_span(tr, spans),
        "nesting": nesting(spans, lo, hi),
        "host_work_s": host_work_s(spans, lo, hi),
        "bench_host_idle_s": sum(bench_idle.get(k, 0.0)
                                 for k in BENCH_HOST),
        "line": line,
    }
    for note in run.notes:
        print(note, file=sys.stderr)
    dest = Path(args.out)
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{args.workload}.{args.seed}.json").write_text(
        json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
