"""Run one cell once with the profiler on and keep what a reader of the
trace needs to look at by hand.

    python3 benchmarks/chip/tools/probe_trace.py --workload <cell> \\
        --seed <n> --seconds <s> --out <dir>

Writes ``<out>/structure.json`` (each plane's lines, event counts, sample
events with their stats, device op time by module and name) and
``<out>/trimmed.json`` (the neutral trace of ``chipbench.trace`` cut to
the first ``--trim-ms`` of the window), then prints the run's result line.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from collections import defaultdict
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run as bench  # noqa: E402
from chipbench import cells, device, harness  # noqa: E402
from chipbench import trace as tracing  # noqa: E402


def structure(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = ProfileData.from_file(path)
    out = {"file_bytes": os.path.getsize(path), "planes": []}
    for plane in pd.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            by_name = defaultdict(float)
            for ev in evs:
                by_name[ev.name] += ev.duration_ns
            p["lines"].append({
                "name": line.name, "events": len(evs),
                "sample": [{"name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns,
                            "stats": [[k, str(v)[:200]] for k, v in ev.stats]}
                           for ev in evs[:12]],
                "top": sorted(by_name.items(), key=lambda kv: -kv[1])[:40]})
        out["planes"].append(p)
    return out


def trim(tr: dict, ms: float) -> dict:
    lo = tracing.window(tr)[0]
    hi = lo + ms * 1e6
    keep = lambda ev: ev[1] < hi and ev[1] + ev[2] > lo  # noqa: E731
    return {"devices": {k: {"ops": [o for o in d["ops"] if keep(o)],
                            "modules": [m for m in d["modules"] if keep(m)]}
                        for k, d in tr["devices"].items()},
            "host": [h for h in tr["host"]
                     if keep(h) and h[0] != tracing.WINDOW]
            + [[tracing.WINDOW, lo, hi - lo]],
            "window": [lo, hi]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--out", required=True)
    ap.add_argument("--trim-ms", type=float, default=150)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    cell = cells.find_cell(args.workload)
    devs = device.require_chips(cell.chips)
    from repro.runtime.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = harness.Run(cell, args.seed, args.seconds, True, devs, T_START)
    run.record["trace_dir"] = str(out / "xplane")
    cell.entry.drive(run)
    (out / "structure.json").write_text(json.dumps(
        structure(run.record["trace_dir"]), indent=1))
    (out / "trimmed.json").write_text(json.dumps(
        trim(run.record["trace"], args.trim_ms)))
    for note in run.notes:
        print(note, file=sys.stderr)
    print(json.dumps(bench.result(run)))


if __name__ == "__main__":
    main()
