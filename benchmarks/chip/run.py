#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Set-up (weights from the seed, compiles through the persistent cache at
``<checkout>/.jax_cache``), then ``--seconds`` of measured window, then the
check against the plain reference.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with its limit, which also close standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits
with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import cells  # noqa: E402

NO_CHIP = 3


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(args, *, require_chip: bool = True, compile_cache: bool = True,
            root: Path = cells.ROOT, bench_dir: Path = cells.BENCH_DIR):
    """Set up, measure and check one run; returns the finished Run.
    CPU tests pass ``require_chip=False`` (no look for a chip) and
    ``compile_cache=False`` (nothing written to the checkout's cache)."""
    import jax
    from chipbench import device, harness
    cell = cells.find_cell(args.workload, root, bench_dir)
    devs = (device.require_chips(cell.chips) if require_chip
            else jax.devices()[:cell.chips])
    if compile_cache:
        from repro.runtime.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace), devs,
                      T_START)
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        run.record["trace_dir"] = tdir
        cell.entry.drive(run)
    return run


def result(run) -> dict:
    """The result line, metrics read by the cell's own readers."""
    from chipbench import device, trace as tracing
    wanted = run.cell.per_layer if run.trace else run.cell.end_to_end
    metrics = {}
    for m in wanted:
        v = m.reader.read(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    dev = {**device.describe(run.devices),
           "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": dev}
    if run.trace:
        s = tracing.summary(run.record["trace"])
        dev.update(busy_s=s["busy_s"], window_s=s["window_s"])
        line["breakdown"] = {"device_ops": s["device_ops"],
                             "idle_gaps": s["idle_gaps"]}
    line["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                               else None, "limit": c.limit}
                      for c in run.checks}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench.device import NoChip
    try:
        run = execute(args)
    except NoChip as e:
        print(f"run.py: {e}; nothing measured", file=sys.stderr)
        return NO_CHIP
    line = result(run)
    for note in run.notes:
        print(note, file=sys.stderr)
    print(f"setup_s {run.setup_s:.3f}", file=sys.stderr)
    for c in run.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
