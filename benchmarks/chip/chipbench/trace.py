"""The device trace: capture, load, and reduce to busy time and kernels.

A run with ``--trace 1`` records the profiler's trace over its measured
window.  :func:`load` turns the ``.xplane.pb`` into a small neutral form,

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns, module], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[span, start_ns, dur_ns], ...],
     "window": [start_ns, end_ns]}

and the reductions below work on that form only, so a test can feed them
a trimmed trace recorded on the chip.  Device ops come from each device
plane's op line; an op's module is its ``hlo_module`` stat, or the module
event that contains it.  Host spans are the harness's own
``bench.<name>`` annotations; the span ``bench.window`` marks the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
WINDOW = "bench.window"


CONTAINERS = re.compile(r"^(while|conditional|call)\b")


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: a TPU op
    event is named by its whole HLO instruction."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(trace_dir: str) -> Dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = {"devices": {}, "host": [], "window": None}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name in OP_LINES:
                    for ev in line.events:
                        mod = _stat(ev, "hlo_module")
                        dev["ops"].append([short_name(ev.name), ev.start_ns,
                                           ev.duration_ns, mod])
                elif line.name in MODULE_LINES:
                    dev["modules"] += [[ev.name, ev.start_ns, ev.duration_ns]
                                       for ev in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        out["host"].append([ev.name, ev.start_ns,
                                            ev.duration_ns])
    for name, st, du in out["host"]:
        if name == WINDOW:
            out["window"] = [st, st + du]
    _attach_modules(out)
    return out


def _attach_modules(tr: Dict) -> None:
    """Give each op without an ``hlo_module`` stat the module event that
    contains its start."""
    for dev in tr["devices"].values():
        mods = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for op in dev["ops"]:
            if op[3]:
                continue
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[1] < mods[i][1] + mods[i][2]:
                op[3] = mods[i][0]


def _clip(iv: Iterable[Tuple[float, float]], lo: float, hi: float):
    for a, b in iv:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def union(iv: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def window(tr: Dict) -> Tuple[float, float]:
    if tr.get("window"):
        return tuple(tr["window"])
    ops = [o for d in tr["devices"].values() for o in d["ops"]] or [[0, 0, 0]]
    return (min(o[1] for o in ops), max(o[1] + o[2] for o in ops))


def busy_intervals(dev: Dict, lo: float, hi: float):
    """Loops enclose the ops they run, so the union is taken as is."""
    return union(_clip(((o[1], o[1] + o[2]) for o in dev["ops"]), lo, hi))


def busy_seconds(tr: Dict) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    lo, hi = window(tr)
    devs = list(tr["devices"].values())
    tot = sum(b - a for d in devs for a, b in busy_intervals(d, lo, hi))
    return tot / max(len(devs), 1) / 1e9


def window_seconds(tr: Dict) -> float:
    lo, hi = window(tr)
    return (hi - lo) / 1e9


def _innermost(spans: Sequence, starts: Sequence, t: float) -> str:
    """Spans nest, so the latest-starting span that covers ``t`` is the
    innermost; look back a bounded number of earlier siblings."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - 64, -1), -1):
        name, st, du = spans[j]
        if t < st + du and name != WINDOW:
            return name[len("bench."):]
    return "outside spans"


def idle_gaps(tr: Dict, device: Optional[str] = None
              ) -> List[Tuple[str, float]]:
    """Each idle gap of one device inside the window, labelled by the
    innermost harness span around its midpoint: [(label, seconds)]."""
    if not tr["devices"]:
        return []
    lo, hi = window(tr)
    dev = tr["devices"][device or sorted(tr["devices"])[0]]
    busy = busy_intervals(dev, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = sorted(tr["host"], key=lambda s: s[1])
    starts = [s[1] for s in spans]
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            out.append((_innermost(spans, starts, (a + b) / 2),
                        (b - a) / 1e9))
    return out


def idle_by_label(tr: Dict) -> List[Tuple[str, float]]:
    tot: Dict[str, float] = defaultdict(float)
    for label, s in idle_gaps(tr):
        tot[label] += s
    return sorted(tot.items(), key=lambda kv: -kv[1])


def op_seconds(tr: Dict, match=lambda name, module: True) -> float:
    """Device seconds of the ops ``match(name, module)`` selects, inside
    the window, summed over devices."""
    lo, hi = window(tr)
    return sum(b - a for d in tr["devices"].values()
               for a, b in _clip(((o[1], o[1] + o[2]) for o in d["ops"]
                                  if match(o[0], o[3] or "")), lo, hi)) / 1e9


def op_family(name: str) -> str:
    """``fusion.12`` -> ``fusion``: ops grouped by what they are."""
    return re.sub(r"[.:]\d+$", "", name)


def top_ops(tr: Dict, n: int = 10) -> List[Tuple[str, float]]:
    """Device seconds by module and op family; loops, which enclose the
    ops they run, are left out."""
    lo, hi = window(tr)
    tot: Dict[str, float] = defaultdict(float)
    for d in tr["devices"].values():
        for o in d["ops"]:
            if CONTAINERS.match(o[0]):
                continue
            for a, b in _clip([(o[1], o[1] + o[2])], lo, hi):
                key = f"{(o[3] or '?').split('(')[0]}/{op_family(o[0])}"
                tot[key] += (b - a) / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def summary(tr: Dict) -> Dict:
    """What every traced run reports: busy and window seconds, and the
    breakdown's two lists."""
    return {"busy_s": busy_seconds(tr), "window_s": window_seconds(tr),
            "device_ops": [[k, v] for k, v in top_ops(tr)],
            "idle_gaps": [[k, v] for k, v in idle_by_label(tr)[:10]]}
