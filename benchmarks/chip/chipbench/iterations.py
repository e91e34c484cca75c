"""The program's own record of each engine iteration, for a serve run's
window.

The program keeps one record per ``ContinuousEngine.step`` call
(``repro.launch.tracing``): each span's nanoseconds and each counter in
that iteration, with the id of the engine that opened it.  The serve
entry calls ``engine.step()`` once per entry of its ``steps`` and its
warm-up opens no record, so the window's iterations are the first
``len(window_steps)`` records of the newest engine: no clock alignment
is needed.  A program that keeps no such records gives ``None``.
"""
from __future__ import annotations

from typing import Iterable, List, Optional


def window(run) -> Optional[List]:
    n = len(run.record.get("window_steps", []))
    if not n:
        return None
    try:
        from repro.launch import tracing
    except ImportError:
        return None
    recs = tracing.records()
    if not recs:
        return None
    newest = max(r.engine for r in recs)
    mine = [r for r in recs if r.engine == newest]
    # the store keeps the newest records: the window's first may be gone
    if len(mine) < n or mine[0].step != 0:
        return None
    return mine[:n]


def mean_span_ms(recs: Optional[List], names: Iterable[str]
                 ) -> Optional[float]:
    """Mean over ``recs`` of the named spans' milliseconds."""
    if not recs:
        return None
    names = tuple(names)
    return sum(r.spans.get(k, 0) for r in recs for k in names) \
        / len(recs) * 1e-6
