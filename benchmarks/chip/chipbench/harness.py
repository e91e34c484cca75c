"""One run of one cell: what an entry fills in and the metrics read.

An entry (``entries/<name>.py``) gets a :class:`Run`, drives the program
through set-up, the measured window and the check, and records what it
saw on the run.  Metric readers (``metrics/<name>.py``) read the run and
return a number or ``None``.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import jax


@dataclasses.dataclass
class Check:
    """One number compared, with its limit: the run is correct only if
    ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    cell: Any
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float                       # perf_counter at process start
    setup_s: Optional[float] = None
    window_s: Optional[float] = None     # the measured window as it ran
    attempted: int = 0
    failed: int = 0
    checks: List[Check] = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    # what the entry recorded (serve: requests and steps; train: steps)
    record: Dict[str, Any] = dataclasses.field(default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)

    def mark_setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    return jax.profiler.TraceAnnotation(f"bench.{name}")
