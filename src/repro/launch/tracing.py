"""Spans and counters of the serving engine, on the profiler's clock.

Three calls instrument the host loop:

* ``span(name)`` — a ``with`` block around one piece of host work.  It
  enters ``jax.profiler.TraceAnnotation("repro.<name>")``, so a profile
  shows the span on the same clock as the device ops, and it adds its
  duration (one pair of ``time.perf_counter_ns`` reads) to the open
  iteration's record and to the process's running totals.  Spans nest;
  a span's self time is its duration less its children's.
* ``iteration(engine, step)`` — opens one engine iteration's record
  under ``jax.profiler.StepTraceAnnotation("repro.engine",
  step_num=step)``.  Only ``ContinuousEngine.step`` opens one.  Work
  outside an iteration (warm-up, the static ``PagedScheduler.run``)
  adds to the totals alone.
* ``count(name, n)`` — adds ``n`` to a counter of the open iteration
  (outside one it counts nothing).  ``to_device`` / ``to_host`` move an
  array across the host-device boundary and count its bytes as
  ``h2d_bytes`` / ``d2h_bytes``.

There is no switch: with no profiler session running an annotation
costs next to nothing, and the in-memory part is two clock reads and a
few dict updates per span.

Reading it: ``records()`` gives the kept :class:`Iteration` records,
oldest first (the newest ``MAX_ITERATIONS``, process-wide); each holds
the id of the engine that opened it, its step number, each span's total
nanoseconds in that iteration (in the order the spans first opened) and
its counters.  ``totals()`` gives ``{span: Total}`` over the process;
``since(before)`` the part of them after an earlier ``totals()``.
Taking a profile: ``jax.profiler.start_trace(dir)`` ...
``jax.profiler.stop_trace()``; the spans are ``repro.*`` events of the
host planes of the ``.xplane.pb`` it writes.

One thread drives the engine, so the state here is not locked.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

PREFIX = "repro."
# 2**15 iterations: a 50 s window plus a 60 s drain at 5 ms an iteration
MAX_ITERATIONS = 1 << 15


@dataclass
class Iteration:
    """What one engine iteration recorded; ``tp`` is the degree of the
    tensor-parallel mesh it ran on (1 without one)."""
    engine: int
    step: int
    spans: Dict[str, int] = field(default_factory=dict)     # ns
    counters: Dict[str, int] = field(default_factory=dict)
    tp: int = 1


@dataclass
class Total:
    """One span's running totals: calls, nanoseconds, self nanoseconds."""
    calls: int = 0
    ns: int = 0
    self_ns: int = 0


_records: Deque[Iteration] = collections.deque(maxlen=MAX_ITERATIONS)
_totals: Dict[str, Total] = {}
_stack: List["Span"] = []
_open: List[Optional[Iteration]] = [None]
_engine_ids = itertools.count()


class Span:
    """One timed region; use through :func:`span`.  After the block,
    ``ns`` is its duration and ``children`` its direct children's
    nanoseconds by name."""
    __slots__ = ("name", "ns", "children", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.ns = 0
        self.children: Dict[str, int] = {}
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        _stack.append(self)
        it = _open[0]
        if it is not None:
            it.spans.setdefault(self.name, 0)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.ns = ns = time.perf_counter_ns() - self._t0
        _stack.pop()
        if _stack:
            parent = _stack[-1].children
            parent[self.name] = parent.get(self.name, 0) + ns
        it = _open[0]
        if it is not None:
            it.spans[self.name] = it.spans.get(self.name, 0) + ns
        tot = _totals.get(self.name)
        if tot is None:
            tot = _totals[self.name] = Total()
        tot.calls += 1
        tot.ns += ns
        tot.self_ns += ns - sum(self.children.values())
        self._ann.__exit__(*exc)
        return False

    def ns_of(self, *names: str) -> int:
        """Nanoseconds of the named direct children."""
        return sum(self.children.get(n, 0) for n in names)


def span(name: str) -> Span:
    """A span named ``repro.<name>`` in the profile."""
    return Span(name)


def new_engine_id() -> int:
    return next(_engine_ids)


@contextlib.contextmanager
def iteration(engine: int, step: int, tp: int = 1) -> Iterator[Iteration]:
    """Open the record of engine ``engine``'s iteration ``step`` on a
    mesh of tensor-parallel degree ``tp``; it is kept when the block
    ends, also when the block raises."""
    rec = Iteration(engine, step, tp=tp)
    prev, _open[0] = _open[0], rec
    try:
        with jax.profiler.StepTraceAnnotation(PREFIX + "engine",
                                              step_num=step):
            yield rec
    finally:
        _open[0] = prev
        _records.append(rec)


def count(name: str, n: int) -> None:
    it = _open[0]
    if it is not None:
        it.counters[name] = it.counters.get(name, 0) + n


def to_device(x) -> jax.Array:
    """``jnp.asarray(x)``, its bytes counted as ``h2d_bytes``."""
    out = jnp.asarray(x)
    count("h2d_bytes", out.nbytes)
    return out


def to_host(x) -> np.ndarray:
    """``np.asarray(x)`` (waits for the device), its bytes counted as
    ``d2h_bytes``."""
    out = np.asarray(x)
    count("d2h_bytes", out.nbytes)
    return out


def records() -> List[Iteration]:
    return list(_records)


def totals() -> Dict[str, Total]:
    return {k: Total(t.calls, t.ns, t.self_ns) for k, t in _totals.items()}


def since(before: Dict[str, Total]) -> Dict[str, Total]:
    """The totals gathered after ``before = totals()``."""
    out = {}
    for k, t in _totals.items():
        b = before.get(k, Total())
        if t.calls > b.calls:
            out[k] = Total(t.calls - b.calls, t.ns - b.ns,
                           t.self_ns - b.self_ns)
    return out
