"""Spans and counters of the serving engine (``launch/tracing.py``).

On the CPU at smoke widths, the engine on its ``tick`` clock: the span
tree and its self times, one record per ``ContinuousEngine.step``, the
bytes counted at the host-device boundary, the store's bound, the
executor's step times fed by the spans, and greedy streams unchanged by
the instrumentation.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCHS
from repro.core.memory import DtypePolicy
from repro.launch import tracing
from repro.launch.engine import ContinuousEngine
from repro.launch.loadgen import trace_stream
from repro.launch.serve import PagedScheduler
from repro.models.transformer import ExecOptions, Model

SLOTS, MAX_LEN, PAGE = 2, 32, 4
# request 0 prefills alone, then decodes while request 1 prefills, then
# both decode: prefill-only, mixed and decode-only iterations
TRACE = [{"t": 0.0, "prompt_len": 6, "max_new": 5},
         {"t": 3.0, "prompt_len": 7, "max_new": 4}]


@pytest.fixture(scope="module")
def model_and_params():
    cfg = dataclasses.replace(ARCHS["codeqwen1.5-7b"].smoke(),
                              dispatch="reference")
    model = Model(cfg, dt=DtypePolicy(compute=jnp.float32),
                  opts=ExecOptions(mode="run"))
    return model, model.init(jax.random.key(0))


def _serve(model_and_params):
    model, params = model_and_params
    sched = PagedScheduler(model, params, slots=SLOTS, max_len=MAX_LEN,
                           page_size=PAGE, log=None)
    engine = ContinuousEngine(sched, clock="tick", log=None)
    done = engine.run(trace_stream(TRACE, vocab_size=model.cfg.vocab_size,
                                   seed=3))
    recs = [r for r in tracing.records() if r.engine == engine.trace_id]
    return engine, {r.rid: list(r.out) for r in done}, recs


@pytest.fixture(scope="module")
def served(model_and_params):
    return _serve(model_and_params)


class _Clock:
    """``perf_counter_ns`` reading from a fixed list."""

    def __init__(self, *ticks):
        self.ticks = list(ticks)

    def perf_counter_ns(self):
        return self.ticks.pop(0)


def test_spans_nest_and_record_self_time(monkeypatch):
    monkeypatch.setattr(tracing, "time", _Clock(0, 10, 30, 35, 40, 100))
    before = tracing.totals()
    with tracing.span("t.outer") as outer:
        with tracing.span("t.inner"):
            pass
        with tracing.span("t.inner"):
            pass
    got = tracing.since(before)
    assert outer.ns == 100 and outer.ns_of("t.inner") == 25
    assert (got["t.outer"].calls, got["t.outer"].ns,
            got["t.outer"].self_ns) == (1, 100, 75)
    assert (got["t.inner"].calls, got["t.inner"].ns,
            got["t.inner"].self_ns) == (2, 25, 25)


def test_a_span_that_raises_is_closed_and_counted(monkeypatch):
    monkeypatch.setattr(tracing, "time", _Clock(0, 10, 30, 50))
    before = tracing.totals()
    with pytest.raises(ValueError, match="inside"):
        with tracing.span("t.outer"):
            with tracing.span("t.inner"):
                raise ValueError("inside")
    got = tracing.since(before)
    assert (got["t.outer"].ns, got["t.outer"].self_ns) == (50, 30)
    assert (got["t.inner"].ns, got["t.inner"].self_ns) == (20, 20)
    assert tracing._stack == []


def test_a_record_is_kept_when_the_iteration_raises():
    eid = tracing.new_engine_id()
    with pytest.raises(RuntimeError):
        with tracing.iteration(eid, 0):
            with tracing.span("t.step"):
                raise RuntimeError
    (rec,) = [r for r in tracing.records() if r.engine == eid]
    assert list(rec.spans) == ["t.step"]
    assert tracing._open == [None]


def test_each_step_leaves_one_record_with_its_spans_in_order(served):
    engine, _, recs = served
    assert [r.step for r in recs] == list(range(engine.step_calls))
    mixed = [r for r in recs if "prefill" in r.spans and "decode" in r.spans]
    assert mixed, "the trace gives an iteration with prefill and decode"
    assert list(mixed[0].spans) == [
        "engine.admit", "engine.compose",
        "prefill", "prefill.prepare", "prefill.launch", "prefill.wait",
        "decode", "decode.prepare", "decode.launch", "decode.wait",
        "engine.account"]
    # the drained last call composes nothing and executes nothing
    assert list(recs[-1].spans) == ["engine.admit", "engine.compose"]
    for r in recs:
        for parent, kids in (("prefill", ("prefill.prepare",
                                          "prefill.launch",
                                          "prefill.wait")),
                             ("decode", ("decode.prepare", "decode.launch",
                                         "decode.wait"))):
            if parent in r.spans:
                assert r.spans[parent] >= sum(r.spans[k] for k in kids)


def test_counters_match_the_arrays_that_cross(served, model_and_params):
    engine, _, recs = served
    sched = engine.sched
    decode_only = [r for r in recs
                   if "decode" in r.spans and "prefill" not in r.spans]
    assert decode_only
    i32 = 4
    h2d = (SLOTS * i32                         # tokens
           + i32                               # the position scalar
           + SLOTS * i32                       # lengths view
           + SLOTS * sched.n_slot_pages * i32)  # table view
    for r in decode_only:
        assert r.counters["h2d_bytes"] == h2d
        assert r.counters["d2h_bytes"] == SLOTS * i32   # the argmax row
        assert 1 <= r.counters["decode_rows"] <= SLOTS
        assert "prefill_chunks" not in r.counters
    vocab = model_and_params[0].cfg.vocab_size
    prefill = [r for r in recs if "prefill" in r.spans]
    assert sum(r.counters["prefill_chunks"] for r in prefill) \
        == engine.executor.prefill_chunks == 2 + 2
    for r in prefill:
        b = r.counters["prefill_chunks"]
        logits = b * vocab * 4                 # f32 compute
        got = r.counters["d2h_bytes"] - (SLOTS * i32 if "decode" in r.spans
                                         else 0)
        assert got == logits


def test_decode_tiles_count_the_kernels_live_tiles(model_and_params):
    """``decode_tiles`` is one layer's grid steps per kv head in the
    decode kernel: the decoding rows' live KV tiles (here 8 pages of 4
    tokens, 32 tokens a tile) at their cached length plus the new token,
    and nothing for the idle slot."""
    model, params = model_and_params
    sched = PagedScheduler(model, params, slots=SLOTS, max_len=64,
                           page_size=PAGE, log=None)
    engine = ContinuousEngine(sched, clock="tick", log=None)
    engine.run(trace_stream([{"t": 0.0, "prompt_len": 29, "max_new": 6}],
                            vocab_size=model.cfg.vocab_size, seed=5))
    decode = [r for r in tracing.records()
              if r.engine == engine.trace_id and "decode" in r.spans]
    assert [r.counters["decode_rows"] for r in decode] == [1] * 5
    # cached lengths 29..33: the row's 30th..34th key crosses into tile 2
    assert [r.counters["decode_tiles"] for r in decode] == [1, 1, 1, 2, 2]


def test_executor_times_are_launch_plus_wait(served):
    engine, _, recs = served
    ex = engine.executor

    def secs(*names):
        return sum(r.spans.get(n, 0) for r in recs for n in names) * 1e-9

    assert ex.t_prefill > 0 and ex.t_decode > 0
    assert ex.t_prefill == pytest.approx(
        secs("prefill.launch", "prefill.wait"), rel=1e-9)
    assert ex.t_decode == pytest.approx(
        secs("decode.launch", "decode.wait"), rel=1e-9)


def test_the_store_stays_at_its_bound():
    assert tracing.MAX_ITERATIONS >= 2 ** 15
    eid = tracing.new_engine_id()
    extra = 5
    for step in range(tracing.MAX_ITERATIONS + extra):
        with tracing.iteration(eid, step):
            tracing.count("t.n", 1)
    recs = tracing.records()
    assert len(recs) == tracing.MAX_ITERATIONS
    assert recs[0].engine == eid and recs[0].step == extra
    assert recs[-1].step == tracing.MAX_ITERATIONS + extra - 1


class _NoSpan:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def ns_of(self, *names):
        return 0


def test_greedy_streams_do_not_depend_on_the_spans(served, model_and_params,
                                                   monkeypatch):
    _, want, _ = served
    assert sum(len(v) for v in want.values()) == 5 + 4
    monkeypatch.setattr(tracing, "span", _NoSpan)
    engine, got, recs = _serve(model_and_params)
    assert got == want
    assert all(r.spans == {} for r in recs)
    assert engine.executor.t_decode == 0.0
