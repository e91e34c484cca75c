"""The trace reduction, on a trace recorded on a TPU v5e (the serving
cell's first 150 ms, trimmed) and on synthetic intervals."""
import json
from pathlib import Path

import pytest

from chipbench import kernels, trace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "trace_decode_heavy.json").read_text())


def test_union_and_busy_on_synthetic_ops():
    tr = {"devices": {"/device:TPU:0": {
        "ops": [["while.1", 0, 100, "m"], ["a", 10, 20, "m"],
                ["b", 25, 10, "m"], ["c", 150, 50, "m"]],
        "modules": []}},
        "host": [["bench.window", 0, 300], ["bench.engine.step", 0, 120],
                 ["bench.wait", 120, 180]],
        "window": [0, 300]}
    assert trace.union([(0, 100), (10, 30), (150, 200)]) == [(0, 100),
                                                             (150, 200)]
    assert trace.busy_seconds(tr) == pytest.approx(150e-9)
    gaps = trace.idle_gaps(tr)
    assert [(lab, round(s * 1e9)) for lab, s in gaps] == [("wait", 50),
                                                          ("wait", 100)]
    assert trace.op_seconds(tr, lambda n, m: n in "ab") == pytest.approx(
        30e-9)
    assert [k for k, _ in trace.top_ops(tr)] == ["m/c", "m/a", "m/b"]


def test_recorded_trace_reduces(recorded):
    busy, win = trace.busy_seconds(recorded), trace.window_seconds(recorded)
    assert 0 < busy <= win == pytest.approx(0.15)
    idle = sum(s for _, s in trace.idle_gaps(recorded))
    assert idle == pytest.approx(win - busy, rel=1e-9)
    labels = {lab for lab, _ in trace.idle_gaps(recorded)}
    assert labels <= {"execute.decode", "execute.prefill", "engine.step",
                      "wait", "outside spans"}


def test_recorded_kernels_are_found_by_name_and_module(recorded):
    dec = trace.op_seconds(recorded, kernels.match("decode_attention"))
    mm_dec = trace.op_seconds(recorded, kernels.match("matmul", "decode"))
    mm_pre = trace.op_seconds(recorded, kernels.match("matmul", "prefill"))
    pre = trace.op_seconds(recorded, kernels.match("prefill_attention"))
    assert min(dec, mm_dec, mm_pre, pre) > 0
    every_mm = trace.op_seconds(recorded, kernels.match("matmul"))
    assert every_mm == pytest.approx(mm_dec + mm_pre)
    top = dict(trace.summary(recorded)["device_ops"])
    assert not any(k.split("/")[1].startswith("while") for k in top)
