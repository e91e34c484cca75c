"""Tails over synthetic timelines match numpy.percentile, and the serve
summary times every request from its due time."""
import types

import numpy as np

from chipbench import harness, stats
from chipbench.cells import load_module, BENCH_DIR


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.lognormal(0, 1, 997)
    for q in (50, 90, 95, 99):
        assert stats.percentile(list(x), q) == np.percentile(x, q)
    assert stats.percentile([], 95) is None


def test_spread_is_interquartile_over_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == (5.25 - 1.75) / 3.5


def test_serve_summary_from_due_times():
    serve = load_module(BENCH_DIR / "entries" / "serve.py")
    cell = types.SimpleNamespace(config={"serve": {"slots": 4}})
    run = harness.Run(cell, 1, 10.0, False, [], 0.0)
    stream = [types.SimpleNamespace(rid=i, due=float(i)) for i in range(4)]

    def req(n):
        return types.SimpleNamespace(done=True, out=[0] * n, max_new=n)
    times = {0: [0.5, 0.6, 0.8], 1: [1.2, 1.3], 2: [2.9], 3: []}
    w = {"window_rids": [0, 1, 2, 3], "end": 12.0, "steps": [],
         "reqs": {0: req(3), 1: req(2), 2: req(1),
                  3: types.SimpleNamespace(done=False, out=[], max_new=4)},
         "times": times, "admitted": {0: 0.1, 1: 1.0, 2: 2.5},
         "late_s": 0.0, "compiles": 0, "truncated": 0, "rejected": 0}
    serve.summarize(run, stream, w)
    ttft = [0.5, 0.2, 0.9, 12.0 - 3.0]
    gaps = [0.1, 0.2, 0.1]
    assert np.allclose(run.record["ttft_s"], ttft)
    assert np.allclose(sorted(run.record["itl_s"]), sorted(gaps))
    assert (run.attempted, run.failed) == (4, 1)
    assert run.record["tokens_in_window"] == 6
    ttft_reader = load_module(BENCH_DIR / "metrics" / "ttft_p95_ms.py")
    assert np.isclose(ttft_reader.read(run), np.percentile(ttft, 95) * 1e3)
    itl_reader = load_module(BENCH_DIR / "metrics" / "itl_p95_ms.py")
    assert np.isclose(itl_reader.read(run), np.percentile(gaps, 95) * 1e3)
