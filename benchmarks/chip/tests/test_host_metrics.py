"""The readers of the program's iteration records (``host_ms.*``,
``transfer_kib.step``) on synthetic records, and once on a tiny traced
serve run on the CPU."""
import sys
from types import SimpleNamespace

import pytest

from chipbench import cells, iterations
from conftest import BENCH

READERS = ("host_ms.engine", "host_ms.decode", "host_ms.prefill",
           "transfer_kib.step")


def _reader(name):
    return cells.load_module(BENCH / "metrics" / f"{name}.py")


def _rec(engine, step, spans=(), **counters):
    from repro.launch.tracing import Iteration
    return Iteration(engine, step, dict(spans), dict(counters))


def _run(n):
    return SimpleNamespace(record={"window_steps": [object()] * n})


MS = 1_000_000


def _two_engines():
    """An older engine's records, then the newest engine's: a prefill
    step, two decode-only steps, and a drain step past the window."""
    old = [_rec(3, s, {"engine.admit": 99 * MS, "decode": 99 * MS,
                       "decode.prepare": 99 * MS}, h2d_bytes=1 << 30)
           for s in range(4)]
    new = [
        _rec(7, 0, {"engine.admit": 1 * MS, "engine.compose": 1 * MS,
                    "prefill": 9 * MS, "prefill.prepare": 2 * MS,
                    "prefill.launch": 4 * MS, "prefill.wait": 3 * MS,
                    "engine.account": 2 * MS},
             h2d_bytes=1024, d2h_bytes=3072, prefill_chunks=1),
        _rec(7, 1, {"engine.admit": 1 * MS, "engine.compose": 0,
                    "decode": 8 * MS, "decode.prepare": 1 * MS,
                    "decode.launch": 2 * MS, "decode.wait": 5 * MS,
                    "engine.account": 1 * MS},
             h2d_bytes=2048, d2h_bytes=0, decode_rows=2),
        _rec(7, 2, {"engine.admit": 2 * MS, "engine.compose": 1 * MS,
                    "decode": 8 * MS, "decode.prepare": 3 * MS,
                    "decode.launch": 2 * MS, "decode.wait": 3 * MS,
                    "engine.account": 0},
             h2d_bytes=1024, d2h_bytes=1024, decode_rows=1),
        _rec(7, 3, {"engine.admit": 50 * MS, "decode": 50 * MS,
                    "decode.prepare": 50 * MS}, h2d_bytes=1 << 30),
    ]
    return old + new


@pytest.fixture()
def records(monkeypatch):
    from repro.launch import tracing
    recs = _two_engines()
    monkeypatch.setattr(tracing, "records", lambda: list(recs))
    return recs


def test_the_window_is_the_newest_engines_first_records(records):
    got = iterations.window(_run(3))
    assert [(r.engine, r.step) for r in got] == [(7, 0), (7, 1), (7, 2)]


def test_readers_on_the_window(records):
    run = _run(3)
    assert _reader("host_ms.engine").read(run) == pytest.approx(
        (4 + 2 + 3) / 3)
    assert _reader("host_ms.decode").read(run) == pytest.approx(
        (3 + 5) / 2)
    assert _reader("host_ms.prefill").read(run) == pytest.approx(6.0)
    assert _reader("transfer_kib.step").read(run) == pytest.approx(
        (4 + 2 + 2) / 3)


def test_a_window_whose_first_record_was_dropped_reads_none(records):
    records[4:] = records[5:]          # the newest engine starts at step 1
    assert iterations.window(_run(2)) is None


def test_a_window_without_prefill_reads_none_for_prefill(records):
    del records[:]
    records += [_rec(1, 0, {"decode": MS, "decode.launch": MS})]
    run = _run(1)
    assert _reader("host_ms.prefill").read(run) is None
    assert _reader("host_ms.decode").read(run) == pytest.approx(1.0)


@pytest.mark.parametrize("name", READERS)
def test_no_records_or_no_window_reads_none(name, records):
    assert _reader(name).read(_run(0)) is None
    assert _reader(name).read(_run(5)) is None     # more than recorded
    del records[:]
    assert _reader(name).read(_run(3)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_records_reads_none(name, records, monkeypatch):
    """As the parent tree, which has no ``repro.launch.tracing``."""
    import repro.launch
    monkeypatch.delattr(repro.launch, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.launch.tracing", None)
    assert _reader(name).read(_run(3)) is None


def test_tiny_traced_serve_run_reads_every_metric(tiny_bench, monkeypatch):
    import run as bench
    from chipbench import device
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    root, bench_dir = tiny_bench
    args = bench.parse(["--workload", "tiny-serve", "--seed", "4243",
                        "--seconds", "2", "--trace", "1"])
    r = bench.execute(args, require_chip=False, compile_cache=False,
                      root=root, bench_dir=bench_dir)
    line = bench.result(r)
    assert line["correct"]
    for name in READERS:
        assert line["metrics"][name]["value"] > 0, name
