"""The tensor-parallel serve entry (``entries/serve_tp.py``) end to end at
the smoke width on 4 simulated CPU devices, and the collectives' readers
(``collective_share.tp``, ``collective_roofline.decode``) on synthetic
traces."""
import json
from types import SimpleNamespace

import pytest

from chipbench import cells
from conftest import BENCH

_TP4_RUN = """
import json, sys, tempfile
from pathlib import Path
sys.path[:0] = [BENCH, BENCH + "/tests", ROOT + "/src"]
import conftest
import run as bench
from repro.launch import tracing

root, bench_dir = conftest.make_tiny_bench(Path(tempfile.mkdtemp()))
cfg = {**conftest.TINY_SERVE, "name": "tiny.tp4", "num_key_value_heads": 4}
cfg["serve"] = {**cfg["serve"], "tp": 4}
(bench_dir / "configs" / "tiny.tp4.json").write_text(json.dumps(cfg))
(bench_dir / "traffic" / "tiny-tp4.json").write_text(
    json.dumps({**conftest.TINY_MIX, "entry": "serve_tp"}))
spec = json.loads((root / "BENCHMARK.json").read_text())
spec["configs"].append({"name": "tiny.tp4", "source": "test", "reduced": [],
                        "file": "benchmarks/chip/configs/tiny.tp4.json",
                        "why": "test"})
spec["workloads"].append({"name": "tiny-tp4", "config": "tiny.tp4",
                          "traffic": "tiny-tp4", "chips": 4, "why": "test"})
for m in spec["end_to_end"] + spec["per_layer"]:
    if "tiny-serve" in m.get("workloads", []):
        m["workloads"].append("tiny-tp4")
(root / "BENCHMARK.json").write_text(json.dumps(spec))
args = bench.parse(["--workload", "tiny-tp4", "--seed", "5000000021",
                    "--seconds", "2", "--trace", "0"])
r = bench.execute(args, require_chip=False, compile_cache=False, root=root,
                  bench_dir=bench_dir)
recs = [x for x in tracing.records()
        if x.engine == max(y.engine for y in tracing.records())]
print("RESULT " + json.dumps({
    "line": bench.result(r), "tp": sorted({x.tp for x in recs}),
    "bytes": sorted({x.counters.get("tp_collective_bytes", 0)
                     for x in recs if "decode" in x.spans
                     and "prefill" not in x.spans})}))
"""


def test_tiny_tp4_run_is_correct():
    """Weights drawn sharded, the pool created sharded, the engine served
    over a 4-device mesh, then checked against the float32 reference."""
    from conftest import ROOT
    import os
    import subprocess
    import sys
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = f"BENCH = {str(BENCH)!r}\nROOT = {str(ROOT)!r}\n" + _TP4_RUN
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.startswith("RESULT ")][-1][len("RESULT "):])
    line = out["line"]
    assert line["correct"] and line["attempted"] > 0, line
    assert line["checks"]["max_gap"]["value"] <= \
        line["checks"]["max_gap"]["limit"]
    assert line["device"]["count"] == 4
    assert {"ttft_p95_ms", "itl_p95_ms", "setup_s"} <= set(line["metrics"])
    assert out["tp"] == [4]
    (decode_bytes,) = out["bytes"]          # one decode shape, one reading
    assert decode_bytes > 0


def _reader(name):
    return cells.load_module(BENCH / "metrics" / f"{name}.py")


def _trace(collectives=True):
    """Four chips, each: a decode step of 10 ns of work and (optionally)
    two collectives of 3 and 2 ns, a prefill step of 20 ns with one
    collective of 5 ns, inside a 100 ns window."""
    dec, pre = "jit_decode_step(1)", "jit_prefill_step_paged(2)"
    ops = [["fusion.1", 0, 10, dec], ["_matmul.3", 40, 15, pre]]
    if collectives:
        ops += [["all-gather.7", 10, 3, dec], ["psum.17", 13, 2, dec],
                ["psum.4", 55, 5, pre]]
    else:
        ops += [["fusion.2", 10, 5, dec], ["fusion.3", 55, 5, pre]]
    return {"devices": {f"/device:TPU:{i}": {"ops": [list(o) for o in ops],
                                             "modules": []}
                        for i in range(4)},
            "host": [["bench.window", 0, 100]], "window": [0, 100]}


def _records(monkeypatch, decode_bytes=4000, prefill_bytes=9000):
    from repro.launch import tracing
    Rec = tracing.Iteration
    recs = [Rec(9, 0, {"prefill": 1}, {"tp_collective_bytes": prefill_bytes},
                tp=4),
            Rec(9, 1, {"decode": 1}, {"tp_collective_bytes": decode_bytes},
                tp=4),
            Rec(9, 2, {"prefill": 1, "decode": 1},
                {"tp_collective_bytes": decode_bytes + prefill_bytes}, tp=4),
            Rec(9, 3, {"decode": 1}, {"tp_collective_bytes": decode_bytes},
                tp=4)]
    monkeypatch.setattr(tracing, "records", lambda: list(recs))
    return recs


def _run(tr):
    return SimpleNamespace(
        record={"trace": tr, "window_steps": [object()] * 4},
        devices=[SimpleNamespace(device_kind="TPU v5 lite")] * 4)


def test_collective_readers_on_a_synthetic_trace(monkeypatch):
    _records(monkeypatch)
    run = _run(_trace())
    # busy 10 + 5 + 15 + 5 = 35 ns a chip, of which 10 in collectives
    assert _reader("collective_share.tp").read(run) == pytest.approx(
        100 * 10 / 35)
    # 3 decode steps of 4000 bytes at 200 GB/s, over 5 ns a chip
    assert _reader("collective_roofline.decode").read(run) == \
        pytest.approx(100 * (3 * 4000 / 200e9) / 5e-9)


@pytest.mark.parametrize("name", ["collective_share.tp",
                                  "collective_roofline.decode"])
def test_no_collectives_read_none(name, monkeypatch):
    """A trace without a collective (one chip, or a parent without the
    mesh), no trace, or a program without the counter: no number."""
    recs = _records(monkeypatch)
    reader = _reader(name)
    assert reader.read(_run(_trace(collectives=False))) is None
    assert reader.read(_run(None)) is None
    assert reader.read(_run({"devices": {}, "host": [],
                             "window": [0, 1]})) is None
    for r in recs:
        r.counters.clear()
    if name == "collective_roofline.decode":
        assert reader.read(_run(_trace())) is None
