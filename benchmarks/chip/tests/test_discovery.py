"""A cell is found from files alone: a configuration, a mix and a metric
dropped into their directories, and entries in BENCHMARK.json, with no
edit to any file the benchmark already has."""
import json

from chipbench import cells


def test_new_files_are_found(tiny_bench):
    root, bench = tiny_bench
    cfg = json.loads((bench / "configs" / "tiny.serve.json").read_text())
    cfg["name"] = "tiny.other"
    (bench / "configs" / "tiny.other.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "tiny-mix.json").read_text())
    mix["rate_per_s"] = 9.0
    (bench / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny.other", "source": "test",
                            "file": "benchmarks/chip/configs/tiny.other.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-other", "config": "tiny.other",
                              "traffic": "bursty", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "new_metric", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine", "moves": "itl_p95_ms",
                              "workloads": ["tiny-other"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.find_cell("tiny-other", root, bench)
    assert cell.config["name"] == "tiny.other"
    assert cell.traffic["rate_per_s"] == 9.0
    assert cell.entry.__name__.endswith("serve")
    names = [m.name for m in cell.per_layer]
    assert "new_metric" in names
    assert [m for m in cell.per_layer if m.name == "new_metric"][0] \
        .reader.read(None) == 42.0
    # a metric listed for other cells only is not read here
    assert "mfu.train" not in names
    assert "setup_s" in [m.name for m in cell.end_to_end]


def test_real_cells_resolve():
    spec = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = cells.find_cell(w["name"])
        assert cell.chips == w["chips"]
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
