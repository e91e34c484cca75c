"""Shared fixtures: a copy of the benchmark with a tiny cell of each kind.

The tests run on the CPU (``JAX_PLATFORMS=cpu``), Pallas kernels in
interpret mode, at the program's smoke widths.  They sit outside the
repository's test paths; run them by hand:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_MODEL = {
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
}
TINY_SERVE = {
    "name": "tiny.serve", **TINY_MODEL,
    "program": {"arch": "codeqwen1.5-7b", "smoke": True,
                "dispatch": "kernels", "param_dtype": "bfloat16"},
    "serve": {"slots": 4, "max_len": 128, "page": 8, "token_budget": 32,
              "pool_pages": 65, "prefix_cache": False},
}
TINY_TRAIN = {
    "name": "tiny.train", **TINY_MODEL,
    "program": {"arch": "codeqwen1.5-7b", "smoke": True,
                "dispatch": "kernels", "param_dtype": "float32"},
    "train": {"remat": True, "block_q": 16, "block_kv": 16,
              "optimizer": {"lr": 0.001, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
                            "weight_decay": 0.1, "grad_clip": 1.0,
                            "warmup_steps": 1, "total_steps": 100,
                            "min_lr_ratio": 0.1}},
}
TINY_MIX = {
    "entry": "serve", "rate_per_s": 3.0,
    "prompt_len": {"median": 20, "sigma": 0.5, "min": 4, "max": 48},
    "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    "drain_cap_s": 60, "check": {"rows": 3, "max_gap": 0.05},
}
TINY_TRAIN_MIX = {"entry": "train", "batch": 2, "seq": 32,
                  "check": {"steps": 3, "loss_gap": 0.01,
                            "grad_norm_gap": 0.05, "change_gap": 0.3}}


def make_tiny_bench(tmp_path):
    """A checkout-like tree: the benchmark's files, plus a tiny config and
    mix of each kind and a BENCHMARK.json naming their two cells."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (bench / "configs" / "tiny.serve.json").write_text(json.dumps(TINY_SERVE))
    (bench / "configs" / "tiny.train.json").write_text(json.dumps(TINY_TRAIN))
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps(TINY_MIX))
    (bench / "traffic" / "tiny-train.json").write_text(
        json.dumps(TINY_TRAIN_MIX))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [
        {"name": "tiny.serve", "source": "test", "reduced": [],
         "file": "benchmarks/chip/configs/tiny.serve.json", "why": "test"},
        {"name": "tiny.train", "source": "test", "reduced": [],
         "file": "benchmarks/chip/configs/tiny.train.json", "why": "test"}]
    spec["workloads"] = [
        {"name": "tiny-serve", "config": "tiny.serve", "traffic": "tiny-mix",
         "chips": 1, "why": "test"},
        {"name": "tiny-train", "config": "tiny.train",
         "traffic": "tiny-train", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = "train" if any("train" in w for w in m["workloads"]) \
                else "serve"
            m["workloads"] = [f"tiny-{kind}"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path, bench


@pytest.fixture()
def tiny_bench(tmp_path):
    return make_tiny_bench(tmp_path)
