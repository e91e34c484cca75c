"""A run whose timed path is broken underneath comes out not correct.

Each test skips the look for a chip and drives the rest of a run at the
smoke widths on the CPU, with one fault planted in the program:

* serving: a token altered where the decode step produces it; a prefill
  step that returns the cache unchanged;
* training: a step that returns its state unchanged; a step that leaves
  half of the batch out and takes the mean over the rest.

(A cell on one chip has no exchange between chips to leave out.)
"""
import jax
import jax.numpy as jnp
import pytest

import run as bench
from chipbench import device


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})


def _run(tiny_bench, cell, seconds="2"):
    root, bench_dir = tiny_bench
    args = bench.parse(["--workload", cell, "--seed", "4242", "--seconds",
                        seconds, "--trace", "0"])
    r = bench.execute(args, require_chip=False, compile_cache=False,
                      root=root, bench_dir=bench_dir)
    return r, bench.result(r)


def test_sound_serving_run_is_correct(tiny_bench):
    r, line = _run(tiny_bench, "tiny-serve")
    assert line["correct"] and line["attempted"] > 0
    assert list(line["checks"]) == ["max_gap"]


def test_altered_token_is_caught(tiny_bench, monkeypatch):
    from repro.launch import serve
    step = serve.PagedScheduler.step

    def altered(self, tokens, view=None):
        return (step(self, tokens, view) + 1) % self.model.cfg.vocab_size
    monkeypatch.setattr(serve.PagedScheduler, "step", altered)
    r, line = _run(tiny_bench, "tiny-serve")
    assert not line["correct"]


def test_prefill_that_keeps_the_cache_is_caught(tiny_bench, monkeypatch):
    from repro.launch import serve
    init = serve.PagedScheduler.__init__

    def keeps_state(self, *a, **k):
        init(self, *a, **k)
        inner = self._prefill

        def prefill(params, cache, *rest):
            kept = jax.tree.map(jnp.copy, cache)   # the call donates cache
            logits, _ = inner(params, cache, *rest)
            return logits, kept
        self._prefill = prefill
    monkeypatch.setattr(serve.PagedScheduler, "__init__", keeps_state)
    r, line = _run(tiny_bench, "tiny-serve")
    assert not line["correct"]


def test_sound_training_run_is_correct(tiny_bench):
    r, line = _run(tiny_bench, "tiny-train")
    assert line["correct"]
    assert list(line["checks"]) == ["loss_gap", "grad_norm_gap",
                                    "change_gap"]


def _patched_step(monkeypatch, fault):
    from repro.train import steps
    make = steps.make_train_step

    def make_faulty(model, cfg):
        inner = make(model, cfg)

        def step(params, opt, batch):
            if fault == "unchanged":
                _, _, m = inner(params, opt, batch)
                return params, opt, m
            half = {k: v[:(v.shape[0] + 1) // 2] for k, v in batch.items()}
            return inner(params, opt, half)
        return step
    monkeypatch.setattr(steps, "make_train_step", make_faulty)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_training_faults_are_caught(tiny_bench, monkeypatch, fault):
    _patched_step(monkeypatch, fault)
    r, line = _run(tiny_bench, "tiny-train")
    assert not line["correct"]
    gaps = {k: v["value"] for k, v in line["checks"].items()}
    if fault == "unchanged":
        assert gaps["change_gap"] == pytest.approx(1.0)
