"""The control comes out not correct: the reference computed from fp8
operands (the precision below the configuration's bf16) in the program's
place, at the smoke widths on the CPU.  On the chip the same readings are
taken at the cells' sizes by ``tools/calibrate.py``."""
import numpy as np
import pytest

from chipbench import device
from conftest import TINY_MIX, TINY_SERVE, TINY_TRAIN, TINY_TRAIN_MIX
from reference import serve_check, train_check

SEEDS = (11, 2**31 + 3, 2**33 + 9)


def _greedy(key, config, prompt, n, span):
    """The reference's own greedy continuation of ``prompt``."""
    out = []
    for _ in range(n):
        toks, start, served, _ = serve_check.pad_batch(
            [(prompt, out + [0])], 1, len(prompt) + span, span)
        _, _, top = serve_check.forward(key, config, toks, start, served)
        out.append(int(top[0, len(out)]))
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_serving_control_fails_the_gap(seed):
    key = device.key_for(seed)
    rng = np.random.default_rng(seed)
    span = TINY_MIX["output_len"]["max"]
    seqs = []
    for _ in range(3):
        prompt = rng.integers(0, 512, 24).astype(np.int32)
        seqs.append((prompt, _greedy(key, TINY_SERVE, prompt, span, span)))
    toks, start, served, mask = serve_check.pad_batch(seqs, 3, 24 + span,
                                                      span)
    ref_served, ref_low = serve_check.control_gaps(key, TINY_SERVE, toks,
                                                   start, served)
    assert serve_check.widest_gap(ref_served, mask) == 0.0
    assert serve_check.widest_gap(ref_low, mask) > \
        TINY_MIX["check"]["max_gap"]


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_training_control_fails_a_gap(seed):
    mix = TINY_TRAIN_MIX
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, 512, (mix["batch"], mix["seq"] + 1))
               .astype(np.int32) for _ in range(3)]
    key = device.key_for(seed)
    ref = train_check.run(key, TINY_TRAIN, batches)
    same = train_check.gaps(ref, ref)
    assert same["loss_gap"] == same["grad_norm_gap"] == 0.0
    low = train_check.gaps(train_check.run(key, TINY_TRAIN, batches,
                                           lowp="fp8"), ref)
    lim = mix["check"]
    assert any(low[k] > lim[k]
               for k in ("loss_gap", "grad_norm_gap", "change_gap")), low
