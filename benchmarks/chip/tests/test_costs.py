"""Operation and byte counts against hand counts at the cells' shapes."""
import json
from pathlib import Path

import pytest

from costs import (decode_attention, flash_bwd, flash_fwd, least_seconds,
                   matmul, model_step, prefill_attention)

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
SERVE = json.loads((CONFIGS / "codeqwen1.5-7b.serve.json").read_text())
TRAIN = json.loads((CONFIGS / "codeqwen1.5-7b.train.json").read_text())
KIB = 1024


def test_layer_and_head_weights():
    assert model_step.layer_weights(SERVE) == (
        4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 13440)
    assert round(model_step.layer_weights(SERVE) / 1e6, 1) == 202.9
    steps = model_step.step_matmuls(SERVE, 32, 32)
    assert len(steps) == 7 * 8 + 1 and steps[-1] == (32, 4096, 92416)


def test_decode_kv_bytes_are_16_kib_per_token_at_8_layers():
    lengths = [100, 2048, 1, 0]
    flops, nbytes = decode_attention.cost(lengths, 32, 4, 128)
    io = 2 * len(lengths) * 32 * 128 * 2
    assert (nbytes - io) * 8 == sum(lengths) * 16 * KIB
    assert flops == 4 * 32 * 128 * sum(lengths)


def test_prefill_chunk_counts():
    flops, nbytes = prefill_attention.cost([(64, 64), (0, 10)], 32, 4, 128)
    keys = (64 * 64 + 64 * 65 / 2) + 10 * 11 / 2
    assert flops == 4 * 32 * 128 * keys
    assert nbytes == (2 * 4 * 128 * 2 * (128 + 10)
                      + 2 * (64 + 10) * 32 * 128 * 2)


def test_matmul_counts():
    flops, nbytes = matmul.cost(32, 4096, 13440, out_bytes=4)
    assert flops == 2 * 32 * 4096 * 13440
    assert nbytes == (32 * 4096 + 4096 * 13440) * 2 + 32 * 13440 * 4


def test_flash_counts_at_the_train_cell():
    b, h, s, hd = 3, 32, 4096, 128
    pairs = b * h * s * (s + 1) / 2
    assert flash_fwd.cost(b, h, s, hd)[0] == 4 * hd * pairs
    f, nb = flash_bwd.cost(b, h, s, hd)
    assert f == 10 * hd * pairs
    assert nb == b * s * hd * 2 * 8 * h + 8 * b * h * s


def test_train_step_flops():
    b, s = 3, 4096
    n = 2 * model_step.layer_weights(TRAIN) + 4096 * 11648
    attn = 4 * 32 * 128 * b * s * (s + 1) / 2 * 2
    assert model_step.train_flops(TRAIN, b, s) == pytest.approx(
        3 * (2 * n * b * s + attn), rel=1e-12)


def test_least_time_takes_the_binding_bound():
    pk = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    assert least_seconds(197e12, 1.0, pk) == 1.0
    assert least_seconds(1.0, 819e9, pk) == 1.0
