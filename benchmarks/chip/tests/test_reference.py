"""The plain reference draws the program's weights from the seed and
computes the program's function: checked at the smoke widths on the CPU,
the program in float32 on its reference route."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import device, program
from conftest import TINY_SERVE, TINY_TRAIN
from reference import qwen2, serve_check

SEED = 2**33 + 77


def _program(config, **dt):
    from repro.core.memory import DtypePolicy
    from repro.models.transformer import ExecOptions, Model
    cfg = program.arch_config(config, dispatch="reference")
    return Model(cfg, dt=DtypePolicy(**dt), opts=ExecOptions(mode="run"))


@pytest.mark.parametrize("config", [TINY_SERVE, TINY_TRAIN],
                         ids=["bf16", "f32"])
def test_weights_match_the_programs(config):
    m = _program(config, param=program.dtype(
        config["program"]["param_dtype"]))
    got = m.init(device.key_for(SEED))
    ref = qwen2.params(device.key_for(SEED), qwen2.Sizes.of(config))
    pairs = [(got["embed"], ref["ends"]["embed"]),
             (got["head"], ref["ends"]["head"])]
    st = got["stack"][0]
    for i, lay in enumerate(ref["layers"]):
        for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv"):
            pairs.append((st["attn"][k][i], lay[k]))
        for k in ("wg", "wu", "wd"):
            pairs.append((st["mlp"][k][i], lay[k]))
    for a, b in pairs:
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b))


def test_forward_matches_the_program_in_f32():
    config = {**TINY_TRAIN, "num_hidden_layers": 3}
    m = _program(config, param=jnp.float32, compute=jnp.float32)
    key = device.key_for(SEED)
    tokens = jax.random.randint(jax.random.key(3), (2, 48), 0, 512)
    with jax.default_matmul_precision("highest"):
        want = m.forward(m.init(key), {"tokens": tokens})
        s = qwen2.Sizes.of(config)
        tree = qwen2.params(key, s)
        x = tree["ends"]["embed"][tokens]
        pos = jnp.broadcast_to(jnp.arange(48), (2, 48))
        for p in tree["layers"]:
            x = qwen2.block_fwd(p, x, pos, s)
        got = qwen2.logits(tree["ends"], x, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_served_gaps_are_zero_for_reference_argmax():
    """Tokens that are the reference's own argmax have gap 0; a token
    swapped for another has a gap of the logits' spread."""
    config = TINY_SERVE
    key = device.key_for(SEED)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 512, 20).astype(np.int32)
    out = []
    for _ in range(6):
        tokens, start, served, mask = serve_check.pad_batch(
            [(prompt, out + [0])], 2, 64, 12)
        _, _, top = serve_check.forward(key, config, tokens, start, served)
        out.append(int(top[0, len(out)]))
    tokens, start, served, mask = serve_check.pad_batch([(prompt, out)], 2,
                                                        64, 12)
    gaps, _, _ = serve_check.forward(key, config, tokens, start, served)
    assert serve_check.widest_gap(gaps, mask) == 0.0
    bad = out[:3] + [(out[3] + 1) % 512] + out[4:]
    tokens, start, served, mask = serve_check.pad_batch([(prompt, bad)], 2,
                                                        64, 12)
    gaps, _, _ = serve_check.forward(key, config, tokens, start, served)
    assert serve_check.widest_gap(gaps, mask) > 0.1
