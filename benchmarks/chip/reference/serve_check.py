"""The served-token check: how far below the reference's best does each
token the program served lie?

For every sampled request the reference runs once over prompt + served
tokens (full causal forward, float32), and at each position that produced
a served token reads ``max(logits) - logits[served]``, the gap.  A greedy
server that computes the configured model serves tokens whose gap is
rounding; a wrong token has a gap of the logits' own spread.  Shapes are
fixed by the mix (``rows`` sequences of ``length`` tokens, ``span`` served
positions each), so the reference compiles once per mix.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import qwen2


def pad_batch(seqs: Sequence[Tuple[np.ndarray, Sequence[int]]], rows: int,
              length: int, span: int):
    """tokens (rows, length), first served position (rows,), served
    tokens (rows, span) and their mask, from (prompt, served) pairs."""
    tokens = np.zeros((rows, length), np.int32)
    start = np.zeros((rows,), np.int32)
    served = np.zeros((rows, span), np.int32)
    mask = np.zeros((rows, span), bool)
    for i, (prompt, out) in enumerate(seqs):
        full = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(out, np.int32)])
        if len(full) > length or len(out) > span:
            raise ValueError(f"sequence {i} exceeds the check's shapes")
        tokens[i, :len(full)] = full
        start[i] = len(prompt) - 1
        served[i, :len(out)] = out
        mask[i, :len(out)] = True
    return tokens, start, served, mask


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(key, i, s):
    return qwen2.layer(key, i, s)


@functools.partial(jax.jit, static_argnums=(1,))
def _ends(key, s):
    return qwen2.ends(key, s)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block(p, x, pos, s, lowp):
    return qwen2.block_fwd(p, x, pos, s, lowp)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _read(e, x, start, served, others, s, lowp):
    """Per sequence: the logits at the served positions, reduced to the
    gap of each served token, the gap of each token in ``others``, and
    the argmax."""
    span = served.shape[1]

    def one(args):
        xi, st, sv, ot = args
        rows = jax.lax.dynamic_slice_in_dim(xi, st, span, axis=0)
        lg = qwen2.logits(e, rows, s, lowp)
        best = lg.max(-1)
        pick = lambda t: best - jnp.take_along_axis(lg, t[:, None], -1)[:, 0]
        return pick(sv), pick(ot), jnp.argmax(lg, -1).astype(jnp.int32)

    return jax.lax.map(one, (x, start, served, others))


def forward(key, config, tokens, start, served, others=None,
            lowp: Optional[str] = None):
    """Run the reference over ``tokens`` layer by layer; returns (gaps of
    ``served``, gaps of ``others``, argmax), each (rows, span)."""
    s = qwen2.Sizes.of(config)
    length = tokens.shape[1]
    pad = -length % qwen2.Q_BLOCK
    tokens = jnp.pad(jnp.asarray(tokens), ((0, 0), (0, pad)))
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    e = _ends(key, s)
    x = e["embed"][tokens]
    for i in range(s.layers):
        x = _block(_layer(key, i, s), x, pos, s, lowp)
    others = served if others is None else others
    out = _read(e, x, jnp.asarray(start), jnp.asarray(served),
                jnp.asarray(others), s, lowp)
    return [np.asarray(a) for a in out]


def widest_gap(gaps: np.ndarray, mask: np.ndarray) -> float:
    return float(np.max(np.where(mask, gaps, -np.inf))) if mask.any() \
        else float("nan")


def control_gaps(key, config, tokens, start, served) -> List[np.ndarray]:
    """The control: at each served position, the token that an fp8
    reference puts first, and its gap under the float32 reference."""
    _, _, low_top = forward(key, config, tokens, start, served, lowp="fp8")
    ref_served, ref_low, _ = forward(key, config, tokens, start, served,
                                     others=low_top)
    return [ref_served, ref_low]
