"""Plain float32 reference of a Qwen2 decoder (CodeQwen1.5), in jax.numpy.

It imports nothing of the program and takes nothing the program made.
Weights are drawn from the run's seed by the same recipe the program's
initializer follows (a seeded recipe is data, like the traffic), rounded
to the configuration's parameter type, and every matmul runs at
``Precision.HIGHEST``.  The block is the published one:

    h = x + Wo · attn(rope(Wq·n1(x) + bq), rope(Wk·n1(x) + bk), Wv·n1(x) + bv)
    y = h + Wd · (silu(Wg·n2(h)) ⊙ Wu·n2(h))

with ``n(x) = x / sqrt(mean(x²) + eps) · (1 + s)``, grouped-query attention
(query head ``i`` reads key/value head ``i // (heads / kv_heads)``), causal
softmax scaled by ``head_dim ** -0.5``, and rotary embeddings on the two
halves of each head.  Departure from the published parameterization: the
norm weight is stored as an offset ``s`` from 1 (the program's choice; it
matters for weight decay, not for the function).

``lowp="fp8"`` computes every matmul from float8 (e4m3) operands scaled
per tensor: the control that a check has to fail.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256      # query rows per attention block
ROW_BLOCK = 1024   # token rows per MLP block


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    theta: float
    eps: float
    param_dtype: str

    @classmethod
    def of(cls, config: Dict) -> "Sizes":
        d, h = config["hidden_size"], config["num_attention_heads"]
        return cls(config["num_hidden_layers"], d, h,
                   config["num_key_value_heads"], d // h,
                   config["intermediate_size"], config["vocab_size"],
                   float(config["rope_theta"]), float(config["rms_norm_eps"]),
                   config["program"]["param_dtype"])


# ----------------------------------------------------------------- weights

def _dense(key, shape, fan_in):
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)


def _round(tree, name):
    dt = {"bfloat16": jnp.bfloat16, "float32": F32}[name]
    return jax.tree.map(lambda a: a.astype(dt).astype(F32), tree)


def ends(key, s: Sizes) -> Dict:
    """Embedding, final norm and head."""
    ke, kh = jax.random.split(jax.random.fold_in(key, 0))
    return _round({"embed": jax.random.normal(ke, (s.vocab, s.d), F32),
                   "final_norm": jnp.zeros((s.d,), F32),
                   "head": _dense(kh, (s.d, s.vocab), s.d)}, s.param_dtype)


def layer(key, i, s: Sizes) -> Dict:
    """Layer ``i``'s weights (``i`` may be traced)."""
    k1, k2 = jax.random.split(jax.random.fold_in(key, 1000 + i))
    kq, kk, kv, ko, _ = jax.random.split(k1, 5)
    kg, ku, kd = jax.random.split(k2, 3)
    d, h, g, hd = s.d, s.heads, s.kv_heads, s.head_dim
    return _round({
        "ln1": jnp.zeros((d,), F32), "ln2": jnp.zeros((d,), F32),
        "wq": _dense(kq, (d, h, hd), d), "wk": _dense(kk, (d, g, hd), d),
        "wv": _dense(kv, (d, g, hd), d), "wo": _dense(ko, (h, hd, d), h * hd),
        "bq": jnp.zeros((h, hd), F32), "bk": jnp.zeros((g, hd), F32),
        "bv": jnp.zeros((g, hd), F32),
        "wg": _dense(kg, (d, s.ff), d), "wu": _dense(ku, (d, s.ff), d),
        "wd": _dense(kd, (s.ff, d), s.ff)}, s.param_dtype)


def params(key, s: Sizes) -> Dict:
    """The whole tree, as a training reference holds it."""
    return {"ends": ends(key, s),
            "layers": [layer(key, i, s) for i in range(s.layers)]}


# ----------------------------------------------------------------- forward

def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(a, b, lowp: Optional[str] = None):
    """``a (..., K) @ b (K, N)`` in float32 at HIGHEST, or from fp8."""
    if lowp == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, s_off, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s_off)


def rope(x, pos, theta):
    """x (B, S, H, hd), pos (B, S)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[..., None] * freqs
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, lowp=None):
    """Causal GQA attention, one block of query rows at a time.
    q (B, S, H, hd); k, v (B, S, G, hd)."""
    b, sq, h, hd = q.shape
    qblk = math.gcd(sq, Q_BLOCK)
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    if lowp == "fp8":
        k, v = _fp8(k), _fp8(v)
    nb = sq // qblk
    qb = jnp.moveaxis(q.reshape(b, nb, qblk, h, hd), 1, 0)
    kpos = jnp.arange(sq)

    @jax.checkpoint
    def block(args):
        qi, i = args
        if lowp == "fp8":
            qi = _fp8(qi)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HIGHEST)
        sc = sc * (hd ** -0.5)
        qpos = i * qblk + jnp.arange(qblk)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if lowp == "fp8":
            p = _fp8(p)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (qb, jnp.arange(nb)))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h * hd)


def _mlp(p, x, lowp):
    return mm(jax.nn.silu(mm(x, p["wg"], lowp)) * mm(x, p["wu"], lowp),
              p["wd"], lowp)


def block_fwd(p, x, pos, s: Sizes, lowp=None):
    """One decoder layer over x (B, S, d)."""
    b, sq, d = x.shape
    h = rmsnorm(x, p["ln1"], s.eps)
    q = mm(h, p["wq"].reshape(d, -1), lowp).reshape(b, sq, s.heads, -1)
    k = mm(h, p["wk"].reshape(d, -1), lowp).reshape(b, sq, s.kv_heads, -1)
    v = mm(h, p["wv"].reshape(d, -1), lowp).reshape(b, sq, s.kv_heads, -1)
    q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k = rope(q, pos, s.theta), rope(k, pos, s.theta)
    a = attention(q, k, v, lowp)
    x = x + mm(a, p["wo"].reshape(-1, d), lowp)
    rows = x.reshape(-1, d)
    n = rows.shape[0]
    blk = math.gcd(n, ROW_BLOCK)
    y = jax.lax.map(jax.checkpoint(
        lambda r: _mlp(p, rmsnorm(r, p["ln2"], s.eps), lowp)),
        rows.reshape(n // blk, blk, d))
    return x + y.reshape(b, sq, d)


def logits(e, x, s: Sizes, lowp=None):
    return mm(rmsnorm(x, e["final_norm"], s.eps), e["head"], lowp)


def loss(tree, tokens, labels, s: Sizes, lowp=None):
    """Mean next-token cross entropy over every position."""
    e = tree["ends"]
    x = e["embed"][tokens]
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    for p in tree["layers"]:
        x = jax.checkpoint(lambda p_, x_: block_fwd(p_, x_, pos, s, lowp))(
            p, x)
    lg = logits(e, x, s, lowp)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)
