"""The training check: three AdamW steps of the float32 reference.

The reference draws the same initial weights from the seed, takes the same
rows, and runs plain AdamW (global-norm clipping, bias-corrected moments,
decoupled weight decay, linear warmup and cosine decay) as the
configuration states it.  Its readings, per leaf (each layer's matrices
apart): the loss of each step, the clipped gradient the optimizer takes at
step 1, and the change of the weights after three steps.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import qwen2


def lr_at(opt: Dict, count: int) -> float:
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((count - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1 - opt["min_lr_ratio"]) * cos)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _grads(tree, tokens, labels, s, lowp, rows):
    def f(t):
        return qwen2.loss(t, tokens[:rows], labels[:rows], s, lowp)
    return jax.value_and_grad(f)(tree)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adamw(tree, g, m, v, count, lr, opt_scalars):
    b1, b2, eps, wd, clip = opt_scalars
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(
        gnorm, 1e-9)), g)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    new = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps)
                                  + wd * p), tree, m, v)
    return new, g, m, v


def leaf_norms(tree) -> Dict[str, float]:
    """Norm of each leaf, each layer's matrices apart."""
    out = {f"ends.{k}": v for k, v in tree["ends"].items()}
    for i, lay in enumerate(tree["layers"]):
        out.update({f"layer{i}.{k}": v for k, v in lay.items()})
    norms = jax.device_get({k: jnp.linalg.norm(v.reshape(-1))
                            for k, v in out.items()})
    return {k: float(v) for k, v in norms.items()}


def run(key, config: Dict, batches: List[np.ndarray], steps: int = 3,
        lowp: Optional[str] = None, rows: Optional[int] = None) -> Dict:
    """``steps`` reference steps on ``batches`` (each ``(B, S + 1)``).
    ``rows`` < B plants the fault "half the batch left out"."""
    s = qwen2.Sizes.of(config)
    opt = config["train"]["optimizer"]
    scal = tuple(float(opt[k]) for k in ("b1", "b2", "eps", "weight_decay",
                                         "grad_clip"))
    tree = qwen2.params(key, s)
    m = jax.tree.map(jnp.zeros_like, tree)
    v = jax.tree.map(jnp.zeros_like, tree)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            b = jnp.asarray(batches[i])
            loss, g = _grads(tree, b[:, :-1], b[:, 1:], s, lowp,
                             rows or b.shape[0])
            tree, g, m, v = _adamw(tree, g, m, v, float(i + 1),
                                   lr_at(opt, i + 1), scal)
            losses.append(float(loss))
            if i == 0:
                grad_norms = leaf_norms(g)
            del g
        del m, v
        start = qwen2.params(key, s)
        change = leaf_norms(jax.tree.map(jnp.subtract, tree, start))
    return {"losses": losses, "grad_norms": grad_norms, "change": change}


def gaps(prog: Dict, ref: Dict, moved_floor: float = 1e-3) -> Dict:
    """The three numbers compared.  Norm gaps are taken leaf by leaf,
    ``|norm_program - norm_reference|`` over the larger of the reference
    leaf's norm and the median leaf's; a leaf whose reference gradient is
    under ``moved_floor`` of the median leaf's moves by round-off alone
    and is left out of the change."""
    def worst(p, r, keep):
        med = float(np.median([r[k] for k in keep]))
        return max(abs(p[k] - r[k]) / max(r[k], med) for k in keep)

    gmed = float(np.median(list(ref["grad_norms"].values())))
    moved = [k for k, g in ref["grad_norms"].items()
             if g >= moved_floor * gmed]
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": worst(prog["grad_norms"], ref["grad_norms"],
                               list(ref["grad_norms"])),
        "change_gap": worst(prog["change"], ref["change"], moved),
        "left_out": sorted(set(ref["grad_norms"]) - set(moved)),
    }
