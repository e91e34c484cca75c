"""Pipeline parallelism: streaming dataflow (§3.3) across a mesh axis.

The paper's iterative-stencil design — P replicated PEs connected by FIFO
channels, each computing one timestep — maps onto TPU pods as GPipe-style
pipeline parallelism: each `stage` (a contiguous group of layers) lives on
one slice of the ``stage`` mesh axis; microbatches stream through; the
channel between consecutive PEs is ``jax.lax.ppermute`` (the FIFO), and the
fill/drain bubble is exactly the paper's pipeline latency ``L`` in
``C = L + I*(N-1)``: with M microbatches and S stages the bubble fraction
is (S-1)/(M+S-1) — the §2.5 motivation at cluster scale.

Implementation: a shard_map over the stage axis running the classic
"rotating buffer" schedule.  All stages execute the same program (SPMD);
stage identity comes from ``jax.lax.axis_index``.  Used by the launch-time
option ``--pipeline-stages`` and validated numerically against the
unpartitioned model in tests (tests/test_pipeline_parallel.py).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x_microbatches: jax.Array,
    *,
    mesh: Mesh,
    stage_axis: str = "pod",
) -> jax.Array:
    """Run ``stage_fn`` as an S-stage pipeline over M microbatches.

    stage_params: pytree whose leaves have a leading stage axis (S, ...),
    sharded P(stage_axis, ...).  x_microbatches: (M, mb, ...) replicated
    over the stage axis.  Returns (M, mb, ...) outputs (from the last
    stage, broadcast).  M must be >= S.
    """
    n_stages = mesh.shape[stage_axis]
    m = x_microbatches.shape[0]
    assert m >= n_stages, (m, n_stages)
    n_ticks = m + n_stages - 1

    def body(params, xs):
        # params: (1, ...) local stage slice; xs: (M, mb, ...) replicated
        params = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(stage_axis)
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)        # current PE buffer
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (when valid)
            feed = xs[jnp.clip(t, 0, m - 1)]
            inp = jnp.where(sid == 0, feed, state)
            out = stage_fn(params, inp)
            # FIFO channel to the next PE (§3.3): rotate downstream
            nxt = jax.lax.ppermute(
                out, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage emits microbatch t-(S-1)
            emit_idx = t - (n_stages - 1)
            valid = emit_idx >= 0
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.maximum(emit_idx, 0)].set(out),
                lambda o: o, outs)
            return (nxt, outs), None

        (state, outs), _ = jax.lax.scan(
            tick, (state, outs), jnp.arange(n_ticks))
        # every device computed `outs`, but only the last stage's is real;
        # broadcast it with a masked psum (one collective at pipeline exit)
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """The §1.2 pipeline model applied to the stage pipeline."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
