"""Time the paged decode attention op alone on one chip.

    python3 scripts/time_decode_attention.py [--label NAME] [--out DIR]

Runs ``repro.kernels.attention.decode_attention`` at the serving cells'
widths (32 query heads over 4 KV heads, or one tensor-parallel shard of 8
over 1, head dim 128, page 64, 32 slots of 8192 tokens, a two-layer
stacked bf16 pool of 4097 pages read at a layer index) for a few length
patterns: every row full, the decode cells' few live rows, long prompts,
and a shard of the four-chip cell.  Each case runs ``--calls`` calls in
one jitted loop, alternating the layer so no call can be hoisted, and
reports the median over ``--repeats`` loops of the time per call and the
largest gap of one call's output to the f32 reference, with the grid
steps of a static (B, Hkv, n_tiles) grid and the live (row, tile) pairs
of these lengths, so that a per-step cost can be read off.  Where the op
takes a work list built once for a step's layers (``decode_work``), the
time per call with the list built once for the loop is reported beside
the time with each call building its own.
Fails without a TPU.  One JSON line per case on stdout, and all of them
in ``<out>/decode_attention_<label>.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.attention import decode_attention, ref
from repro.kernels.attention.decode import heuristic_pages_per_tile

try:                        # a step's shared work list (the op builds its
    from repro.kernels.attention.ops import decode_work   # own otherwise)
except ImportError:         # a checkout from before the work-list grid
    decode_work = None

HD, PAGE, SLOTS, MAX_LEN, LAYERS = 128, 64, 32, 8192, 2
N_PAGES = MAX_LEN // PAGE
POOL = 1 + SLOTS * N_PAGES


def _cases():
    """(name, q heads, kv heads, per-row lengths)."""
    full = [MAX_LEN] * SLOTS
    few = [600, 420, 910, 380, 750] + [0] * (SLOTS - 5)
    return [
        ("full_b8", 32, 4, full[:8]),
        ("full_b16", 32, 4, full[:16]),
        ("full_b32", 32, 4, full),
        ("decode_5_rows", 32, 4, few),
        ("prompt_2_rows", 32, 4, [7000, 2600] + [0] * (SLOTS - 2)),
        ("one_token_rows", 32, 4, [1] * SLOTS),
        ("tp4_shard_20_rows", 8, 1, [300 + 37 * i for i in range(20)]
         + [0] * (SLOTS - 20)),
    ]


def _grid_steps(lengths, hkv):
    ppt = heuristic_pages_per_tile(N_PAGES, PAGE)
    tile = ppt * PAGE
    live = sum(-(-n // tile) for n in lengths if n > 0)
    return len(lengths) * hkv * (N_PAGES // ppt), hkv * live


def _time_case(name, h, hkv, lengths, calls, repeats, pools):
    b = len(lengths)
    kp, vp = pools[hkv]
    rng = np.random.default_rng(0)
    table = jnp.asarray((1 + rng.permutation(POOL - 1))[:b * N_PAGES]
                        .reshape(b, N_PAGES), jnp.int32)
    q = jax.random.normal(jax.random.key(1), (b, h, HD), jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)

    def timed(shared):
        @jax.jit
        def loop(q, kp, vp, table, lens):
            # the work list is built once for every call, as a decode step
            # builds it once for its layers, or by each call
            kw = ({"work": decode_work(lens, table, page=PAGE)}
                  if shared else {})

            def body(i, acc):
                out = decode_attention(q, kp, vp, table, lens,
                                       layer=i % LAYERS, plan="heuristic",
                                       **kw)
                return acc + out[0, 0, 0]
            return jax.lax.fori_loop(0, calls, body, jnp.float32(0))

        jax.block_until_ready(loop(q, kp, vp, table, lens))  # compile, warm
        secs = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(loop(q, kp, vp, table, lens))
            secs.append(time.perf_counter() - t0)
        return [round(s / calls * 1e6, 3) for s in secs]

    got = decode_attention(q, kp, vp, table, lens, layer=1,
                           plan="heuristic")
    want = ref.decode_attention_ref(q, kp, vp, table, lens, layer=1)
    err = float(jnp.max(jnp.abs(got - want)))
    static, live = _grid_steps(lengths, hkv)
    own = timed(False)
    row = {"case": name, "b": b, "heads": h, "kv_heads": hkv,
           "live_tokens": int(sum(lengths)), "static_grid_steps": static,
           "live_grid_steps": live, "us_per_call": statistics.median(own),
           "max_abs_err_vs_ref": err, "us_per_call_all": own}
    if decode_work is not None:
        shared = timed(True)
        row.update(us_per_call_shared_work=statistics.median(shared),
                   us_per_call_shared_work_all=shared)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--label", default="run")
    ap.add_argument("--out", default="results/scratch")
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    pools = {}
    for hkv in (4, 1):
        shape = (LAYERS, POOL, hkv, PAGE, HD)
        k1, k2 = jax.random.split(jax.random.key(hkv))
        pools[hkv] = (jax.random.normal(k1, shape, jnp.bfloat16),
                      jax.random.normal(k2, shape, jnp.bfloat16))
    rows = []
    for name, h, hkv, lengths in _cases():
        row = _time_case(name, h, hkv, lengths, args.calls, args.repeats,
                         pools)
        row.update(label=args.label, device=dev.device_kind)
        print(json.dumps(row), flush=True)
        rows.append(row)
    out = Path(args.out)
    os.makedirs(out, exist_ok=True)
    (out / f"decode_attention_{args.label}.json").write_text(
        json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
