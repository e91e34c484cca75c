"""Per-kernel candidate enumeration — the paper's design space, pruned.

Each function returns a list of candidate plan dicts for one kernel at one
problem shape.  A plan dict holds the kernel's tunable call kwargs plus an
optional ``"level"`` (paper stage T1→T3, as an int for JSON friendliness).
The paper's transformation parameters map onto the kernels' knobs as:

  tile geometry (§3.4)    -> bm/bn/bk (matmul), block_rows (stencil)
  vector width (§3.1)     -> lane-dim block sizes: block_kv, block (histogram),
                             block_sources (nbody)
  accumulator lanes (§2.1)-> row-dim accumulator tiles: block_q,
                             block_targets
  prefetch depth (§4.2)   -> double-buffering (TilePlanner double_buffer)
  level (T1→T3)           -> reference lowering vs Pallas kernel

Every candidate is feasibility-pruned against the VMEM budget through the
same ``TilePlanner`` working-set arithmetic the heuristics use, so the
tuner never times (or caches) a plan the hardware could not hold.  The
first candidate of every space is the exact heuristic the kernel would
pick on its own — the sweep can therefore only match or beat the default,
which is what makes tuned-vs-heuristic rows meaningful.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from ..core.model import HardwareSpec
from ..core.plan import Level, TUNE_PREFETCH_DEPTHS
from ..core.scaling import TilePlanner

PlanDict = Dict[str, Any]

# modest default: sweeps stay tens-of-candidates even on big shapes
MAX_CANDIDATES = 8


def _dedup(cands: List[PlanDict], cap: int) -> List[PlanDict]:
    seen, out = set(), []
    for c in cands:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            out.append(c)
        if len(out) >= cap:
            break
    return out


def _divisors(n: int, cands: Sequence[int]) -> List[int]:
    return [c for c in cands if c <= n and n % c == 0]


def matmul_space(shape: Sequence[int], dtype_bytes: int = 4, *,
                 hw: Optional[HardwareSpec] = None,
                 max_candidates: int = MAX_CANDIDATES) -> List[PlanDict]:
    """shape = (m, k, n) for C[m,n] = A[m,k] @ B[k,n]."""
    m, k, n = shape
    heur = TilePlanner(hw).plan_matmul(m, n, k, in_bytes=dtype_bytes)
    # knob sweep of the heuristic tiles goes BEFORE the tile enumeration so
    # the max_candidates cap can never silently drop a whole axis: prefetch
    # depth 1 (§4.2 off) halves the A/B working set, so it is feasible
    # whenever the double-buffered plan is
    cands: List[PlanDict] = [
        {"level": int(Level.T3_REPLICATED), "bm": heur.bm, "bn": heur.bn,
         "bk": heur.bk, "prefetch_depth": pf}
        for pf in sorted(TUNE_PREFETCH_DEPTHS, reverse=True)
    ]
    cands.append({"level": int(Level.T1_PIPELINED)})
    for plan in TilePlanner(hw).enumerate_matmul(m, n, k,
                                                 in_bytes=dtype_bytes):
        cands.append({"level": int(Level.T3_REPLICATED), "bm": plan.bm,
                      "bn": plan.bn, "bk": plan.bk, "prefetch_depth": 2})
    return _dedup(cands, max_candidates)


def quantized_matmul_space(shape: Sequence[int], dtype_bytes: int = 4, *,
                           hw: Optional[HardwareSpec] = None,
                           max_candidates: int = MAX_CANDIDATES
                           ) -> List[PlanDict]:
    """shape = (m, k, n) — the int8-weight matmul's own plan namespace.

    Same geometry axes as ``matmul_space``; ``dtype_bytes`` is the
    ACTIVATION width, and charging the int8 B tile at that width is a
    conservative over-estimate, so every emitted candidate stays feasible
    under the plain-matmul VMEM arithmetic the cache reuses."""
    return matmul_space(shape, dtype_bytes, hw=hw,
                        max_candidates=max_candidates)


def stencil_space(shape: Sequence[int], dtype_bytes: int = 4, *,
                  hw: Optional[HardwareSpec] = None,
                  max_candidates: int = MAX_CANDIDATES) -> List[PlanDict]:
    """shape = (rows, cols)."""
    rows, cols = shape
    planner = TilePlanner(hw)
    feasible = [br for br, _ in planner.enumerate_stencil(
        rows, cols, dtype_bytes=dtype_bytes,
        candidates=_divisors(rows, (8, 16, 32, 64, 128, 256, 512, 1024)))]
    try:
        br_heur, _ = planner.plan_stencil(rows, cols,
                                          dtype_bytes=dtype_bytes)
        br_heur = min(br_heur, rows)
        while rows % br_heur:
            br_heur //= 2
    except ValueError:
        # rows too small for the planner's default candidate grid: the
        # "heuristic" becomes the best divisor-aligned feasible block
        br_heur = feasible[0] if feasible else None
    cands: List[PlanDict] = []
    if br_heur is not None:
        cands.append({"level": int(Level.T3_REPLICATED),
                      "block_rows": br_heur})
    cands.append({"level": int(Level.T1_PIPELINED)})
    for br in sorted(set(feasible), reverse=True):
        cands.append({"level": int(Level.T3_REPLICATED), "block_rows": br})
    return _dedup(cands, max_candidates)


def attention_space(shape: Sequence[int], dtype_bytes: int = 2, *,
                    hw: Optional[HardwareSpec] = None,
                    max_candidates: int = MAX_CANDIDATES) -> List[PlanDict]:
    """shape = (batch, heads, seq, head_dim)."""
    _, _, s, hd = shape
    budget = TilePlanner(hw).budget
    cands: List[PlanDict] = [
        {"level": int(Level.T3_REPLICATED), "block_q": min(512, s),
         "block_kv": min(512, s)},
        {"level": int(Level.T1_PIPELINED)},
    ]
    for bq in _divisors(s, (512, 256, 128, 64, 32)):
        for bkv in _divisors(s, (512, 256, 128, 64, 32)):
            # working set: Q tile + K/V tiles + logits tile + O/m/l carry,
            # double-buffered KV streams (§4.2)
            vmem = (bq * hd + 2 * 2 * bkv * hd + bq * bkv
                    + 2 * bq * hd) * dtype_bytes
            if vmem <= budget:
                cands.append({"level": int(Level.T3_REPLICATED),
                              "block_q": bq, "block_kv": bkv})
    return _dedup(cands, max_candidates)


def _attn_bwd_vmem(bq: int, bkv: int, hd: int, dtype_bytes: int) -> int:
    """Working set of the fused backward's larger (dKV) kernel: K/V tiles
    resident, Q streamed double-buffered (§4.2) in the input dtype; dO
    streams, the f32 dK/dV accumulators, the recomputed P and dS tiles,
    and the lse/di row carries all in f32."""
    return ((2 * bkv * hd + 2 * 2 * bq * hd) * dtype_bytes
            + (2 * 2 * bq * hd + 2 * bkv * hd + 2 * bq * bkv + 2 * bq) * 4)


def flash_attention_bwd_space(shape: Sequence[int], dtype_bytes: int = 2, *,
                              hw: Optional[HardwareSpec] = None,
                              max_candidates: int = MAX_CANDIDATES
                              ) -> List[PlanDict]:
    """shape = (batch, heads, seq, head_dim) — same key as the forward.

    The backward design space is the recompute schedule: ``block_q`` /
    ``block_kv`` tile geometry for the dQ/dKV kernels (level T3), or level
    T1 — the dense reference VJP, i.e. the "stash the whole score matrix"
    schedule that wins when (S, S) is small enough to re-derive wholesale.
    The tuner's per-shape level pick IS the recompute-vs-stash threshold.
    """
    _, _, s, hd = shape
    budget = TilePlanner(hw).budget
    cands: List[PlanDict] = [
        {"level": int(Level.T3_REPLICATED), "block_q": min(256, s),
         "block_kv": min(256, s)},
        {"level": int(Level.T1_PIPELINED)},
    ]
    for bq in _divisors(s, (256, 128, 64, 32)):
        for bkv in _divisors(s, (256, 128, 64, 32)):
            if _attn_bwd_vmem(bq, bkv, hd, dtype_bytes) <= budget:
                cands.append({"level": int(Level.T3_REPLICATED),
                              "block_q": bq, "block_kv": bkv})
    return _dedup(cands, max_candidates)


def histogram_space(shape: Sequence[int], dtype_bytes: int = 4, *,
                    hw: Optional[HardwareSpec] = None,
                    max_candidates: int = MAX_CANDIDATES) -> List[PlanDict]:
    """shape = (n_values, n_bins)."""
    n, n_bins = shape
    budget = TilePlanner(hw).budget
    cands: List[PlanDict] = [
        {"level": int(Level.T3_REPLICATED), "block": min(2048, n)},
        {"level": int(Level.T1_PIPELINED)},
    ]
    for block in _divisors(n, (8192, 4096, 2048, 1024, 512, 256)):
        if block % 8:
            continue
        # one-hot tile (block, n_bins) + value block + bin accumulator
        vmem = (block * n_bins + block) * dtype_bytes + n_bins * 4
        if vmem <= budget:
            cands.append({"level": int(Level.T3_REPLICATED), "block": block})
    return _dedup(cands, max_candidates)


def nbody_space(shape: Sequence[int], dtype_bytes: int = 4, *,
                hw: Optional[HardwareSpec] = None,
                max_candidates: int = MAX_CANDIDATES) -> List[PlanDict]:
    """shape = (n_bodies,)."""
    (n,) = shape
    budget = TilePlanner(hw).budget
    cands: List[PlanDict] = [
        {"level": int(Level.T3_REPLICATED), "block_targets": min(512, n),
         "block_sources": min(512, n)},
        {"level": int(Level.T1_PIPELINED)},
    ]
    for bt in _divisors(n, (512, 256, 128, 64, 32)):
        for bs in _divisors(n, (512, 256, 128, 64, 32)):
            # resident targets (pos+acc) + streamed source block (pos+mass,
            # double-buffered) + (bt, bs) pairwise distance tile
            vmem = (4 * bt + 2 * 4 * bs + bt * bs) * dtype_bytes
            if vmem <= budget:
                cands.append({"level": int(Level.T3_REPLICATED),
                              "block_targets": bt, "block_sources": bs})
    return _dedup(cands, max_candidates)


def _decode_vmem(grp: int, ppt: int, page: int, hd: int, pf: int,
                 dtype_bytes: int) -> int:
    """Per-grid-step working set of the paged decode kernel: q group tile,
    ``ppt`` K and V page streams (x ``pf`` pipeline buffers, §4.2), the
    (grp, ppt*page) score tile, and the m/l/acc carry."""
    return (grp * hd + 2 * pf * ppt * page * hd + grp * ppt * page
            + 2 * grp * hd) * dtype_bytes


def decode_attention_space(shape: Sequence[int], dtype_bytes: int = 2, *,
                           hw: Optional[HardwareSpec] = None,
                           max_candidates: int = MAX_CANDIDATES
                           ) -> List[PlanDict]:
    """shape = (slots, heads, n_pages, page_size, head_dim).

    The decode plan space is the serving-cache design space: ``page_size``
    echoes the pool layout the plan was tuned on (the serve scheduler picks
    its layout by comparing tuned entries across page sizes),
    ``pages_per_tile`` is the KV-tile geometry the kernel consumes, and
    ``prefetch_depth`` is the §4.2 pipeline-buffer count the feasibility
    arithmetic charges for.
    """
    from ..kernels.attention.decode import heuristic_pages_per_tile
    b, h, n_pages, page, hd = shape
    budget = TilePlanner(hw).budget
    grp = h                      # conservative GQA bound (grp = h / hkv)
    ppt_h = heuristic_pages_per_tile(n_pages, page)
    cands: List[PlanDict] = [
        {"level": int(Level.T3_REPLICATED), "page_size": page,
         "pages_per_tile": ppt_h, "prefetch_depth": pf}
        for pf in sorted(TUNE_PREFETCH_DEPTHS, reverse=True)
    ]
    # the reference lowering also records the layout it was timed on, so
    # the serve scheduler's page-size pick works whichever level wins
    cands.append({"level": int(Level.T1_PIPELINED), "page_size": page})
    for ppt in (16, 8, 4, 2, 1):
        if ppt > n_pages:
            continue
        for pf in sorted(TUNE_PREFETCH_DEPTHS, reverse=True):
            if _decode_vmem(grp, ppt, page, hd, pf, dtype_bytes) <= budget:
                cands.append({"level": int(Level.T3_REPLICATED),
                              "page_size": page, "pages_per_tile": ppt,
                              "prefetch_depth": pf})
    return _dedup(cands, max_candidates)


def _prefill_vmem(rows: int, ppt: int, page: int, hd: int, pf: int,
                  dtype_bytes: int) -> int:
    """Per-grid-step working set of the paged prefill kernel: the
    (chunk*grp, hd) query tile, ``ppt`` K and V page streams (x ``pf``
    pipeline buffers, §4.2), the (rows, ppt*page) score tile, and the
    m/l/acc carry."""
    return (rows * hd + 2 * pf * ppt * page * hd + rows * ppt * page
            + 2 * rows * hd) * dtype_bytes


def prefill_attention_space(shape: Sequence[int], dtype_bytes: int = 2, *,
                            hw: Optional[HardwareSpec] = None,
                            max_candidates: int = MAX_CANDIDATES
                            ) -> List[PlanDict]:
    """shape = (slots, chunk, heads, n_pages, page_size, head_dim).

    The prefill plan space mirrors decode's (it is the same paged-KV
    streaming problem with a chunk of query rows instead of one):
    ``page_size`` echoes the pool layout, ``pages_per_tile`` is the
    KV-tile geometry, ``prefetch_depth`` the §4.2 pipeline-buffer count —
    but feasibility charges for the (chunk * grp, ppt * page) score tile,
    which is what separates it from the decode space.
    """
    from ..kernels.attention.decode import heuristic_pages_per_tile
    b, c, h, n_pages, page, hd = shape
    budget = TilePlanner(hw).budget
    rows = c * h                 # conservative GQA bound (grp = h / hkv)
    ppt_h = heuristic_pages_per_tile(n_pages, page)
    cands: List[PlanDict] = [
        {"level": int(Level.T3_REPLICATED), "page_size": page,
         "pages_per_tile": ppt_h, "prefetch_depth": pf}
        for pf in sorted(TUNE_PREFETCH_DEPTHS, reverse=True)
    ]
    cands.append({"level": int(Level.T1_PIPELINED), "page_size": page})
    for ppt in (16, 8, 4, 2, 1):
        if ppt > n_pages:
            continue
        for pf in sorted(TUNE_PREFETCH_DEPTHS, reverse=True):
            if _prefill_vmem(rows, ppt, page, hd, pf, dtype_bytes) <= budget:
                cands.append({"level": int(Level.T3_REPLICATED),
                              "page_size": page, "pages_per_tile": ppt,
                              "prefetch_depth": pf})
    return _dedup(cands, max_candidates)


SPACES = {
    "matmul": matmul_space,
    "quantized_matmul": quantized_matmul_space,
    "stencil": stencil_space,
    "attention": attention_space,
    "flash_attention_bwd": flash_attention_bwd_space,
    "decode_attention": decode_attention_space,
    "prefill_attention": prefill_attention_space,
    "histogram": histogram_space,
    "nbody": nbody_space,
}


# ------------------------------------------------------------- feasibility
def plan_feasible(kernel: str, shape: Sequence[int], plan: PlanDict, *,
                  dtype_bytes: int = 4, hw: Optional[HardwareSpec] = None) -> bool:
    """Is a tuned plan dict VMEM-feasible for ``shape``?

    The single feasibility oracle behind the cache's nearest-shape lookup:
    a plan tuned on shape A may only be transplanted onto query shape B if
    its working set — computed through the same TilePlanner arithmetic the
    heuristics and the space enumerations use — fits the VMEM budget at B
    (and, where a kernel demands it, its tiles divide B's dims).  Non-T3
    plans (reference lowerings) claim no VMEM and are always feasible.
    """
    level = plan.get("level")
    if level is not None and level != int(Level.T3_REPLICATED):
        return True
    budget = TilePlanner(hw).budget
    if kernel == "quantized_matmul":
        # int8 B only shrinks the working set vs the plain-matmul charge
        return plan_feasible("matmul", shape, plan,
                             dtype_bytes=dtype_bytes, hw=hw)
    if kernel == "matmul":
        m, k, n = shape
        bm = min(plan["bm"], m)
        bn = min(plan["bn"], n)
        bk = min(plan["bk"], k)
        if m % bm or n % bn or k % bk:
            return False      # matmul_pallas rejects ragged grids
        planner = TilePlanner(
            hw, double_buffer=plan.get("prefetch_depth", 2) >= 2)
        try:
            planner.plan_from_tiles(m, n, k, bm, bn, bk,
                                    in_bytes=dtype_bytes)
        except ValueError:
            return False
        return True
    if kernel == "attention":
        _, _, s, hd = shape
        bq = min(plan["block_q"], s)
        bkv = min(plan["block_kv"], s)
        vmem = (bq * hd + 2 * 2 * bkv * hd + bq * bkv
                + 2 * bq * hd) * dtype_bytes
        return vmem <= budget
    if kernel == "flash_attention_bwd":
        _, _, s, hd = shape
        bq = min(plan["block_q"], s)
        bkv = min(plan["block_kv"], s)
        return _attn_bwd_vmem(bq, bkv, hd, dtype_bytes) <= budget
    if kernel == "decode_attention":
        _, h, n_pages, page, hd = shape
        # the kernel pads the logical page axis, so pages_per_tile never
        # needs to divide n_pages — clamp and recheck the working set
        # against the QUERY layout's page size (plans transplant across
        # page sizes; tile geometry is what carries over)
        ppt = max(1, min(plan["pages_per_tile"], n_pages))
        pf = 2 if plan.get("prefetch_depth", 2) >= 2 else 1
        return _decode_vmem(h, ppt, page, hd, pf, dtype_bytes) <= budget
    if kernel == "prefill_attention":
        _, c, h, n_pages, page, hd = shape
        ppt = max(1, min(plan["pages_per_tile"], n_pages))
        pf = 2 if plan.get("prefetch_depth", 2) >= 2 else 1
        return _prefill_vmem(c * h, ppt, page, hd, pf,
                             dtype_bytes) <= budget
    if kernel == "stencil":
        rows, cols = shape
        br = min(plan["block_rows"], rows)
        if rows % br:
            return False
        halo = 1
        vmem = ((br + 2 * halo) * (cols + 2 * halo) + br * cols) \
            * dtype_bytes * 2
        return vmem <= budget
    if kernel == "histogram":
        n, n_bins = shape
        block = min(plan["block"], n)
        if n % block:
            return False
        vmem = (block * n_bins + block) * dtype_bytes + n_bins * 4
        return vmem <= budget
    if kernel == "nbody":
        (n,) = shape
        bt = min(plan["block_targets"], n)
        bs = min(plan["block_sources"], n)
        if n % bt or n % bs:
            return False
        vmem = (4 * bt + 2 * 4 * bs + bt * bs) * dtype_bytes
        return vmem <= budget
    return False                  # unknown kernel: never transplant
