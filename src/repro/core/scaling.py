"""Scaling transformations (paper §3): vectorization, replication, tiling.

On the FPGA, scaling = folding pipelined loops into unrolled hardware.  On
the TPU the "unrolled hardware" already exists (8x128 VPU lanes, 128x128 MXU,
N chips) — the transformation becomes *choosing shapes and shardings that
keep it fed*:

* vectorization §3.1  -> pad/align trailing dims to (sublane, lane) tiles,
* replication  §3.2   -> reuse-fed parallelism: K-blocking in kernels,
                         TP/EP sharding across chips,
* tiling       §3.4   -> ``TilePlanner``: solve BlockSpec shapes against the
                         VMEM budget, the paper's "fit fast memory" objective.

``TilePlanner`` is used by every Pallas kernel in ``repro.kernels`` to derive
its BlockSpecs, so the kernels' VMEM claims are *planned*, not guessed — the
roofline napkin math in EXPERIMENTS.md §Perf reads straight off it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

from .model import TPU_V5E, HardwareSpec, device_hardware


def round_up(x: int, to: int) -> int:
    return ((x + to - 1) // to) * to


def vector_pad(shape: Sequence[int], dtype_bytes: int = 4,
               hw: HardwareSpec = TPU_V5E) -> Tuple[int, ...]:
    """Vectorization §3.1: the lane-aligned shape the VPU actually processes.

    Trailing dim pads to the 128-lane width; the second-to-last pads to the
    sublane count scaled by the packing factor of the dtype (bf16 packs 2x,
    int8 4x) — narrower types widen W, the paper's W_max = B/(f*S).
    """
    if not shape:
        return tuple(shape)
    packing = max(1, 4 // dtype_bytes)
    out = list(shape)
    out[-1] = round_up(out[-1], hw.lane)
    if len(out) >= 2:
        out[-2] = round_up(out[-2], hw.sublane * packing)
    return tuple(out)


def lane_utilization(shape: Sequence[int], dtype_bytes: int = 4,
                     hw: HardwareSpec = TPU_V5E) -> float:
    """Fraction of VPU lanes doing useful work for this (unpadded) shape."""
    padded = vector_pad(shape, dtype_bytes, hw)
    used = math.prod(shape) if shape else 1
    total = math.prod(padded) if padded else 1
    return used / total


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """A solved tiling for a matmul-like kernel (bm, bn, bk blocks)."""

    bm: int
    bn: int
    bk: int
    vmem_bytes: int          # working set incl. double buffering
    grid: Tuple[int, ...]    # (m/bm, n/bn, k/bk)
    flops_per_step: float
    hbm_bytes_per_step: float

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops_per_step / max(self.hbm_bytes_per_step, 1)


class TilePlanner:
    """Tiling §3.4 as a solver: pick MXU-aligned (bm, bn, bk) maximizing
    arithmetic intensity subject to the VMEM budget.

    Working set per grid step for C[bm,bn] += A[bm,bk] @ B[bk,bn]:
        A-block + B-block (double-buffered: x2 for DMA overlap, the paper's
        memory oversubscription §4.2) + C-accumulator (single, revisited)
        + the f32 C output block, which the pipeline double-buffers for its
        write-back like the inputs.
    Larger bm*bn raises reuse of each loaded A/B element — the §3.2
    "replication fed by reuse" argument in shape form.
    """

    def __init__(self, hw: Optional[HardwareSpec] = None, *,
                 vmem_fraction: float = 0.75,
                 double_buffer: bool = True):
        # default: the chip JAX runs on (``device_hardware``), v5e off-TPU
        self.hw = hw if hw is not None else device_hardware()
        self.budget = int(self.hw.vmem_bytes * vmem_fraction)
        self.double_buffer = double_buffer

    def plan_from_tiles(self, m: int, n: int, k: int,
                        bm: int, bn: int, bk: int, *,
                        in_bytes: int = 2, acc_bytes: int = 4,
                        out_bytes: int = 4) -> TilePlan:
        """Materialize the TilePlan for explicit (bm, bn, bk) tiles, or raise
        if the working set exceeds the VMEM budget.  This is the single
        feasibility check shared by the heuristic solver, the autotuner's
        space enumeration, and cache-deserialized plans."""
        buf = 2 if self.double_buffer else 1
        vmem = ((bm * bk + bk * bn) * in_bytes + bm * bn * out_bytes) * buf \
            + bm * bn * acc_bytes
        if vmem > self.budget:
            raise ValueError(
                f"tiles ({bm},{bn},{bk}) need {vmem} bytes of VMEM, "
                f"budget is {self.budget}")
        grid = (math.ceil(m / bm), math.ceil(n / bn), math.ceil(k / bk))
        flops = 2.0 * bm * bn * bk
        hbm = (bm * bk + bk * bn) * in_bytes
        return TilePlan(bm, bn, bk, vmem, grid, flops, hbm)

    def enumerate_matmul(self, m: int, n: int, k: int, *,
                         in_bytes: int = 2, acc_bytes: int = 4,
                         candidates: Optional[Sequence[int]] = None
                         ) -> List[TilePlan]:
        """All feasible MXU-aligned tilings within the VMEM budget — the
        autotuner's matmul design space (§3.4 as an enumerable set rather
        than a point solution).  Sorted best-first by the heuristic order
        so `[0]`, when non-empty, is what ``plan_matmul`` returns."""
        cands = list(candidates or (128, 256, 512, 1024, 2048))
        mxu = self.hw.mxu_dim

        def tiles(dim: int) -> List[int]:
            # tiles must divide the (clamped) problem dim: matmul_pallas
            # shrinks b to min(b, dim) and rejects ragged grids.  A dim no
            # candidate divides (a tensor-parallel shard such as
            # 13440 / 4 = 3360) takes one whole-dim block, which the TPU
            # accepts at any size
            return [b for b in cands
                    if b <= round_up(dim, mxu) and not dim % min(b, dim)] \
                or [dim]

        plans: List[TilePlan] = []
        for bm in tiles(m):
            for bn in tiles(n):
                for bk in tiles(k):
                    try:
                        plans.append(self.plan_from_tiles(
                            m, n, k, bm, bn, bk,
                            in_bytes=in_bytes, acc_bytes=acc_bytes))
                    except ValueError:
                        continue
        plans.sort(key=_plan_order_key, reverse=True)
        return plans

    def plan_matmul(self, m: int, n: int, k: int, *,
                    in_bytes: int = 2, acc_bytes: int = 4,
                    candidates: Optional[Sequence[int]] = None) -> TilePlan:
        plans = self.enumerate_matmul(m, n, k, in_bytes=in_bytes,
                                      acc_bytes=acc_bytes,
                                      candidates=candidates)
        if not plans:
            raise ValueError(
                f"no MXU-aligned tiling of ({m},{n},{k}) fits "
                f"{self.budget} bytes of VMEM")
        return plans[0]

    def enumerate_stencil(self, rows: int, cols: int, halo: int = 1, *,
                          dtype_bytes: int = 4,
                          candidates: Optional[Sequence[int]] = None
                          ) -> List[Tuple[int, int]]:
        """All feasible (brows, bcols) stencil blocks within the VMEM budget,
        sorted best-first by halo waste (then larger blocks) — the
        autotuner's stencil design space."""
        cands = list(candidates or (128, 256, 512, 1024, 2048, 4096))
        feasible = []
        for br in cands:
            if br > round_up(rows, self.hw.sublane):
                continue
            for bc in cands:
                if bc > round_up(cols, self.hw.lane):
                    continue
                vmem = ((br + 2 * halo) * (bc + 2 * halo) + br * bc) \
                    * dtype_bytes * 2
                if vmem > self.budget:
                    continue
                waste = ((br + 2 * halo) * (bc + 2 * halo)) / (br * bc)
                feasible.append(((waste, -br * bc), (br, bc)))
        feasible.sort(key=lambda kv: kv[0])
        return [blk for _, blk in feasible]

    def plan_stencil(self, rows: int, cols: int, halo: int = 1, *,
                     dtype_bytes: int = 4,
                     candidates: Optional[Sequence[int]] = None
                     ) -> Tuple[int, int]:
        """Block shape for a 2-D stencil: (brows+2*halo, bcols+2*halo) input
        window + (brows, bcols) output, double-buffered.  The halo overlap is
        the TPU form of the paper's delay buffer — each interior row is
        DMA'd once per block instead of once per use."""
        blocks = self.enumerate_stencil(rows, cols, halo,
                                        dtype_bytes=dtype_bytes,
                                        candidates=candidates)
        if not blocks:
            raise ValueError("no stencil tiling fits VMEM")
        return blocks[0]


def _plan_order_key(p: TilePlan):
    """Heuristic rank: higher arithmetic intensity, then fewer grid steps."""
    return (p.arithmetic_intensity, -math.prod(p.grid))


def replication_factor(reuse: int, unit_flops: float,
                       hw: HardwareSpec = TPU_V5E) -> int:
    """§3.2 napkin math: with `reuse` uses per loaded element, how many
    parallel units can one HBM stream feed before compute saturates?
        P_max = reuse * machine_balance / (flops per element per unit)
    """
    balance = hw.peak_flops / hw.hbm_bw
    return max(1, int(reuse * balance / max(unit_flops, 1e-9)))
