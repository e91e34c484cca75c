"""Performance models: the paper's pipeline model (§1.2) and a TPU roofline.

The paper quantifies pipelines with two numbers — latency ``L`` (depth in
cycles) and initiation interval ``I`` (cycles between accepted inputs) — and
the total cycle count

    C = L + I * (N - 1)                                              (Eq. 1)

for N inputs.  Sequential pipelines compose as ``L = L0 + L1`` with
``I = max(I0, I1)``.  We reuse this model verbatim for TPU reasoning:

* a Pallas grid is a pipeline whose N is the number of grid steps and whose I
  is ``max(compute_cycles, dma_cycles)`` per step (double buffering makes the
  DMA a pipeline stage exactly like the paper's "memory extraction"),
* a scan-over-layers is a pipeline over layers,
* fill/drain overhead (the paper's §2.5 motivation) is ``L / C``.

``Roofline`` holds the three dry-run-derived terms used in EXPERIMENTS.md.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional


@dataclass(frozen=True)
class PipelineModel:
    """The paper's Eq. 1: C = L + I * (N - 1)."""

    latency: float          # L [cycles]
    initiation_interval: float  # I [cycles]
    n: float                # N [inputs]

    def cycles(self) -> float:
        return self.latency + self.initiation_interval * (self.n - 1)

    def seconds(self, clock_hz: float) -> float:
        return self.cycles() / clock_hz

    def fill_drain_overhead(self) -> float:
        """Fraction of cycles lost to fill/drain (what §2.5 eliminates)."""
        c = self.cycles()
        return self.latency / c if c else 0.0

    def then(self, other: "PipelineModel") -> "PipelineModel":
        """Sequential composition (paper: L adds, I is max)."""
        if self.n != other.n:
            raise ValueError("sequential pipelines must agree on N")
        return PipelineModel(
            latency=self.latency + other.latency,
            initiation_interval=max(self.initiation_interval,
                                    other.initiation_interval),
            n=self.n,
        )

    def folded(self, factor: float) -> "PipelineModel":
        """Scaling transformations (§3) fold the iteration space by `factor`."""
        return PipelineModel(self.latency, self.initiation_interval,
                             math.ceil(self.n / factor))


# --------------------------------------------------------------------------
# TPU v5e hardware constants (the assignment's numbers).
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float         # FLOP/s per chip (bf16)
    hbm_bw: float             # B/s per chip
    ici_bw: float             # B/s per link
    hbm_bytes: float          # HBM capacity per chip
    vmem_bytes: float         # VMEM per core
    clock_hz: float
    mxu_dim: int = 128        # systolic array edge
    lane: int = 128           # VPU lane count
    sublane: int = 8


TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=16 * 1024**2,
    clock_hz=940e6,
)

# chips this repo has a spec for, keyed by ``jax.Device.device_kind`` (a
# v5e reports "TPU v5 lite")
HARDWARE_BY_KIND: Dict[str, HardwareSpec] = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def device_hardware() -> HardwareSpec:
    """The spec of the chip JAX computes on.

    On a TPU backend it is looked up by the first device's ``device_kind``;
    a TPU kind missing from ``HARDWARE_BY_KIND`` raises rather than being
    planned as a v5e.  Every other backend runs the kernels in interpret
    mode, and plans for the v5e, the design target."""
    import jax
    if jax.default_backend() != "tpu":
        return TPU_V5E
    kind = jax.devices()[0].device_kind
    if kind not in HARDWARE_BY_KIND:
        raise ValueError(
            f"no HardwareSpec for TPU device_kind {kind!r}; known kinds: "
            f"{sorted(HARDWARE_BY_KIND)} (add one to core/model.py)")
    return HARDWARE_BY_KIND[kind]


@dataclass
class Roofline:
    """Three-term roofline for one (arch x shape x mesh) dry-run cell."""

    name: str
    chips: int
    hlo_flops: float               # total, all chips
    hlo_bytes: float               # HBM traffic, all chips
    collective_bytes: float        # total bytes crossing ICI, all chips
    model_flops: float             # 6*N*D analytic "useful" FLOPs
    hw: HardwareSpec = field(default_factory=lambda: TPU_V5E)

    # ---- the three terms, in seconds ----
    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.chips * self.hw.peak_flops)

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / (self.chips * self.hw.hbm_bw)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (self.chips * self.hw.ici_bw)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Optimistic overlap model: bound by the slowest roofline term."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/redundancy waste."""
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved at the modeled step
        time: MODEL_FLOPS / (step_s * chips * peak)."""
        denom = self.step_s * self.chips * self.hw.peak_flops
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> Dict[str, float]:
        return {
            "name": self.name,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_s": self.step_s,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def dense_model_flops(n_params: int, n_tokens: int) -> float:
    """MODEL_FLOPS = 6 * N * D for a dense decoder train step."""
    return 6.0 * n_params * n_tokens


def arithmetic_intensity(flops: float, bytes_: float) -> float:
    return flops / bytes_ if bytes_ else float("inf")


def machine_balance(hw: HardwareSpec = TPU_V5E) -> float:
    """FLOP/B at which a kernel transitions memory- to compute-bound."""
    return hw.peak_flops / hw.hbm_bw
