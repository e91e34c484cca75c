"""Interconnect bandwidth of one chip, for the collectives' least time.

A TPU v5e chip's inter-chip interconnect carries 1,600 Gbps (Google
Cloud documentation, "TPU v5e", system architecture table: "Interchip
Interconnect BW: 1,600 Gbps").  The program counts what each step's
collectives move per chip (``tp_collective_bytes``: ring traffic of each
all-gather and all-reduce, from the compiled step's HLO); moving it at
this rate is the least time a chip could take.
"""

ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}


def least_seconds(nbytes: float, kind: str) -> float:
    if kind not in ICI_BYTES_PER_S:
        raise KeyError(f"no interconnect bandwidth for device kind "
                       f"{kind!r}; known: {sorted(ICI_BYTES_PER_S)}")
    return nbytes / ICI_BYTES_PER_S[kind]
