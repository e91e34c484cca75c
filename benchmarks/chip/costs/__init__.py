"""Operations and bytes that each kernel's algorithm needs, from the
call's shapes and actual lengths; and the least time a chip could take."""


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline bound: the larger of compute and memory time."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
