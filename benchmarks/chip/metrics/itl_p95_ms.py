"""95th percentile of every gap between consecutive output tokens of the
requests sent in the window (harness clock), in milliseconds."""
from chipbench.stats import percentile


def read(run):
    v = percentile(run.record.get("itl_s", []), 95)
    return None if v is None else v * 1e3
