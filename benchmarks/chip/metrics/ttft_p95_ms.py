"""95th percentile of time to first token over every request sent in the
window, timed from its due time (harness clock), in milliseconds."""
from chipbench.stats import percentile


def read(run):
    v = percentile(run.record.get("ttft_s", []), 95)
    return None if v is None else v * 1e3
