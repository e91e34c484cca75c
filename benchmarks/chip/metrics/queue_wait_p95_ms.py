"""Engine layer: 95th percentile of the wait from a request's due time to
its admission into a slot (harness clock), in milliseconds."""
from chipbench.stats import percentile


def read(run):
    v = percentile(run.record.get("queue_wait_s", []), 95)
    return None if v is None else v * 1e3
