"""Device layer: share of the traced window in which no op ran on the
chip, in percent (1 - busy union / window)."""
from chipbench import trace


def read(run):
    tr = run.record.get("trace")
    if not tr or not tr["devices"]:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(tr) / trace.window_seconds(tr))
