"""Engine layer: KiB copied between host and device in an iteration (the
program's ``h2d_bytes`` and ``d2h_bytes`` counters), averaged over the
window's iterations."""
from chipbench import iterations


def read(run):
    recs = iterations.window(run)
    if not recs:
        return None
    return sum(r.counters.get("h2d_bytes", 0) + r.counters.get("d2h_bytes", 0)
               for r in recs) / len(recs) / 1024.0
