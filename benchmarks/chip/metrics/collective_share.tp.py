"""Collectives layer: the share of the device's busy time that the
tensor-parallel collectives take, in percent: device seconds of the
collective ops (``chipbench/collectives.py``) in the traced window over
the busy seconds, both averaged over the mesh's chips.  A trace with no
collective reads nothing."""
from chipbench import collectives, trace


def read(run):
    tr = run.record.get("trace")
    if not tr or not tr["devices"]:
        return None
    coll = trace.op_seconds(tr, collectives.match()) / len(tr["devices"])
    busy = trace.busy_seconds(tr)
    if coll <= 0 or busy <= 0:
        return None
    return 100.0 * coll / busy
