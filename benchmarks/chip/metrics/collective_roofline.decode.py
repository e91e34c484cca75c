"""Collectives layer: the decode step's collectives' share of their
roofline, in percent.  The least time is what the window's decode steps
move per chip (the program's ``tp_collective_bytes`` counter) at one
chip's interconnect bandwidth (``costs/collectives.py``); the time is the
collective ops' device seconds inside the decode step's module, averaged
over the mesh's chips.

The counter sums an iteration's steps, and a decode step's shapes never
change, so its bytes are read from the iterations that ran a decode step
and no prefill; an iteration that also prefilled is taken at that."""
from chipbench import collectives, iterations, kernels, trace
from costs import collectives as cost


def read(run):
    tr = run.record.get("trace")
    recs = iterations.window(run)
    if not tr or not tr["devices"] or not recs:
        return None
    decode = [r for r in recs if "decode" in r.spans]
    only = {r.counters.get("tp_collective_bytes", 0) for r in decode
            if "prefill" not in r.spans}
    if len(only) != 1 or not min(only):
        return None
    dev = trace.op_seconds(tr, collectives.match(kernels.MODULES["decode"]))
    dev /= len(tr["devices"])
    if dev <= 0:
        return None
    least = cost.least_seconds(only.pop() * len(decode),
                               run.devices[0].device_kind)
    return 100.0 * least / dev
