"""Kernels layer: the decode attention kernel's share of its roofline, in
percent.  The least time of every call in the traced window (each decode
step, each layer, at the rows' actual lengths; ``costs/decode_attention``)
over the kernel's device time in the trace."""
from chipbench import device, kernels, trace
from costs import decode_attention, least_seconds, model_step


def read(run):
    tr = run.record.get("trace")
    steps = [s for s in run.record.get("window_steps", [])
             if len(s.decode_lengths)]
    if not tr or not steps:
        return None
    dev = trace.op_seconds(tr, kernels.match("decode_attention"))
    if dev <= 0:
        return None
    _, h, g, hd, _, _, layers = model_step.shapes(run.cell.config)
    pk = device.peaks(run.devices[0].device_kind)
    least = sum(least_seconds(*decode_attention.cost(s.decode_lengths + 1,
                                                     h, g, hd), pk)
                for s in steps) * layers
    return 100.0 * least / dev
