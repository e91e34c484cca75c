"""Kernels layer: the fused flash backward's share of its roofline, in
percent: least time of the traced steps' backward attention (each layer,
``costs/flash_bwd`` at the kernel's operand shapes) over the device time
of its dq and dkv kernels."""
from chipbench import device, kernels, trace
from costs import flash_bwd, least_seconds, model_step


def read(run):
    tr = run.record.get("trace")
    n = run.record.get("traced_steps")
    if not tr or not n:
        return None
    dev = trace.op_seconds(tr, kernels.match("flash_bwd"))
    if dev <= 0:
        return None
    _, h, _, hd, _, _, layers = model_step.shapes(run.cell.config)
    mix = run.cell.traffic
    one = least_seconds(*flash_bwd.cost(mix["batch"], h, mix["seq"], hd),
                        device.peaks(run.devices[0].device_kind))
    return 100.0 * one * layers * n / dev
