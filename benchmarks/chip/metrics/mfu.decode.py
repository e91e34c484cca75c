"""Model step layer: model FLOPs of the useful decode rows (forward, with
attention over each row's cached tokens) over the decode steps' host-clock
time (the executor's ``t_decode``) times the chip's peak, in percent."""
from chipbench import device
from costs import model_step


def read(run):
    steps = [s for s in run.record.get("window_steps", [])
             if len(s.decode_lengths)]
    secs = sum(s.decode_s for s in steps)
    if not steps or secs <= 0:
        return None
    cfg = run.cell.config
    flops = sum(model_step.forward_flops(cfg, len(s.decode_lengths),
                                         float((s.decode_lengths + 1).sum()))
                for s in steps)
    return 100.0 * flops / (secs * device.peaks(run.devices[0].device_kind)
                            ["flops_bf16"])
