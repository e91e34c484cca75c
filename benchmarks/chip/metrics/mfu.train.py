"""Train step layer: model FLOPs of the window's steps (6 N per token and
causal attention forward and backward, recomputation excluded) over the
window's seconds times the chip's peak, in percent."""
from chipbench import device
from costs import model_step


def read(run):
    n = run.record.get("train_steps")
    if not n:
        return None
    mix = run.cell.traffic
    flops = n * model_step.train_flops(run.cell.config, mix["batch"],
                                       mix["seq"])
    return 100.0 * flops / (run.window_s * device.peaks(
        run.devices[0].device_kind)["flops_bf16"])
