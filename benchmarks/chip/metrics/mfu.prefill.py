"""Model step layer: model FLOPs of the prefill chunks (real prompt tokens
through every layer, causal attention over each token's history, one head
row per chunk) over the prefill steps' host-clock time (the executor's
``t_prefill``) times the chip's peak, in percent."""
from chipbench import device
from costs import model_step


def read(run):
    steps = [s for s in run.record.get("window_steps", []) if s.chunks]
    secs = sum(s.prefill_s for s in steps)
    if not steps or secs <= 0:
        return None
    cfg = run.cell.config
    flops = sum(model_step.forward_flops(
        cfg, sum(r for _, r in s.chunks),
        sum(r * st + r * (r + 1) / 2.0 for st, r in s.chunks),
        head_rows=len(s.chunks)) for s in steps)
    return 100.0 * flops / (secs * device.peaks(run.devices[0].device_kind)
                            ["flops_bf16"])
