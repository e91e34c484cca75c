"""Model step layer: host milliseconds before the device has a prefill
step (the program's ``prefill.prepare`` and ``prefill.launch`` spans:
stacking chunks, copies, dispatch), averaged over the window's prefill
steps."""
from chipbench import iterations


def read(run):
    recs = [r for r in iterations.window(run) or [] if "prefill" in r.spans]
    return iterations.mean_span_ms(recs,
                                   ("prefill.prepare", "prefill.launch"))
