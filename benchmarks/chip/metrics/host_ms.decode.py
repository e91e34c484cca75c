"""Model step layer: host milliseconds before the device has a decode
step (the program's ``decode.prepare`` and ``decode.launch`` spans:
copy-on-write sweep, masked view, copies, dispatch), averaged over the
window's decode steps."""
from chipbench import iterations


def read(run):
    recs = [r for r in iterations.window(run) or [] if "decode" in r.spans]
    return iterations.mean_span_ms(recs, ("decode.prepare", "decode.launch"))
