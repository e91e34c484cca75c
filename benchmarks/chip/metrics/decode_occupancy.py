"""Engine layer: decoding rows over slots, averaged over the window's
decode steps, in percent.  Every decode step runs all slots; the rest
ride along masked."""


def read(run):
    steps = [s for s in run.record.get("window_steps", [])
             if len(s.decode_lengths)]
    if not steps:
        return None
    return 100.0 * sum(len(s.decode_lengths) for s in steps) / (
        len(steps) * run.record["slots"])
