"""Process start to the first measured request or step, in seconds:
weights, compiles (or cache loads) and warm-up."""


def read(run):
    return run.setup_s
