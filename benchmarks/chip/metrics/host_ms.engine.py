"""Engine layer: host milliseconds of the engine's own work in an
iteration (the program's ``engine.admit``, ``engine.compose`` and
``engine.account`` spans), averaged over the window's iterations."""
from chipbench import iterations


def read(run):
    return iterations.mean_span_ms(
        iterations.window(run),
        ("engine.admit", "engine.compose", "engine.account"))
