"""Training tokens of every step completed in the window, over the
window's seconds (the window ends when its last step completes)."""


def read(run):
    n = run.record.get("train_tokens")
    return None if n is None else n / run.window_s
