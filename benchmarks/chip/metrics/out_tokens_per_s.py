"""Every output token emitted inside the window, over the window."""


def read(run):
    ws, we = run.loop.window
    n = sum(t.tokens for t in run.loop.ticks if ws <= t.t1 < we)
    return n / (we - ws) if n else None
