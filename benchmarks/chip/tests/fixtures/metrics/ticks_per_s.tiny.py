"""Engine ticks per second of the window (a fixture: shows that a new
metric is one new file and one new entry)."""


def read(run):
    ws, we = run.loop.window
    n = sum(ws <= t.t1 < we for t in run.loop.ticks)
    return n / (we - ws) if n else None
