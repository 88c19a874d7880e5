"""Slots decoding per decode tick over max_batch, from the engine's
``active_ticks`` counter differenced across the window, in %."""


def read(run):
    ws, we = run.loop.window
    ticks = [t for t in run.loop.ticks if ws <= t.t1 < we and t.active]
    if not ticks:
        return None
    return 100.0 * sum(t.active for t in ticks) / (len(ticks) * run.serve.max_batch)
