"""Mean over the window's ticks of the pool's pages in use over its pages
(``pages_in_use()`` after each tick), in %."""


def read(run):
    ws, we = run.loop.window
    used = [t.pages_in_use for t in run.loop.ticks if ws <= t.t1 < we]
    if not used:
        return None
    return 100.0 * sum(used) / (len(used) * run.num_pages)
