"""Mean time from due time until the request left the pending queue (its
status no longer ``pending``), over the requests due in the window, in ms.
One never admitted counts until the run stopped waiting."""
from readings import window_arrivals


def read(run):
    reqs = window_arrivals(run)
    if not reqs:
        return None
    waits = [(r.admit_t if r.admit_t is not None else run.loop.end) - r.due
             for r in reqs]
    return 1e3 * sum(waits) / len(waits)
