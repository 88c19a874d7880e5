"""90th percentile, over the requests due in the window, of (last-token
time - first-token time) / (tokens - 1), in ms; a request that failed or
never finished is a miss."""
from readings import finite, nearest_rank, tpot_s, window_arrivals


def read(run):
    reqs = window_arrivals(run)
    if not reqs:
        return None
    return finite(1e3 * nearest_rank([tpot_s(r) for r in reqs], 90))
