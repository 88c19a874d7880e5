"""90th percentile, over the requests due in the window, of first-token
time minus due time (open loop), in ms; a failed request is a miss."""
from readings import finite, nearest_rank, ttft_s, window_arrivals


def read(run):
    reqs = window_arrivals(run)
    if not reqs:
        return None
    return finite(1e3 * nearest_rank([ttft_s(r) for r in reqs], 90))
