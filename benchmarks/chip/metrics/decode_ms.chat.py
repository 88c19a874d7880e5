"""Mean device time of one decode dispatch in the traced window, in ms."""


def read(run):
    if run.trace is None:
        return None
    d = run.trace.dispatches("decode")
    return 1e3 * sum(d) / len(d) if d else None
