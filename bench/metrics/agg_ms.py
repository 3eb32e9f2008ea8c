"""Milliseconds per full-graph aggregation: the whole window over the
number of aggregations completed in it (host clock)."""


def read(run):
    w = run.window
    if w.tokens is not None or not w.units:
        return None
    return w.seconds / w.units * 1e3
