"""Edges of the graphs that completed requests analysed, over the time from
the window's start to the last completion (host clock)."""


def read(run):
    done = [o for o in run.ops if o.ok]
    if not done:
        return None
    span = max(o.end for o in done) - run.window[0]
    return sum(o.edges for o in done) / span
