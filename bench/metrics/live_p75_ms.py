"""75th percentile, nearest rank, of every operation's latency in the
window (reads and writes), each timed from the moment it was due. At the
cell's rate a window holds 54 operations, 13 of them beyond this one."""
from bench.stats import latencies_from_due, percentile


def read(run):
    if not run.ops:
        return None
    lat = latencies_from_due([o.due for o in run.ops],
                             [o.end for o in run.ops])
    return 1e3 * percentile(lat, 75)
