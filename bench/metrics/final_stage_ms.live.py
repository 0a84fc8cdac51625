"""Device milliseconds under ``stage/final/`` per operation (every read and
write of the live mix runs the final stage once)."""
from bench.metrics._common import scope_ms_per


def read(run):
    return scope_ms_per(run, ["stage/final/"], sum(o.ok for o in run.ops))
