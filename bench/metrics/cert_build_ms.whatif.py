"""Device milliseconds under ``stage/certificate_build/`` per completed
what-if request."""
from bench.metrics._common import scope_ms_per


def read(run):
    return scope_ms_per(run, ["stage/certificate_build/"],
                        sum(o.ok for o in run.ops))
