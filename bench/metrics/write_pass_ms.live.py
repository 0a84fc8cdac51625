"""Device milliseconds under ``stage/append``, ``stage/tombstone`` and
``stage/merge/`` (the warm-start fold) per write operation."""
from bench.metrics._common import scope_ms_per


def read(run):
    writes = sum(o.ok and o.kind != "read" for o in run.ops)
    return scope_ms_per(run, ["stage/append", "stage/tombstone",
                              "stage/merge/"], writes)
