"""Device milliseconds of the final stage's ``tour/lowhigh`` phase
(non-tree reach, two sparse tables and their range reductions, the bridge
test, orientation, subtree ends), per operation."""
from bench.metrics._phases import phase_ms_per


def read(run):
    return phase_ms_per(run, ["stage/final/", "/tour/lowhigh/"],
                        sum(o.ok for o in run.ops))
