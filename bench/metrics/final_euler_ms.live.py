"""Device milliseconds of the final stage's ``tour/euler`` phase, the
Euler tour of the forest (arc sort and Wyllie list ranking), per
operation."""
from bench.metrics._phases import phase_ms_per


def read(run):
    return phase_ms_per(run, ["stage/final/", "/tour/euler/"],
                        sum(o.ok for o in run.ops))
