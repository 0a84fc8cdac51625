"""Device milliseconds of the final stage's ``tour/forest`` phase, the
spanning forest of the certificate (Borůvka rounds over its slots), per
operation (every read and write of the live mix runs the final stage
once)."""
from bench.metrics._phases import phase_ms_per


def read(run):
    return phase_ms_per(run, ["stage/final/", "/tour/forest/"],
                        sum(o.ok for o in run.ops))
