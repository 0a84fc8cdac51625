"""Device milliseconds of the final stage's ``emit`` phase, the kind's own
test and output buffers, per operation: with the three ``tour/*`` phases
it accounts for ``final_stage_ms.live``."""
from bench.metrics._phases import phase_ms_per


def read(run):
    return phase_ms_per(run, ["stage/final/", "/emit/"],
                        sum(o.ok for o in run.ops))
