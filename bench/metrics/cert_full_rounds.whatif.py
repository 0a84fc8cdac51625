"""Borůvka rounds of the certificate whose scatter-min ran over the whole
edge buffer, per completed what-if request: the program's own count
(``certificate/full_rounds``, both forests of the one-shot program's
certificate). The rest of ``certificate/rounds`` stopped before the
scatter-min (no cross edge left) or ran on a compacted buffer. None where
the program keeps no such count."""
from bench.metrics._phases import program_count


def read(run):
    done = sum(o.ok for o in run.ops)
    full = program_count("certificate/full_rounds")
    if run.trace is None or not done or full is None:
        return None
    return full / done
