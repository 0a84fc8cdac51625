"""Borůvka rounds of the certificate per completed what-if request: the
program's own count (``certificate/rounds``, both forests of the one-shot
program's certificate), not an inference from the trace."""
from bench.metrics._phases import program_count


def read(run):
    done = sum(o.ok for o in run.ops)
    rounds = program_count("certificate/rounds")
    if run.trace is None or not done or not rounds:
        return None
    return rounds / done
