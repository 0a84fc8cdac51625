"""Device milliseconds of the certificate's forests (``cert/forest1`` and
``cert/forest2`` under ``stage/certificate_build/``) per Borůvka round the
program counted (``certificate/rounds``)."""
from bench.metrics._phases import phase_ms_per, program_count


def read(run):
    return phase_ms_per(run, ["stage/certificate_build/", "/cert/forest"],
                        program_count("certificate/rounds"))
