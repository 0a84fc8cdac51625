"""Process start to the window's start: imports, chip start-up, graph
generation, upload, the cell's warm-up (and, in a run that compiles,
compilation)."""


def read(run):
    return run.setup_s
