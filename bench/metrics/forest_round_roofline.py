"""Share of the HBM roofline reached by the Borůvka rounds: the bytes a
round must move (``bench.kernel_bytes``, from the padded shapes) times the
rounds the trace shows, over the peak HBM bandwidth, over the device time
under ``kernel/round/``. Rounds are the executions of the loop bodies
that hold ``kernel/round/boruvka`` operations (``Trace.iterations``)."""
from bench.kernel_bytes import boruvka_round_bytes, bucket


def read(run):
    t = run.trace
    if t is None or not t.devices or run.peaks is None:
        return None
    rounds = t.iterations(["kernel/round/boruvka"])
    seconds = t.scope_s(["kernel/round/"])
    if rounds == 0 or seconds <= 0:
        return None
    g = run.driver.g
    moved = boruvka_round_bytes(bucket(g.n_edges), bucket(g.n)) * rounds
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / seconds
