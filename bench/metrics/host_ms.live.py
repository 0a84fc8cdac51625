"""Milliseconds per operation spent inside the engine's outermost
``engine/*`` host spans while no operation ran on the device: the host's
part of the service time (dispatch, upload, readbacks, convert), as apart
from slower device work."""
from bench.metrics._phases import host_only_s


def read(run):
    t = run.trace
    done = sum(o.ok for o in run.ops)
    if t is None or not t.devices or not done:
        return None
    if not any(h.name.startswith("engine/") for h in t.host):
        return None
    return 1e3 * host_only_s(t) / done
