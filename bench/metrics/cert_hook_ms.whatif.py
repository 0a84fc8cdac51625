"""Device milliseconds of the certificate's Borůvka hooking (the O(n) work
of a round: the picked edges' endpoint labels, the hook, the forest write
and the pointer-doubling shortcut, scope ``kernel/hook/``) per completed
what-if request. None where the program has no such scope.

An operation is the certificate's where its op metadata names
``stage/certificate_build/``. The two forests call one jitted loop, and
the compiler names some of the operations it inlines from there by the
loop's own name stack alone (``while/body/kernel/hook/boruvka/gather``):
such an operation is the certificate's where its HLO computation (a loop
body) holds operations named under ``stage/certificate_build/``."""
from bench.tracefile import union_ns

CERT = "stage/certificate_build/"


def _hook_s(events, lo: int, hi: int) -> float:
    cert_bodies = {e.body for e in events if CERT in e.scope}
    return union_ns([
        (max(e.start, lo), min(e.end, hi)) for e in events
        if e.end > lo and e.start < hi and "kernel/hook/" in e.scope
        and (CERT in e.scope or (not e.scope.startswith("jit(")
                                 and e.body in cert_bodies))]) * 1e-9


def read(run):
    t = run.trace
    done = sum(o.ok for o in run.ops)
    if t is None or not t.devices or not done:
        return None
    s = max(_hook_s(ev, *t.window) for ev in t.devices)
    return 1e3 * s / done if s > 0 else None
