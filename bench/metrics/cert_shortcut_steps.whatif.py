"""Pointer-doubling steps of the certificate's Borůvka hooking per
completed what-if request: executions of the body of ``_shortcut``'s loop
(scope ``kernel/hook/shortcut``, one ``p = p[p]`` step an execution), read
with ``Trace.iterations`` from the device trace. It shows how far short of
the fixed ``ceil(log2 n)`` steps a hook the loop stops. The loop's
condition is left out, since it runs once more than the body.

An operation is the certificate's by ``cert_hook_ms.whatif``'s rule: its
op metadata names ``stage/certificate_build/``, or it is named by the
loop's own name stack alone and sits in an HLO computation (loop body)
that holds operations so named. None where the program has no such
scope."""
from bench.tracefile import Trace

CERT = "stage/certificate_build/"
BODY = "kernel/hook/shortcut/while/body/"


def read(run):
    t = run.trace
    done = sum(o.ok for o in run.ops)
    if t is None or not t.devices or not done:
        return None
    ev = t.devices[0]
    cert_bodies = {e.body for e in ev if CERT in e.scope}
    cert = [e for e in ev if CERT in e.scope
            or (not e.scope.startswith("jit(") and e.body in cert_bodies)]
    steps = Trace([cert], [], t.window).iterations([BODY])
    return steps / done if steps else None
