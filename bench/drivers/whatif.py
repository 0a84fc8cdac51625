"""What-if failure queries, closed loop with one caller.

An operator's planning tool asks, back to back: which links are bridges if
this set of links fails? Each request is
``analyze(base, kind="bridges", final="device", delete=F)`` on the engine,
with a fresh failure set ``F`` of ``fail_links`` links drawn from the seed,
so no result cache can stand in for the work. ``critical_per_set`` of them are
links the generator knows to be bridges (none where it knows none): every
answer then depends on the whole failure set.

Mix keys: ``fail_links``, ``critical_per_set``, ``check_answers`` (answers
compared with the reference, a sample drawn from the seed).
"""
from __future__ import annotations

import sys
import time
import traceback

import numpy as np

from bench import reference
from bench.harness import Check, Op


class Driver:
    def __init__(self, config: dict, mix: dict, graph, seed: int, *,
                 devs=None, control: bool = False):
        self.config = config
        self.mix = mix
        self.g = graph
        self.seed = seed
        self.control = control
        self.engine = None

    # ------------------------------------------------------------- traffic
    def _fail_set(self, rng):
        g = self.g
        k = min(int(self.mix["critical_per_set"]), len(g.critical))
        n = int(self.mix["fail_links"])
        idx = rng.integers(0, g.n_edges, n - k)
        ks, kd = g.src[idx], g.dst[idx]
        if k:
            pick = rng.choice(len(g.critical), k, replace=False)
            ks = np.concatenate([ks, g.critical[pick, 0]])
            kd = np.concatenate([kd, g.critical[pick, 1]])
        order = rng.permutation(n)
        return ks[order].astype(np.int32), kd[order].astype(np.int32)

    def _ask(self, fail):
        if self.control:
            # the control: the base graph's bridges, as a result cache of
            # the graph would answer, whatever the failure set
            return reference.bridges(self.g.src, self.g.dst, self.g.n)
        return self.engine.analyze(self.g.src, self.g.dst, self.g.n,
                                   kind="bridges", final="device",
                                   delete=fail, seed=self.seed)

    def _make_engine(self):
        from repro.engine import BridgeEngine

        return BridgeEngine()

    def setup(self, seconds: float) -> None:
        self.engine = self._make_engine()
        # warm the one program the window runs: its shapes are set by the
        # number of links, of vertices and of failed links alone, so a
        # graph of as many links, all among three vertices, compiles (or
        # loads) and runs it once, with one Boruvka round and a certificate
        # of a few links
        m, fail = self.g.n_edges, int(self.mix["fail_links"])
        ring = np.arange(m, dtype=np.int32) % 3
        far = np.arange(3, 3 + fail, dtype=np.int32)
        self.engine.analyze(ring, (ring + 1) % 3, self.g.n, kind="bridges",
                            final="device", delete=(far, far + 1),
                            seed=self.seed)
        self.rng = np.random.default_rng([self.seed, 1])

    def traces(self) -> int:
        return self.engine.stats.traces

    def window(self, t0: float, seconds: float) -> list[Op]:
        import jax

        ops = []
        while time.perf_counter() < t0 + seconds:
            fail = self._fail_set(self.rng)
            start = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench/whatif"):
                    ans, ok = self._ask(fail), True
            except Exception:  # a failed request counts; the loop goes on
                traceback.print_exc(file=sys.stderr)
                ans, ok = None, False
            ops.append(Op("whatif", start, time.perf_counter(), ok, ans,
                          fail, self.g.n_edges))
        return ops

    def summary(self, ops) -> dict:
        done = [o for o in ops if o.ok]
        dur = [o.end - o.due for o in done]
        return {"requests": len(done),
                "request_s_min": min(dur) if dur else None,
                "request_s_max": max(dur) if dur else None}

    def release(self) -> None:
        self.engine = None

    # --------------------------------------------------------------- check
    def check(self, ops) -> list[Check]:
        """Exact comparison of a sample of the answers with the reference
        on the graph minus each request's failure set."""
        done = [o for o in ops if o.ok]
        rng = np.random.default_rng([self.seed, 2])
        k = min(int(self.mix["check_answers"]), len(done))
        sample = [done[i] for i in sorted(rng.choice(len(done), k,
                                                     replace=False))]
        ref = reference.Multigraph(self.g.src, self.g.dst, self.g.n)
        got = ref.bridges_each([o.arg for o in sample])
        wrong = sum(want != o.answer for want, o in zip(got, sample))
        return [Check("no_answer_checked", float(k == 0), 0.0),
                Check("wrong_answers", float(wrong), 0.0)]
