"""Live link flaps: an open loop of reads and single-link writes on the
engine's live graph.

Network monitoring, where a few flaky links cause most failures and
dashboards read far more often than links change. Set-up ``load``s the
base graph and then inserts a pool of flap links in one ``insert_edges``:
vertex pairs two hops apart that are not links yet (inside the dense
part of the graph, so the warm-start fold adds none of them to the
certificate and taking one down costs no certificate rebuild). Besides
them the pool holds ``bypass_links`` links that start down, each joining
a neighbour of one end of a known bridge to a neighbour of its other end:
while one is up, that bridge is no bridge. They come back up during the
window and stay up, so the answers depend on the writes.

Requests arrive at ``rate_per_s``, Poisson, and each is timed from the
moment it was due: ``read_share`` are ``current_analysis("bridges")``,
the rest are, in equal numbers, flap-downs (``delete_edges`` of an up pool
link) and flap-ups (``insert_edges`` of a down one, bypass links
included). The arrival times and the sequence of kinds are drawn once from
``arrival_seed``, for a given rate and window, and are the same for every
seed: at four fifths of the knee the queue makes the tail follow the
order of arrivals, so a seed that reordered them would change the load.
The seed draws the graph, the pool and which link each flap takes.

Mix keys: ``rate_per_s``, ``read_share``, ``pool_links``, ``bypass_links``,
``arrival_seed``, ``check_answers``, ``late_s`` (an operation not begun
this long after the window closed is dropped and counts as failed).
"""
from __future__ import annotations

import sys
import time
import traceback

import numpy as np
from scipy.sparse import csr_matrix

from bench import reference
from bench.harness import Check, Op

READ, DOWN, UP = "read", "down", "up"


def schedule(n_ops_kinds, rng):
    """Op kinds in a random order in which flap-ups never outnumber
    flap-downs so far (the cycle lemma on the write sequence)."""
    n_read, n_down, n_up = n_ops_kinds
    kinds = np.array([READ] * n_read + [DOWN] * n_down + [UP] * n_up)
    kinds = kinds[rng.permutation(len(kinds))]
    pos = np.flatnonzero(kinds != READ)
    writes = kinds[pos]
    step = np.where(writes == DOWN, 1, -1)
    if len(step):
        low = int(np.argmin(np.cumsum(step)))
        writes = np.roll(writes, -(low + 1))
    kinds[pos] = writes
    return kinds


class Driver:
    def __init__(self, config: dict, mix: dict, graph, seed: int, *,
                 devs=None, control: bool = False):
        self.config = config
        self.mix = mix
        self.g = graph
        self.seed = seed
        self.control = control
        self.engine = None

    # ---------------------------------------------------------------- pool
    def _pool(self, rng):
        """(inner [k, 2], bypass [b, 2]) link arrays, none of them a link
        of the base graph or of each other."""
        g = self.g
        adj = csr_matrix((np.ones(2 * g.n_edges, np.int8),
                          (np.concatenate([g.src, g.dst]),
                           np.concatenate([g.dst, g.src]))),
                         shape=(g.n, g.n))
        indptr, nbr = adj.indptr, adj.indices
        deg = np.diff(indptr)
        base = np.sort(reference.pair_keys(g.src, g.dst, g.n))

        def rand_nbr(v):
            return nbr[indptr[v] + (rng.random(len(v)) * deg[v]).astype(
                np.int64)]

        def fresh(x, y, taken):
            """Pairs that are no base link, no earlier pair, no loop."""
            key = reference.pair_keys(x, y, g.n)
            pos = np.minimum(np.searchsorted(base, key), len(base) - 1)
            ok = (x != y) & (base[pos] != key) & ~np.isin(key, taken)
            _, first = np.unique(key, return_index=True)
            uniq = np.zeros(len(key), bool)
            uniq[first] = True
            return ok & uniq, key

        n_bypass = min(int(self.mix["bypass_links"]), len(g.critical))
        bypass = np.zeros((0, 2), np.int32)
        taken = np.zeros(0, np.int64)
        for u, v in g.critical[:n_bypass]:
            while True:  # a neighbour of each end, not the bridge itself
                a = rand_nbr(np.array([u]))
                b = rand_nbr(np.array([v]))
                ok, key = fresh(a, b, taken)
                if ok[0] and a[0] != v and b[0] != u:
                    bypass = np.concatenate([bypass, [[a[0], b[0]]]])
                    taken = np.concatenate([taken, key])
                    break
        avoid = np.zeros(g.n, bool)
        avoid[g.critical.reshape(-1)] = True
        want = int(self.mix["pool_links"]) - len(bypass)
        inner = np.zeros((0, 2), np.int32)
        while len(inner) < want:
            x = rng.integers(0, g.n, 4 * want)
            x = x[(deg[x] > 0) & ~avoid[x]]
            y = rand_nbr(rand_nbr(x))
            ok, key = fresh(x, y, taken)
            ok &= ~avoid[y]
            pairs = np.stack([x[ok], y[ok]], 1)[:want - len(inner)]
            inner = np.concatenate([inner, pairs]).astype(np.int32)
            taken = np.concatenate([taken, key[ok][:len(pairs)]])
        return inner, bypass.astype(np.int32)

    # --------------------------------------------------------------- set-up
    def setup(self, seconds: float) -> None:
        from repro.engine import BridgeEngine

        rng = np.random.default_rng([self.seed, 3])
        inner, bypass = self._pool(rng)
        self.links = np.concatenate([inner, bypass])
        n_inner = len(inner)
        self.engine = eng = BridgeEngine()
        eng.load(self.g.src, self.g.dst, self.g.n)
        eng.insert_edges(inner[:, 0], inner[:, 1])
        # warm each program the window runs: one flap of an inner link
        # leaves the live graph as it was
        eng.delete_edges(inner[:1, 0], inner[:1, 1])
        eng.insert_edges(inner[:1, 0], inner[:1, 1])
        eng.current_analysis("bridges")
        self.n_inner = n_inner
        self.rng = rng
        self.plan(seconds)

    def plan(self, seconds: float) -> None:
        """The traffic, fixed before the window: arrivals and kinds from
        ``arrival_seed`` (the same for every seed), links from the seed."""
        rng, n_inner = self.rng, self.n_inner
        rate = float(self.mix["rate_per_s"])
        fixed = np.random.default_rng(int(self.mix["arrival_seed"]))
        gaps = fixed.exponential(1.0 / rate, size=int(rate * seconds * 3)
                                 + 16)
        self.due = np.cumsum(gaps)
        n_ops = int(np.searchsorted(self.due, seconds))
        self.due = self.due[:n_ops]
        n_write = n_ops - int(round(n_ops * float(self.mix["read_share"])))
        n_write -= n_write % 2
        kinds = schedule((n_ops - n_write, n_write // 2, n_write // 2), fixed)
        up = np.zeros(len(self.links), bool)
        up[:n_inner] = True
        self.up0 = up.copy()
        plan, states = [], []
        for kind in kinds:
            if kind == DOWN:
                i = int(rng.choice(np.flatnonzero(up[:n_inner])))
                up[i] = False
            elif kind == UP:
                i = int(rng.choice(np.flatnonzero(~up)))
                up[i] = True
            else:
                i = -1
            plan.append((str(kind), i))
            states.append(up.copy())
        self.steps, self.states = plan, states
        self.control_answer = None
        if self.control:
            # the control: every answer as of the window's start, as a
            # cache of the first answer would give it
            self.control_answer = reference.bridges(*self._graph(self.up0),
                                                    self.g.n)

    def _graph(self, up):
        links = self.links[up]
        return (np.concatenate([self.g.src, links[:, 0]]),
                np.concatenate([self.g.dst, links[:, 1]]))

    def traces(self) -> int:
        return self.engine.stats.traces

    # --------------------------------------------------------------- window
    def _do(self, kind, i):
        eng = self.engine
        if kind == READ:
            ans = eng.current_analysis("bridges")
        elif kind == DOWN:
            ans = eng.delete_edges(self.links[i:i + 1, 0],
                                   self.links[i:i + 1, 1])
        else:
            ans = eng.insert_edges(self.links[i:i + 1, 0],
                                   self.links[i:i + 1, 1])
        return self.control_answer if self.control else ans

    def window(self, t0: float, seconds: float) -> list[Op]:
        import jax

        ops = []
        late = float(self.mix["late_s"])
        for j, ((kind, i), due_rel) in enumerate(zip(self.steps, self.due)):
            due = t0 + float(due_rel)
            now = time.perf_counter()
            if now > t0 + seconds + late:
                ops.append(Op(kind, due, now, False, None, j))
                continue
            if due > now:
                with jax.profiler.TraceAnnotation("bench/wait_arrival"):
                    time.sleep(due - now)
            try:
                with jax.profiler.TraceAnnotation(f"bench/{kind}"):
                    ans, ok = self._do(kind, i), True
            except Exception:  # a failed op counts; the loop goes on
                traceback.print_exc(file=sys.stderr)
                ans, ok = None, False
            ops.append(Op(kind, due, time.perf_counter(), ok, ans, j))
        self.live_edges = (self.engine.num_live_graph_edges
                           if self.engine is not None else None)
        return ops

    def summary(self, ops) -> dict:
        lat = [o.end - o.due for o in ops]
        # service: from the later of its due time and the previous op's end
        service, prev = {}, -np.inf
        for o in ops:
            service.setdefault(o.kind, []).append(o.end - max(o.due, prev))
            prev = o.end
        return {"ops": len(ops), "writes": sum(o.kind != READ for o in ops),
                "rate_per_s": float(self.mix["rate_per_s"]),
                "max_latency_s": max(lat) if lat else None,
                "service_s": {k: [float(np.median(v)), max(v)]
                              for k, v in service.items()},
                "rebuilds": (self.engine.live_rebuilds
                             if self.engine is not None else None)}

    def release(self) -> None:
        self.engine = None

    # --------------------------------------------------------------- check
    def check(self, ops) -> list[Check]:
        """Exact comparison of sampled answers (and the last one, which
        every write has reached) with the reference on the live graph as
        it stood at each; and of the engine's live edge count after the
        window with the reference's."""
        done = [o for o in ops if o.ok]
        rng = np.random.default_rng([self.seed, 2])
        k = min(int(self.mix["check_answers"]) - 1, len(done) - 1)
        pick = set() if not done else {len(done) - 1}
        if k > 0:
            pick |= set(rng.choice(len(done) - 1, k, replace=False).tolist())
        # the live graph with every pool link up; each state takes its
        # down links out
        ref = reference.Multigraph(*self._graph(np.ones(len(self.links),
                                                        bool)), self.g.n)
        sample = [done[idx] for idx in sorted(pick)]
        downs = [self.links[~self.states[o.arg]] for o in sample]
        got = ref.bridges_each([(d[:, 0], d[:, 1]) for d in downs])
        wrong = sum(want != o.answer for want, o in zip(got, sample))
        final = self.states[-1] if self.states else self.up0
        want_edges = self.g.n_edges + int(final.sum())
        gap = abs(self.live_edges - want_edges)
        return [Check("no_answer_checked", float(not pick), 0.0),
                Check("wrong_answers", float(wrong), 0.0),
                Check("live_edges_gap", float(gap), 0.0)]
