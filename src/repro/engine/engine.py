"""BridgeEngine: compile-once, shape-bucketed, batched + incrementally-
updatable query engine for the bridges pipeline.

The one-shot ``find_bridges`` function re-traces and re-compiles per exact
array shape and discards all state between calls. The engine restructures
that into the three properties a query-serving deployment needs:

* **compile-once** — jitted executables are cached in the engine keyed by
  ``(kind, n_nodes_bucket, capacity_bucket, backend, schedule)``. Inputs are
  padded to power-of-two buckets (``graph.datastructs.admission_capacity``),
  so nearby graph sizes share one XLA program. ``stats`` counts cache hits,
  misses, and actual retraces so serving code can assert no-retrace.

* **batched** — ``find_bridges_batch`` / ``analyze_batch`` pack B
  independent graphs into a ``BatchedEdgeList`` and resolve them in one
  vmapped device dispatch.

* **multi-kind** — ``analyze(..., kind=...)`` serves every kind in the
  analysis registry through the same program cache with ZERO kind-specific
  engine code (DESIGN.md §Analysis registry).

* **multi-certificate** — the certificate stage dispatches through the
  certificate registry (``core.certs``) the same way: the engine holds a
  generic ``dict[certificate name, state]`` of live pairs and drives
  materialize / insert fold-in / delete-rebuild entirely through the
  registered descriptors (DESIGN.md §Certificate registry).

* **incremental / decremental** — ``load`` + ``insert_edges`` +
  ``delete_edges`` serve edge churn from device-resident live state via
  the warm-start fold-in and the certificate-hit rebuild rule (DESIGN.md
  §Decremental) without ever re-running the full pipeline.

* **streaming** — ``load_stream`` + ``ingest_chunk`` serve graphs whose
  edge set does NOT fit one device: edges flow through fixed-size chunk
  buffers folded straight into the live certificates, the full buffer is
  never materialized, and peak device memory is O(chunk + certificate)
  instead of O(E) (DESIGN.md §Streaming ingest). Deletions tombstone the
  host spill ring and rebuild hit certificates by chunk replay.

* **observable** — every device dispatch is wrapped in a tracer span
  named for its pipeline stage (``stage/certificate_build/...``,
  ``stage/merge/...``, ``stage/final/...``, ``stage/pipeline/...`` for
  the fused one-shot programs), with a device-sync boundary so async
  device work is billed to the stage that launched it; the traced jaxprs
  carry matching ``jax.named_scope`` labels (DESIGN.md §Observability).
  Tracing is off by default (``repro.obs.NULL_TRACER`` — a no-op) and
  enabling it adds no retraces: spans live outside the traced functions
  and appear in no cache key. ``snapshot()`` is the one rollup dict
  (cache counters + hit rate + live rebuild counters) serving code
  consumes.

The engine is layered across three modules (the serving split,
DESIGN.md §Engine): ``state.py`` (counters + live-graph state),
``dispatch.py`` (program cache + program builders, where the
``named_scope`` stage labels live), and this file (the ``BridgeEngine``
orchestration: bucketing, cache keys, substrate selection, spans).

Bucketing the vertex count is sound because every stage treats the extra
vertices as isolated: they join no component, appear on no tour, and can
never be a bridge endpoint. Bucketing the edge capacity is sound because all
device code is mask-aware by construction (see DESIGN.md §Buffers).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.connectivity.registry import get_analysis
from repro.core.certs import (
    certificate_names,
    get_certificate,
    primary_certificate,
)
from repro.engine.batched import BatchedEdgeList, normalize_kind
from repro.engine.dispatch import (
    ProgramCache,
    build_analysis_program,
    build_append_program,
    build_batched_program,
    build_cert_insert_program,
    build_cert_load_program,
    build_delete_program,
    build_distributed_program,
    build_final_program,
)
from repro.engine.state import (
    EngineStats,
    LiveState,
    live_state_from_flat,
    live_state_tree,
    masked_arrays,
)
from repro.graph.datastructs import (
    ChunkedEdgeStream,
    EdgeList,
    admission_capacity,
)
from repro.obs import get_metrics, get_tracer

__all__ = ["BridgeEngine", "EngineStats", "analyze_batch",
           "find_bridges_batch", "get_default_engine"]


class BridgeEngine:
    """Persistent connectivity-query engine (single-device or distributed).

    Single-device (``mesh=None``): certificate + final stage, compile-cached
    per shape bucket, with batched and incremental entry points.

    Distributed (``mesh=...``): the paper's full pipeline (partition,
    per-machine certificates, merge schedule, final stage) with the built
    shard_map program cached per (kind, n_nodes, shard-capacity bucket).
    Every registry kind is served: the merge phases exchange whichever
    certificate the kind declares (2ec or sfs), both of which compose under
    union-merge.
    """

    def __init__(self, *, mesh=None, machine_axes=None, schedule: str = "paper",
                 merge: str = "recertify", min_bucket: int = 16,
                 certificate: str | None = None):
        self.mesh = mesh
        if mesh is not None and machine_axes is None:
            machine_axes = tuple(mesh.axis_names)
        if isinstance(machine_axes, str):
            machine_axes = (machine_axes,)
        self.machine_axes = tuple(machine_axes) if machine_axes else None
        self.schedule = schedule
        self.merge = merge
        self.min_bucket = min_bucket
        # engine-wide certificate preference: "auto"/None = each kind's
        # declared default; a name = use it wherever it preserves what the
        # kind needs, fall back to the default elsewhere (per-call
        # ``certificate=`` overrides are strict instead: see
        # ``_resolve_certificate``).
        if certificate in (None, "auto"):
            self.certificate = None
        else:
            self.certificate = get_certificate(certificate).name
        self.backend = jax.default_backend()
        self.stats = EngineStats()
        self._cache = ProgramCache(self.stats)
        self._live: LiveState | None = None
        self._scheduler = None  # lazy BridgeScheduler (see .scheduler)
        self._ckpt = None       # CheckpointPolicy (see enable_checkpoints)
        self._write_ops = 0     # applied write ops = checkpoint step clock
        self._peak_live_bytes = 0  # high-water device bytes since load

    @property
    def _programs(self) -> dict:
        # pre-split spelling of the program store, kept for tooling
        return self._cache._programs

    def _resolve_certificate(self, analysis, override: str | None = None) -> str:
        """The certificate serving ``analysis``: its declared default,
        unless a per-call ``override`` (strict — ValueError if it does not
        preserve what the kind's default does) or the engine-wide
        preference (permissive — falls back to the default where the kind
        cannot ride it) picks another registered type."""
        default = get_certificate(analysis.certificate)
        if override is not None:
            cert = get_certificate(override)
            if not cert.preserves >= default.preserves:
                raise ValueError(
                    f"certificate {cert.name!r} does not preserve "
                    f"{sorted(default.preserves - cert.preserves)} required "
                    f"by kind {analysis.kind!r} (declared certificate "
                    f"{default.name!r})")
            return cert.name
        if self.certificate is not None:
            cert = get_certificate(self.certificate)
            if cert.preserves >= default.preserves:
                return cert.name
        return default.name

    def certificate_for(self, kind: str) -> str:
        """The certificate name queries for ``kind`` resolve to under this
        engine's configuration (serving dashboards report this)."""
        return self._resolve_certificate(get_analysis(kind))

    def _program_certificate(self, analysis, final: str,
                             override: str | None) -> str | None:
        """Certificate component of a one-shot program's cache key: the
        resolved name where the program builds a certificate (final='host'
        or a ``device_input='certificate'`` kind), else None so programs
        that never build one are shared across certificate choices.
        Overrides are validated either way."""
        cert_name = self._resolve_certificate(analysis, override)
        if final != "host" and analysis.device_input != "certificate":
            return None
        return cert_name

    # ------------------------------------------------------------------ cache
    def _program(self, key: tuple, build):
        """Compile-once: build on first use, count hits afterwards."""
        return self._cache.get(key, build)

    def cache_info(self) -> dict:
        return {
            "programs": len(self._cache),
            "hits": self.stats.hits,
            "misses": self.stats.misses,
            "traces": self.stats.traces,
        }

    def snapshot(self) -> dict:
        """THE engine rollup: program-cache counters + hit rate, and (when
        a live graph is loaded) the per-certificate rebuild counters with
        their total — one dict for serving reports and benchmark records
        (``serve_bridges``, ``fig6_engine``; DESIGN.md §Observability).
        Counter semantics match ``cache_info``/``live_rebuilds`` exactly.
        """
        snap = {"programs": len(self._cache), **self.stats.snapshot()}
        if self._live is not None:
            rebuilds = dict(self._live.rebuilds)
            snap["rebuilds"] = rebuilds
            snap["rebuilds_total"] = sum(rebuilds.values())
            snap["live_graph_edges"] = self._live.count
            snap["live_bytes"] = self._account_live_bytes()
            snap["peak_live_bytes"] = self._peak_live_bytes
            if self._live.stream is not None:
                st = self._live.stream
                snap["ingest"] = {
                    "chunks": st.chunks_in, "folds": st.folds,
                    "spilled": st.spilled_edges, "replays": st.replays,
                    "chunk_bucket": st.chunk_bucket,
                }
        if self._scheduler is not None:
            snap["scheduler"] = self._scheduler.snapshot()
        if self._ckpt is not None:
            snap["checkpoint"] = self._ckpt.snapshot()
        return snap

    # ------------------------------------------------------------- checkpoint
    def enable_checkpoints(self, directory, *, every: int = 8, keep: int = 3):
        """Attach an every-K-write-ops ``CheckpointPolicy``: from now on
        each ``insert_edges``/``delete_edges`` counts one write op, and
        every ``every``-th write snapshots the live state (full buffer +
        materialized certificate states + counters) through an atomic
        manifest+CRC ``CheckpointManager`` under ``directory``. See
        DESIGN.md §Fault tolerance for the currency rule this cadence
        implements. Returns the policy (counters in ``snapshot()``)."""
        from repro.checkpoint.manager import CheckpointManager, CheckpointPolicy

        self._ckpt = CheckpointPolicy(
            CheckpointManager(directory, keep=keep), every=every)
        return self._ckpt

    def _after_write(self):
        """One write op applied: advance the checkpoint clock and let the
        policy decide whether this step snapshots (the tree is only built
        when it does)."""
        self._write_ops += 1
        if self._ckpt is None or self._live is None:
            return
        if self._live.full is None:
            # streamed live state does not checkpoint: there is no full
            # buffer to snapshot, and the host spill ring IS the recovery
            # log (replay rebuilds everything)
            return
        with get_tracer().span("engine/checkpoint_maybe",
                               step=self._write_ops):
            self._ckpt.on_write(self._write_ops,
                                lambda: live_state_tree(self._live))

    def checkpoint_now(self) -> "object":
        """Snapshot the live state immediately, regardless of cadence."""
        if self._ckpt is None:
            raise RuntimeError("checkpointing not enabled: call "
                               "enable_checkpoints() first")
        if self._live is None:
            raise RuntimeError("no live graph: call load() first")
        if self._live.full is None:
            raise RuntimeError(
                "streamed live state does not checkpoint: the spill ring "
                "is the recovery log (re-ingest replays it)")
        with get_tracer().span("engine/checkpoint", step=self._write_ops):
            return self._ckpt.checkpoint(self._write_ops,
                                         live_state_tree(self._live))

    def restore_live(self, step: int | None = None) -> int:
        """Restore the live state from the newest (or ``step``'s) verified
        checkpoint — the serving-side recovery path (DESIGN.md §Fault
        tolerance).

        Restore runs NO compiled program: buffers are device_put straight
        from the verified arrays, lazy certificates that were
        unmaterialized at save time come back as ``None`` (they
        re-materialize from the restored full buffer on first query,
        through the already-cached ``cert_load`` program), and the program
        cache is untouched — so an engine that was serving a bucket before
        the restore serves it after with zero retraces (asserted in
        tests/test_failover.py, pinned by fig11). Ticks
        ``failures/recovered``. Returns the restored checkpoint step."""
        if self._ckpt is None:
            raise RuntimeError("checkpointing not enabled: call "
                               "enable_checkpoints() first")
        tr = get_tracer()
        with tr.span("recover/restore_live", step=step) as sp:
            found, flat = self._ckpt.manager.restore_flat(step)
            if found is None:
                raise RuntimeError(
                    f"no verified checkpoint to restore under "
                    f"{self._ckpt.manager.dir}")
            live = live_state_from_flat(flat)
            live.full = tuple(jnp.asarray(x) for x in live.full)
            live.certs = {name: tuple(jnp.asarray(x) for x in state)
                          for name, state in live.certs.items()}
            for name in certificate_names():
                live.certs.setdefault(name, None)
            sp.sync(live.full)
            self._live = live
            self._write_ops = found
            self._ckpt.restores += 1
            get_metrics().counter("failures/recovered").inc()
            if getattr(sp, "attrs", None) is not None:
                # enrich the span in place: programs cached across the
                # restore (must be unchanged — warm-serve readiness)
                sp.attrs.update(warm_programs=len(self._cache),
                                n_bucket=live.n_bucket, restored_step=found)
        return found

    # -------------------------------------------------------------- scheduler
    @property
    def scheduler(self):
        """The engine's continuous-batching request path, created on first
        use (``engine/scheduler.py``; DESIGN.md §Serving). For a custom
        coalescing window or an isolated metrics registry, construct
        ``BridgeScheduler(engine, ...)`` directly and drive it instead."""
        if self._scheduler is None:
            from repro.engine.scheduler import BridgeScheduler

            self._scheduler = BridgeScheduler(self)
        return self._scheduler

    def submit(self, tenant: str, src, dst, n_nodes: int | None = None,
               *, op: str = "analyze", kind: str = "bridges",
               final: str = "device", certificate: str | None = None):
        """Queue a tenant-tagged request on the engine's scheduler; the
        returned ``Ticket`` resolves on a later ``drain``."""
        return self.scheduler.submit(tenant, src, dst, n_nodes, op=op,
                                     kind=kind, final=final,
                                     certificate=certificate)

    def drain(self) -> int:
        """One scheduler step: a coalesced read wave + the write turn."""
        return self.scheduler.drain()

    def drain_all(self) -> int:
        """Drain the scheduler queue to empty."""
        return self.scheduler.drain_all()

    def _bucket(self, m: int) -> int:
        return admission_capacity(m, self.min_bucket)

    def _tick_trace(self):
        self.stats.traces += 1

    def _delete_keys(self, delete, n_nodes: int):
        """One-shot deletion keys -> (padded key EdgeList, key bucket).
        Shared by the single-graph and distributed ``delete=`` paths."""
        ks = np.asarray(delete[0], np.int32)
        kd = np.asarray(delete[1], np.int32)
        kcap = self._bucket(max(len(ks), 1))
        return EdgeList.from_arrays(ks, kd, n_nodes, capacity=kcap), kcap

    # ---------------------------------------------------------- single device
    def analyze(self, src, dst, n_nodes: int, *, kind: str = "bridges",
                final: str = "device", seed: int = 0, delete=None,
                certificate: str | None = None):
        """One graph, one analysis kind; compile-once per shape bucket.

        kind='bridges'     -> set[(u, v)] bridge pairs
        kind='cuts'        -> set[int] articulation points
        kind='2ecc'        -> int array[n_nodes] canonical 2ECC labels
        kind='bridge_tree' -> set[(a, b)] 2ECC supernode pairs
        kind='bcc'         -> set[frozenset[int]] biconnected blocks

        ``final='host'`` answers with the kind's sequential host reference
        run on the kind's sparse certificate instead of the device final
        stage. ``seed`` only affects the distributed edge partition.

        ``delete=(ksrc, kdst)`` answers on the graph MINUS every live copy
        of the given unordered endpoint pairs: the one-shot spelling of a
        link-failure query, served by the same cached program (a tombstone
        pass prepended to the pipeline; key buffers shape-bucketed like
        the edges). Works on the distributed substrate too — keys are
        replicated and each machine tombstones its own shard before the
        certificate/merge phases.

        ``certificate`` overrides the kind's declared certificate type
        with any registered type that preserves what the kind needs
        (``core.certs``; ValueError otherwise). One-shot device queries
        for the ``device_input='full'`` kinds never build a certificate,
        so the override only affects ``final='host'``, the certificate
        kinds, and the distributed merge phases.
        """
        analysis = get_analysis(kind)
        kind = analysis.kind
        if self.mesh is not None:
            return self._analyze_distributed(src, dst, n_nodes, kind=kind,
                                             final=final, seed=seed,
                                             delete=delete,
                                             certificate=certificate)
        tr = get_tracer()
        with tr.span(f"engine/analyze/{kind}", substrate="single",
                     final=final):
            with tr.span("stage/pad"):
                src = np.asarray(src, np.int32)
                dst = np.asarray(dst, np.int32)
                n_bucket = self._bucket(n_nodes)
                cap = self._bucket(max(len(src), 1))
                el = EdgeList.from_arrays(src, dst, n_bucket, capacity=cap)
                args = (el.src, el.dst, el.mask)
                kcap = None
                if delete is not None:
                    kel, kcap = self._delete_keys(delete, n_bucket)
                    args += (kel.src, kel.dst, kel.mask)
            cert_name = self._program_certificate(analysis, final, certificate)
            key = ("single", kind, final, n_bucket, cap, kcap, self.backend,
                   cert_name)
            fn = self._program(
                key, lambda: build_analysis_program(
                    n_bucket, kind, final, self._tick_trace,
                    with_delete=kcap is not None, certificate=cert_name))
            with tr.span(f"stage/pipeline/{kind}", n_bucket=n_bucket,
                         cap=cap, certificate=cert_name) as sp:
                out = sp.sync(fn(*args))
            with tr.span("stage/convert"):
                if final == "host":
                    return analysis.host_fn(*masked_arrays(out), n_nodes)
                return analysis.to_result(out, n_nodes)

    def find_bridges(self, src, dst, n_nodes: int, *, final: str = "device",
                     seed: int = 0) -> set[tuple[int, int]]:
        """Bridges of one graph. Same contract as ``core.find_bridges``."""
        return self.analyze(src, dst, n_nodes, kind="bridges", final=final,
                            seed=seed)

    def find_cuts(self, src, dst, n_nodes: int) -> set[int]:
        """Articulation points (cut vertices) of one graph."""
        return self.analyze(src, dst, n_nodes, kind="cuts")

    def find_two_ecc(self, src, dst, n_nodes: int) -> np.ndarray:
        """Canonical 2-edge-connected-component label per vertex."""
        return self.analyze(src, dst, n_nodes, kind="2ecc")

    def find_bridge_tree(self, src, dst, n_nodes: int) -> set[tuple[int, int]]:
        """Bridge tree edges as pairs of canonical 2ECC labels."""
        return self.analyze(src, dst, n_nodes, kind="bridge_tree")

    def find_bcc(self, src, dst, n_nodes: int) -> set[frozenset[int]]:
        """Biconnected blocks as canonical vertex sets."""
        return self.analyze(src, dst, n_nodes, kind="bcc")

    # ----------------------------------------------------------------- batched
    def analyze_batch(self, graphs, n_nodes, *, kind: str = "bridges",
                      final: str = "device", delete=None,
                      certificate: str | None = None) -> list:
        """Resolve B independent graphs in ONE device dispatch.

        ``graphs``: iterable of (src, dst) pairs. ``n_nodes``: shared vertex
        count, or a per-graph sequence (bucketed to the max). Returns the
        per-graph results in order, typed per ``analyze``'s kind table.

        ``delete``: optional per-graph deletion-key lists — ``(ksrc, kdst)``
        or ``None`` per graph — applied as a vmapped tombstone pass inside
        the same dispatch (each graph answers minus its own failed links).

        ``certificate``: as in ``analyze`` — a validated override of the
        kind's declared certificate type, where one is built.
        """
        analysis = get_analysis(kind)
        kind = analysis.kind
        if self.mesh is not None:
            raise NotImplementedError(
                "batched dispatch is single-device; use mesh=None")
        graphs = [(np.asarray(s, np.int32), np.asarray(d, np.int32))
                  for s, d in graphs]
        if not graphs:
            return []
        ns = ([int(n_nodes)] * len(graphs)
              if np.ndim(n_nodes) == 0 else [int(x) for x in n_nodes])
        if len(ns) != len(graphs):
            raise ValueError(
                f"{len(graphs)} graphs but {len(ns)} vertex counts")
        tr = get_tracer()
        with tr.span(f"engine/analyze_batch/{kind}", substrate="batched",
                     batch=len(graphs), final=final):
            with tr.span("stage/pad"):
                n_bucket = self._bucket(max(ns))
                cap = self._bucket(
                    max(max((len(s) for s, _ in graphs), default=1), 1))
                b_bucket = admission_capacity(len(graphs), 1)
                bel = BatchedEdgeList.from_graphs(graphs, n_bucket,
                                                  capacity=cap,
                                                  batch_pad=b_bucket)
                args = (bel.src, bel.dst, bel.mask)
                kcap = None
                if delete is not None:
                    delete = list(delete)
                    if len(delete) != len(graphs):
                        raise ValueError(f"{len(graphs)} graphs but "
                                         f"{len(delete)} deletion lists")
                    empty = (np.zeros(0, np.int32), np.zeros(0, np.int32))
                    keys = [empty if sd is None else sd for sd in delete]
                    kcap = self._bucket(
                        max((len(s) for s, _ in keys), default=1))
                    kel = BatchedEdgeList.from_graphs(keys, n_bucket,
                                                      capacity=kcap,
                                                      batch_pad=b_bucket)
                    args += (kel.src, kel.dst, kel.mask)
            cert_name = self._program_certificate(analysis, final, certificate)
            key = ("batch", kind, final, n_bucket, cap, b_bucket, kcap,
                   self.backend, cert_name)
            fn = self._program(
                key, lambda: build_batched_program(
                    n_bucket, kind, final, self._tick_trace,
                    with_delete=kcap is not None, certificate=cert_name))
            with tr.span(f"stage/pipeline/{kind}", n_bucket=n_bucket,
                         cap=cap, batch=b_bucket,
                         certificate=cert_name) as sp:
                out_dev = sp.sync(fn(*args))
            with tr.span("stage/convert"):
                stacked = (tuple(np.asarray(x) for x in out_dev)
                           if isinstance(out_dev, (tuple, list))
                           else (np.asarray(out_dev),))
                out = []
                for i, n in enumerate(ns):
                    row = tuple(x[i] for x in stacked)
                    if final == "host":
                        s, d, m = row
                        out.append(analysis.host_fn(s[m], d[m], n))
                    else:
                        out.append(analysis.to_result(
                            row if len(row) > 1 else row[0], n))
                return out

    def find_bridges_batch(self, graphs, n_nodes, *, final: str = "device",
                           ) -> list[set[tuple[int, int]]]:
        """Batched bridges: B graphs, one vmapped dispatch."""
        return self.analyze_batch(graphs, n_nodes, kind="bridges",
                                  final=final)

    def find_cuts_batch(self, graphs, n_nodes) -> list[set[int]]:
        """Batched articulation points: B graphs, one vmapped dispatch."""
        return self.analyze_batch(graphs, n_nodes, kind="cuts")

    def find_two_ecc_batch(self, graphs, n_nodes) -> list[np.ndarray]:
        """Batched canonical 2ECC labels: B graphs, one vmapped dispatch."""
        return self.analyze_batch(graphs, n_nodes, kind="2ecc")

    def find_bridge_tree_batch(self, graphs, n_nodes,
                               ) -> list[set[tuple[int, int]]]:
        """Batched bridge trees: B graphs, one vmapped dispatch."""
        return self.analyze_batch(graphs, n_nodes, kind="bridge_tree")

    def find_bcc_batch(self, graphs, n_nodes) -> list[set[frozenset[int]]]:
        """Batched biconnected blocks: B graphs, one vmapped dispatch."""
        return self.analyze_batch(graphs, n_nodes, kind="bcc")

    # ------------------------------------------------------------- incremental
    def _cert_load(self, name: str, n_bucket: int, buffers) -> tuple:
        """Run the cached load/rebuild program for ``name`` on an edge
        buffer's shape bucket; returns the live state tuple. Span:
        ``stage/certificate_build/<name>`` (initial load, lazy
        materialization, and decremental rebuild all land here — the
        paper's per-machine certificate-build cost term)."""
        s, d, m = buffers
        key = ("cert_load", name, n_bucket, s.shape[0], self.backend, None)
        fn = self._program(
            key, lambda: build_cert_load_program(name, n_bucket,
                                                 self._tick_trace))
        with get_tracer().span(f"stage/certificate_build/{name}",
                               n_bucket=n_bucket) as sp:
            return tuple(sp.sync(fn(s, d, m)))

    def _delete_pass(self, buffers, keys, target: str):
        """Run the cached tombstone program for ``buffers``' shape bucket.
        Returns (new_mask, removed-count device scalar). Span:
        ``stage/tombstone`` with the probed buffer named in ``target``."""
        s, d, m = buffers
        key = ("delete", s.shape[0], keys.capacity, self.backend, None)
        fn = self._program(key,
                           lambda: build_delete_program(self._tick_trace))
        with get_tracer().span("stage/tombstone", target=target) as sp:
            return sp.sync(fn(s, d, m, keys.src, keys.dst, keys.mask))

    def _materialize(self, name: str) -> tuple:
        """Lazy certificates (``Certificate.lazy``, e.g. the scan-first and
        hybrid pairs) are only computed — from the live full buffer, or,
        streamed, by spill-ring replay — on the FIRST query that resolves
        to them, so workloads that never ask never pay their passes. Once
        live a state is maintained on device per delta (and rebuilt when a
        deletion kills one of its edges)."""
        live = self._live
        state = live.certs.get(name)
        if state is None:
            if live.full is None:
                state = live.certs[name] = self._replay_state(name)
            else:
                state = live.certs[name] = self._cert_load(
                    name, live.n_bucket, live.full)
            live.rebuilds.setdefault(name, 0)
            self._account_live_bytes()
        return state

    def load(self, src, dst, n_nodes: int) -> "BridgeEngine":
        """Set the engine's live graph: every EAGER certificate in the
        registry (the warm-start Borůvka pair) is computed now; lazy ones
        (sfs, hybrid) wait for the first query that resolves to them
        (``_materialize`` — workloads that never ask pay nothing). The
        full edge buffer stays resident on device: it is the tombstone
        target for ``delete_edges`` and the rebuild source when a deletion
        kills a certificate edge."""
        if self.mesh is not None:
            raise NotImplementedError(
                "incremental updates are single-device; use mesh=None")
        with get_tracer().span("engine/load"):
            src = np.asarray(src, np.int32)
            dst = np.asarray(dst, np.int32)
            n_bucket = self._bucket(n_nodes)
            cap = self._bucket(max(len(src), 1))
            el = EdgeList.from_arrays(src, dst, n_bucket, capacity=cap)
            self._live = LiveState(
                certs={}, rebuilds={}, full=(el.src, el.dst, el.mask),
                count=len(src), n_nodes=int(n_nodes), n_bucket=n_bucket)
            self._peak_live_bytes = 0
            for name in certificate_names():
                if get_certificate(name).lazy:
                    self._live.certs[name] = None
                else:
                    self._materialize(name)
            self._account_live_bytes()
        return self

    # --------------------------------------------------------------- streaming
    def load_stream(self, src, dst, n_nodes: int, *,
                    chunk_edges: int = 1024) -> "BridgeEngine":
        """Set the engine's live graph WITHOUT materializing its edge
        buffer: the streaming counterpart of ``load`` for graphs bigger
        than one device (DESIGN.md §Streaming ingest).

        The initial edges — and every later ``ingest_chunk`` delta — flow
        through fixed ``chunk_edges``-sized device chunks folded straight
        into the live certificate states via the registry's
        ``load_state``/``fold_state`` programs, so peak device memory is
        O(chunk + certificate) instead of O(E). A host-side spill ring
        (``ChunkedEdgeStream``) keeps numpy copies of every chunk: it is
        the tombstone target for ``delete_edges`` and the replay source
        for certificate-hit rebuilds and lazy materialization. All chunk
        buffers share ONE pow-2 ``chunk_bucket``, the same
        ``admission_capacity`` currency as every other engine buffer, so
        steady-state ingest reuses one compiled load/fold program per
        certificate — zero retraces after warmup regardless of incoming
        delta sizes. Checkpointing is not available in streamed mode (the
        spill ring is itself the recovery log)."""
        if self.mesh is not None:
            raise NotImplementedError(
                "streaming ingest is single-device; shard with "
                "core.merge.stream_shard_states and merge per-shard results")
        with get_tracer().span("engine/load_stream", chunk_edges=chunk_edges):
            n_bucket = self._bucket(n_nodes)
            stream = ChunkedEdgeStream(n_nodes, chunk_edges,
                                       minimum=self.min_bucket)
            self._live = LiveState(
                certs={name: None for name in certificate_names()},
                rebuilds={}, full=None, count=0, n_nodes=int(n_nodes),
                n_bucket=n_bucket, stream=stream)
            self._peak_live_bytes = 0
            self.ingest_chunk(src, dst)
        return self

    def ingest_chunk(self, src, dst, *, final: str = "device",
                     kind: str | None = None, certificate: str | None = None):
        """Stream an edge delta into the streamed live graph.

        The delta is split into ``chunk_bucket``-padded device chunks
        (``ChunkedEdgeStream.admit``, which also spills host copies into
        the ring), and each chunk folds into every certificate the engine
        currently tracks: eager certificates initialize from the first
        chunk through the cached ``cert_load`` program and fold the rest
        through the cached ``cert_insert`` program; lazy certificates stay
        unmaterialized until the first query that resolves to them (then
        replay the ring) — but once materialized they fold along like the
        eager ones, staying current. ``mem/live_bytes`` is updated at
        every chunk-fold boundary, which is what makes the O(chunk +
        certificate) peak observable (fig12).

        With ``kind=None`` (the default for raw ingest loops) returns the
        engine; with a kind, returns that analysis of the updated live
        graph — the scheduler's ``op='ingest_chunk'`` path."""
        live = self._live
        if live is None or live.stream is None:
            raise RuntimeError(
                "no streamed live graph: call load_stream() first")
        tr = get_tracer()
        src = np.asarray(src, np.int32)
        dst = np.asarray(dst, np.int32)
        with tr.span("stage/ingest", edges=len(src),
                     chunk_bucket=live.stream.chunk_bucket):
            for chunk in live.stream.admit(src, dst):
                self._fold_chunk(chunk)
                self._account_live_bytes()
            live.count = live.stream.count
        self._after_write()
        if kind is None:
            return self
        return self.current_analysis(kind=kind, final=final,
                                     certificate=certificate)

    def _fold_chunk(self, chunk: EdgeList) -> None:
        """Fold ONE admitted chunk into every tracked certificate state
        (initialize eager / already-materialized ones from it if needed)."""
        live = self._live
        n_bucket = live.n_bucket
        tr = get_tracer()
        for name in list(live.certs):
            state = live.certs[name]
            if state is None:
                if get_certificate(name).lazy:
                    continue  # materializes by ring replay on first query
                live.certs[name] = self._cert_load(
                    name, n_bucket, (chunk.src, chunk.dst, chunk.mask))
                live.rebuilds.setdefault(name, 0)
            else:
                key = ("cert_insert", name, n_bucket, chunk.capacity,
                       self.backend, None)
                fn = self._program(
                    key, lambda name=name: build_cert_insert_program(
                        name, n_bucket, self._tick_trace))
                with tr.span(f"stage/merge/{name}",
                             delta=chunk.capacity) as sp:
                    live.certs[name] = tuple(sp.sync(
                        fn(*state, chunk.src, chunk.dst, chunk.mask)))
            live.stream.folds += 1

    def _empty_chunk(self) -> EdgeList:
        """All-masked chunk-bucket buffer: the streamed spelling of an
        edgeless graph (fixes shapes so the cached programs still apply)."""
        cb = self._live.stream.chunk_bucket
        z = jnp.zeros((cb,), jnp.int32)
        return EdgeList(z, z, jnp.zeros((cb,), bool), self._live.n_bucket)

    def _replay_state(self, name: str) -> tuple:
        """Rebuild ``name``'s live state by replaying the spill ring's
        surviving chunks — the streamed rebuild source (tombstone-then-
        replay, DESIGN.md §Streaming ingest). Replay chunks carry the same
        ``chunk_bucket`` as ingest, so this reuses the cached programs."""
        live = self._live
        n_bucket = live.n_bucket
        tr = get_tracer()
        state = None
        for chunk in live.stream.replay():
            if state is None:
                state = self._cert_load(
                    name, n_bucket, (chunk.src, chunk.dst, chunk.mask))
            else:
                key = ("cert_insert", name, n_bucket, chunk.capacity,
                       self.backend, None)
                fn = self._program(
                    key, lambda name=name: build_cert_insert_program(
                        name, n_bucket, self._tick_trace))
                with tr.span(f"stage/merge/{name}",
                             delta=chunk.capacity) as sp:
                    state = tuple(sp.sync(
                        fn(*state, chunk.src, chunk.dst, chunk.mask)))
            live.stream.folds += 1
        if state is None:  # empty ring: certify the edgeless world
            ec = self._empty_chunk()
            state = self._cert_load(name, n_bucket, (ec.src, ec.dst, ec.mask))
            live.stream.folds += 1
        return state

    # ---------------------------------------------------------- memory gauges
    def _account_live_bytes(self) -> int:
        """Device bytes of the live state — certificate states plus the
        edge buffer (full, or one streamed chunk) — published to the
        ``mem/live_bytes`` / ``mem/peak_live_bytes`` gauges. Called at
        load and at every chunk-fold / churn boundary, so the gauges trace
        the O(chunk + certificate) claim fig12 pins (peak resets on
        ``load``/``load_stream``)."""
        live = self._live
        if live is None:
            return 0
        total = 0
        for state in live.certs.values():
            if state is None:
                continue
            for x in state:
                total += x.size * x.dtype.itemsize
        if live.full is not None:
            for x in live.full:
                total += x.size * x.dtype.itemsize
        else:
            total += live.stream.device_chunk_bytes
        m = get_metrics()
        m.gauge("mem/live_bytes").set(total)
        if total > self._peak_live_bytes:
            self._peak_live_bytes = total
        m.gauge("mem/peak_live_bytes").set(self._peak_live_bytes)
        return total

    @property
    def live_bytes(self) -> int:
        """Current device bytes of the live state (see
        ``_account_live_bytes``)."""
        return self._account_live_bytes()

    @property
    def peak_live_bytes(self) -> int:
        """High-water ``live_bytes`` since the last ``load``/``load_stream``
        — the number fig12 compares across the one-shot and streamed
        paths."""
        self._account_live_bytes()
        return self._peak_live_bytes

    @property
    def num_live_edges(self) -> int:
        """Edge count of the live primary certificate — the eager 2-edge
        pair (<= 2(n-1), Lemma 1)."""
        if self._live is None:
            raise RuntimeError("no live graph: call load() first")
        return int(np.asarray(
            self._materialize(primary_certificate())[2]).sum())

    @property
    def num_live_graph_edges(self) -> int:
        """Edge count of the live FULL graph (inserts minus deletions),
        tracked on host — no device sync."""
        if self._live is None:
            raise RuntimeError("no live graph: call load() first")
        return self._live.count

    @property
    def live_rebuilds(self) -> dict:
        """Per-certificate rebuild counts caused by certificate-hit
        deletions, one entry per MATERIALIZED certificate (e.g.
        ``{'2ec': 0, 'sfs': 1}``) — the observable for 'most deletions are
        free' (DESIGN.md §Decremental)."""
        if self._live is None:
            raise RuntimeError("no live graph: call load() first")
        return dict(self._live.rebuilds)

    def insert_edges(self, src, dst, *, final: str = "device",
                     kind: str = "bridges", certificate: str | None = None):
        """Fold an edge delta into the live certificates, return the updated
        analysis for ANY registry kind (see ``current_analysis``).

        One registry-driven loop folds the delta into every MATERIALIZED
        certificate state via its registered ``fold_state`` program: the
        2-edge pair's warm-start labels scan only the delta buffer (the
        PR 1/PR 2 hot path, unchanged), while the rescan certificates
        (sfs, hybrid) — what make ``kind='cuts'`` and ``'bcc'`` serveable
        incrementally, since the 2-edge-only live state provably does not
        preserve vertex cuts (DESIGN.md §Connectivity counterexample,
        pinned as a regression test) — re-certify the bounded cert ∪ delta
        union. Unmaterialized lazy certificates cost nothing until the
        first query that resolves to them (``_materialize``). The delta is
        also compact-appended into the device-resident full buffer — the
        ``delete_edges`` tombstone target and rebuild source (DESIGN.md
        §Decremental). The full pipeline is never re-run.
        """
        kind = normalize_kind(kind)
        if self._live is None:
            raise RuntimeError("no live graph: call load() first")
        live = self._live
        if live.full is None:
            # streamed live graph: an insert IS an ingest (chunk currency
            # instead of per-delta buckets; the ring records the delta)
            return self.ingest_chunk(src, dst, final=final, kind=kind,
                                     certificate=certificate)
        n_bucket = live.n_bucket
        tr = get_tracer()
        with tr.span("engine/insert_edges", kind=kind):
            src = np.asarray(src, np.int32)
            dst = np.asarray(dst, np.int32)
            delta_cap = self._bucket(max(len(src), 1))
            recv = EdgeList.from_arrays(src, dst, n_bucket,
                                        capacity=delta_cap)
            for name, state in live.certs.items():
                if state is None:
                    continue
                key = ("cert_insert", name, n_bucket, delta_cap,
                       self.backend, None)
                fn = self._program(
                    key, lambda name=name: build_cert_insert_program(
                        name, n_bucket, self._tick_trace))
                with tr.span(f"stage/merge/{name}", delta=delta_cap) as sp:
                    live.certs[name] = tuple(sp.sync(
                        fn(*state, recv.src, recv.dst, recv.mask)))
            # Keep the live FULL buffer current: compact-append the delta,
            # reclaiming tombstoned holes. The edge count is tracked on host
            # so the output bucket (and thus a possible grow-retrace) is a
            # static shape decision; same-bucket churn reuses one compiled
            # program.
            fs, fd, fm = live.full
            needed = live.count + len(src)
            out_cap = (fs.shape[0] if needed <= fs.shape[0]
                       else admission_capacity(needed, self.min_bucket))
            akey = ("append", n_bucket, fs.shape[0], delta_cap, out_cap,
                    self.backend)
            afn = self._program(
                akey, lambda: build_append_program(n_bucket, out_cap,
                                                   self._tick_trace))
            with tr.span("stage/append") as sp:
                live.full = tuple(sp.sync(
                    afn(fs, fd, fm, recv.src, recv.dst, recv.mask)))
            live.count = needed
            self._after_write()
            return self.current_analysis(kind=kind, final=final,
                                         certificate=certificate)

    def delete_edges(self, src, dst, *, final: str = "device",
                     kind: str = "bridges", certificate: str | None = None):
        """Serve edge DELETIONS (link failures) from the live state, return
        the updated analysis for ANY registry kind (``current_analysis``).

        Each ``(src[i], dst[i])`` names a link by unordered endpoint pair;
        every live copy of a matched pair dies. Mechanism (DESIGN.md
        §Decremental):

        1. **Tombstone** the live full buffer: one cached program per
           (buffer bucket, key bucket) masks out matches in place — the
           buffer keeps its shape, so churn never recompiles.
        2. **Certificate-hit rule**, one registry-driven loop over the
           MATERIALIZED certificates: probe each live pair with the same
           tombstone program. A certificate whose edges all survive is
           still a valid sparse certificate of the smaller graph (its
           forests are still spanning: deleting a non-forest edge cannot
           disconnect what the forests connect), so serving continues
           warm — the common dense-graph case, since certificates hold
           ≤ 2(n−1) of the E live edges. A certificate that lost an edge
           is rebuilt from the surviving full buffer through its
           already-cached ``load_state`` program (no new kernels, no
           retrace after warm-up); ``live_rebuilds`` counts the hits per
           certificate name.

        The removed-count and per-certificate hit counts are the only host
        syncs in the delete path (the rebuild decision is host-side control
        flow): one small scalar readback per probed buffer — the full
        buffer plus one per live certificate. Fusing them into one probe
        program is a possible future micro-optimization; the counters gate
        in ``scripts/check_bench.py`` pins today's program structure.
        """
        analysis = get_analysis(kind)
        kind = analysis.kind
        if not analysis.decremental:
            raise NotImplementedError(
                f"kind {kind!r} is not registered as decremental")
        if self.mesh is not None:
            raise NotImplementedError(
                "live deletions are single-device; use mesh=None (one-shot "
                "distributed deletion: analyze(..., delete=...))")
        if self._live is None:
            raise RuntimeError("no live graph: call load() first")
        live = self._live
        n_bucket = live.n_bucket
        with get_tracer().span("engine/delete_edges", kind=kind,
                               streamed=live.full is None):
            src = np.asarray(src, np.int32)
            dst = np.asarray(dst, np.int32)
            kcap = self._bucket(max(len(src), 1))
            keys = EdgeList.from_arrays(src, dst, n_bucket, capacity=kcap)

            if live.full is None:
                # streamed: tombstone the host spill ring (the edge-set
                # record), probe the device certificate states as usual,
                # rebuild hits by ring replay instead of a full-buffer load
                removed = live.stream.tombstone(src, dst)
                live.count = live.stream.count
            else:
                fs, fd, fm = live.full
                fm, removed = self._delete_pass((fs, fd, fm), keys, "full")
                live.full = (fs, fd, fm)
                live.count -= int(removed)

            for name, state in live.certs.items():
                if state is None:
                    continue
                _, hits = self._delete_pass(state[:3], keys, name)
                if int(hits):
                    live.rebuilds[name] += 1
                    live.certs[name] = (self._replay_state(name)
                                        if live.full is None else
                                        self._cert_load(name, n_bucket,
                                                        live.full))
            self._account_live_bytes()
            self._after_write()
            return self.current_analysis(kind=kind, final=final,
                                         certificate=certificate)

    def current_analysis(self, kind: str = "bridges", *,
                         final: str = "device",
                         certificate: str | None = None):
        """Analysis of the live graph (final stage only; no certificate
        recomputation). Serves EVERY registry kind straight off the live
        state of the certificate the kind resolves to — its declared
        default (2-edge kinds: the Borůvka pair; vertex kinds: the
        scan-first pair), or any registered override that preserves what
        the kind needs, e.g. ``certificate='hybrid'`` for cuts/bcc on
        sparse worlds (DESIGN.md §Certificate registry). The resolved
        certificate is materialized from the live full buffer on first
        use.
        """
        analysis = get_analysis(kind)
        kind = analysis.kind
        if self._live is None:
            raise RuntimeError("no live graph: call load() first")
        live = self._live
        tr = get_tracer()
        with tr.span(f"engine/current/{kind}", final=final):
            cert = self._materialize(
                self._resolve_certificate(analysis, certificate))[:3]
            if final == "host":
                with tr.span("stage/convert"):
                    s, d, m = (np.asarray(x) for x in cert)
                    return analysis.host_fn(s[m], d[m], live.n_nodes)
            key = ("final", kind, live.n_bucket, self.backend, None)
            fn = self._program(
                key, lambda: build_final_program(live.n_bucket, kind,
                                                 self._tick_trace))
            with tr.span(f"stage/final/{kind}") as sp:
                out = sp.sync(fn(*cert))
            with tr.span("stage/convert"):
                return analysis.to_result(out, live.n_nodes)

    def current_bridges(self, *, final: str = "device") -> set[tuple[int, int]]:
        """Bridges of the live graph (final stage only)."""
        return self.current_analysis("bridges", final=final)

    # ------------------------------------------------------------- distributed
    def _machines(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.machine_axes)

    def _analyze_distributed(self, src, dst, n_nodes: int, *, kind: str,
                             final: str, seed: int, delete=None,
                             certificate: str | None = None):
        from repro.core.partition import partition_edges

        analysis = get_analysis(kind)
        cert_name = self._resolve_certificate(analysis, certificate)
        tr = get_tracer()
        with tr.span(f"engine/analyze/{kind}", substrate="distributed",
                     schedule=self.schedule, final=final):
            with tr.span("stage/partition", machines=self._machines()):
                src = np.asarray(src, np.int32)
                dst = np.asarray(dst, np.int32)
                m = self._machines()
                psrc, pdst, pmask = partition_edges(src, dst, n_nodes, m,
                                                    seed=seed)
                shard_cap = self._bucket(psrc.shape[1])
                pad = shard_cap - psrc.shape[1]
                if pad:
                    psrc = np.pad(psrc, ((0, 0), (0, pad)))
                    pdst = np.pad(pdst, ((0, 0), (0, pad)))
                    pmask = np.pad(pmask, ((0, 0), (0, pad)))
                # one [1, cap] row per machine, on that machine's device
                rows = NamedSharding(self.mesh, P(self.machine_axes, None))
                args = tuple(jax.device_put(x, rows)
                             for x in (psrc, pdst, pmask))
                kcap = None
                if delete is not None:
                    # deletion keys are global: replicate to every machine,
                    # each tombstones its own shard before certifying
                    # (core/merge.py)
                    kel, kcap = self._delete_keys(delete, n_nodes)
                    args += (kel.src, kel.dst, kel.mask)
            key = ("dist", kind, n_nodes, shard_cap, kcap, self.backend,
                   self.schedule, final, self.merge, cert_name)
            fn = self._program(
                key, lambda: build_distributed_program(
                    self.mesh, self.machine_axes, n_nodes, kind, final,
                    self.schedule, self.merge, with_delete=kcap is not None,
                    certificate=cert_name))
            with tr.span(f"stage/pipeline/{kind}", substrate="distributed",
                         schedule=self.schedule, machines=m,
                         certificate=cert_name) as sp:
                with jax.set_mesh(self.mesh):
                    out = sp.sync(fn(*args))
            with tr.span("stage/convert"):
                # machine 0 (paper) — or any machine under xor/hierarchical
                # — answers
                shard0 = jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[0], out)
                if final == "host":
                    s, d, mk = shard0
                    return analysis.host_fn(s[mk], d[mk], n_nodes)
                return analysis.to_result(shard0, n_nodes)


_DEFAULT_ENGINE: BridgeEngine | None = None


def get_default_engine() -> BridgeEngine:
    """Process-wide single-device engine behind ``core.find_bridges``."""
    global _DEFAULT_ENGINE
    if _DEFAULT_ENGINE is None:
        _DEFAULT_ENGINE = BridgeEngine()
    return _DEFAULT_ENGINE


def find_bridges_batch(graphs, n_nodes, *, final: str = "device",
                       engine: BridgeEngine | None = None):
    """Module-level batched entry point over the default engine."""
    eng = engine if engine is not None else get_default_engine()
    return eng.find_bridges_batch(graphs, n_nodes, final=final)


def analyze_batch(graphs, n_nodes, *, kind: str = "bridges",
                  engine: BridgeEngine | None = None):
    """Module-level batched analysis (any kind) over the default engine."""
    eng = engine if engine is not None else get_default_engine()
    return eng.analyze_batch(graphs, n_nodes, kind=kind)
