"""Engine dispatch layer: compile-once program cache + program builders.

Split out of ``engine.py`` (DESIGN.md §Engine): everything that builds or
caches a jitted executable lives here. ``ProgramCache`` is the keyed
compile-once store (hit/miss counters feed ``EngineStats``); the
``build_*_program`` functions are the engine's program factories — each
returns a fresh ``jax.jit`` callable for one (shape bucket, kind,
certificate) configuration, with the traced stages wrapped in
``jax.named_scope`` labels that match the host span taxonomy 1:1
(DESIGN.md §Observability), so an on-device profiler capture lines up
with the wall-clock spans the engine records around each dispatch.

``named_scope`` is jaxpr metadata only: it never changes the compiled
program, its output, or its cache key — the no-retrace tests gate this.
"""
from __future__ import annotations

import jax

from repro.connectivity.common import tour_state
from repro.connectivity.registry import get_analysis
from repro.core.certificate import certificate_capacity
from repro.core.certs import get_certificate
from repro.engine.batched import make_analysis_fn, make_batched_pipeline
from repro.graph.datastructs import (
    EdgeList,
    admission_capacity,
    compact_edges,
    concat_edges,
    tombstone_mask,
)


def admission_bucket(n_nodes: int, n_edges: int,
                     min_bucket: int = 16) -> tuple[int, int]:
    """The pow-2 ``(n_bucket, capacity_bucket)`` shape bucket a request
    is admitted under — exactly the bucket components of every
    ``ProgramCache`` key, which makes the bucket the scheduler's
    admission currency: two requests with equal admission buckets are
    guaranteed to share one compiled program, so coalescing them can
    never retrace (``engine/scheduler.py``; DESIGN.md §Serving)."""
    return (admission_capacity(int(n_nodes), min_bucket),
            admission_capacity(max(int(n_edges), 1), min_bucket))


class ProgramCache:
    """Compile-once store: ``get(key, build)`` builds on first use and
    counts hits afterwards (into the shared ``EngineStats``)."""

    def __init__(self, stats):
        self.stats = stats
        self._programs: dict[tuple, object] = {}

    def get(self, key: tuple, build):
        fn = self._programs.get(key)
        if fn is None:
            self.stats.misses += 1
            fn = self._programs[key] = build()
        else:
            self.stats.hits += 1
        return fn

    def __len__(self) -> int:
        return len(self._programs)

    def __contains__(self, key: tuple) -> bool:
        return key in self._programs

    def keys(self):
        """The cached program keys (read-only view). The checkpoint-restore
        path reports these to prove warm-serve readiness: restoring a
        ``LiveState`` into an engine whose cache already holds the bucket's
        programs must serve with zero retraces — restore itself runs no
        program, so the set must be unchanged across it
        (``BridgeEngine.restore_live``; pinned by fig11 EXACT counters)."""
        return self._programs.keys()


# ------------------------------------------------------------ one-shot
def build_analysis_program(n_bucket: int, kind: str, final: str, on_trace,
                           with_delete: bool = False,
                           certificate: str | None = None):
    """Single-graph one-shot pipeline (certificate + final fused into one
    XLA program); the host span around its dispatch is
    ``stage/pipeline/<kind>``."""
    return jax.jit(make_analysis_fn(n_bucket, kind, final, on_trace,
                                    with_delete=with_delete,
                                    certificate=certificate))


def build_batched_program(n_bucket: int, kind: str, final: str, on_trace,
                          with_delete: bool = False,
                          certificate: str | None = None):
    """vmapped one-shot pipeline over the batch axis."""
    return make_batched_pipeline(n_bucket, final=final, on_trace=on_trace,
                                 kind=kind, with_delete=with_delete,
                                 certificate=certificate)


# ---------------------------------------------------------- live-state
def build_cert_load_program(name: str, n_bucket: int, on_trace):
    """Program for one certificate type's ``load_state``: (src, dst,
    mask) buffer -> live state tuple. ONE program per (certificate,
    buffer bucket) serves the initial load, the lazy materialization,
    and the decremental certificate-hit rebuild — the registered
    ``load_state`` IS the rebuild program factory. Span/scope label:
    ``stage/certificate_build/<name>``."""
    desc = get_certificate(name)
    cert_cap = certificate_capacity(n_bucket)

    def run(src, dst, mask):
        on_trace()
        with jax.named_scope(f"stage/certificate_build/{name}"):
            return desc.load_state(EdgeList(src, dst, mask, n_bucket),
                                   cert_cap)

    return jax.jit(run)


def build_cert_insert_program(name: str, n_bucket: int, on_trace):
    """Program for one certificate type's ``fold_state``: live state +
    delta buffer -> updated state. For the warm-start Borůvka pair the
    fold scans only the delta; for the rescan certificates (sfs,
    hybrid) it re-certifies the bounded cert ∪ delta union — O(n + Δ)
    either way, never O(E), with the same shape every call. Span/scope
    label: ``stage/merge/<name>`` (the fold IS the warm-start
    certificate merge)."""
    desc = get_certificate(name)
    cert_cap = certificate_capacity(n_bucket)

    def run(*args):
        on_trace()
        state, (rs, rd, rm) = args[:-3], args[-3:]
        with jax.named_scope(f"stage/merge/{name}"):
            return desc.fold_state(state, EdgeList(rs, rd, rm, n_bucket),
                                   cert_cap)

    return jax.jit(run)


def build_append_program(n_bucket: int, out_cap: int, on_trace):
    """Compact-append the delta into the live full buffer: tombstoned
    holes are reclaimed, real edges land at the front, and the output
    capacity is a host-chosen bucket (same as the input except when the
    live edge count crosses it — the only churn event that compiles a
    new program). Span/scope label: ``stage/append``."""

    def run(fs, fd, fm, rs, rd, rm):
        on_trace()
        with jax.named_scope("stage/append"):
            out = compact_edges(
                concat_edges(EdgeList(fs, fd, fm, n_bucket),
                             EdgeList(rs, rd, rm, n_bucket)), out_cap)
            return out.src, out.dst, out.mask

    return jax.jit(run)


def build_delete_program(on_trace):
    """Tombstone pass: mask matched (min, max) keys out of a buffer and
    count the kills. Shared by the full-buffer deletion and the
    certificate-hit probe (same program per (capacity, key-bucket)).
    Span/scope label: ``stage/tombstone``."""

    def run(s, d, m, ks, kd, km):
        on_trace()
        with jax.named_scope("stage/tombstone"):
            return tombstone_mask(s, d, m, ks, kd, km)

    return jax.jit(run)


def build_final_program(n_bucket: int, kind: str, on_trace):
    """Final analysis stage over the kind's live certificate. Span/scope
    label: ``stage/final/<kind>``."""
    analysis = get_analysis(kind)
    out_cap = max(n_bucket - 1, 1)

    def run(cs, cd, cm):
        on_trace()
        with jax.named_scope(f"stage/final/{kind}"):
            st = tour_state(cs, cd, cm, n_bucket)
            with jax.named_scope("emit"):
                return analysis.device_fn(cs, cd, cm, n_bucket, st, out_cap)

    return jax.jit(run)


# ---------------------------------------------------------- distributed
def build_distributed_program(mesh, machine_axes, n_nodes: int, kind: str,
                              final: str, schedule: str, merge: str,
                              with_delete: bool = False,
                              certificate: str | None = None):
    """The paper's full distributed pipeline as one shard_map program."""
    from repro.core.merge import build_distributed_analysis_fn

    fn = build_distributed_analysis_fn(
        mesh, machine_axes, n_nodes, schedule=schedule,
        final=final, merge=merge, kind=kind,
        with_deletions=with_delete, certificate=certificate)
    return jax.jit(fn)
