"""Batched graph containers + vmapped analysis pipelines.

``BatchedEdgeList`` stacks B same-capacity edge buffers so B independent
graphs resolve in ONE device dispatch: every analysis pipeline (certificate
-> tour -> final stage, for every kind in the analysis registry) is
rank-polymorphic jnp code, so a single ``jax.vmap`` lifts it to the batch.
All graphs in a batch share one (n_nodes, capacity) shape bucket — that is
what makes the batched program compile once and serve any mix of nearby
graph sizes (see DESIGN.md §Engine, §Analysis registry).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.connectivity.common import tour_state
from repro.connectivity.registry import (  # noqa: F401  (re-exports)
    ANALYSIS_KINDS,
    certificate_fn,
    get_analysis,
    normalize_kind,
)
from repro.core.certificate import certificate_capacity
from repro.graph.datastructs import (
    INT,
    EdgeList,
    admission_capacity,
    pad_edges,
    tombstone_mask,
)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src", "dst", "mask"],
    meta_fields=["n_nodes"],
)
@dataclasses.dataclass(frozen=True)
class BatchedEdgeList:
    """B stacked padded edge lists with a shared static vertex count.

    src, dst : int32[B, capacity]
    mask     : bool[B, capacity]
    n_nodes  : int   static vertex-count bucket shared by the whole batch
    """

    src: jax.Array
    dst: jax.Array
    mask: jax.Array
    n_nodes: int

    @property
    def batch_size(self) -> int:
        return self.src.shape[0]

    @property
    def capacity(self) -> int:
        return self.src.shape[1]

    def __getitem__(self, i: int) -> EdgeList:
        return EdgeList(self.src[i], self.dst[i], self.mask[i], self.n_nodes)

    @staticmethod
    def from_graphs(graphs, n_nodes: int, capacity: int | None = None,
                    batch_pad: int | None = None) -> "BatchedEdgeList":
        """Stack ``[(src, dst), ...]`` into one batched buffer.

        Each graph is padded to the shared ``capacity`` (default: the max raw
        edge count). ``batch_pad`` optionally pads the batch dimension with
        empty graphs so nearby batch sizes share one program too.
        """
        graphs = [(np.asarray(s, np.int32), np.asarray(d, np.int32))
                  for s, d in graphs]
        if capacity is None:
            capacity = max(max((len(s) for s, _ in graphs), default=1), 1)
        rows = []
        for s, d in graphs:
            if len(s) > capacity:
                raise ValueError(
                    f"graph with {len(s)} edges exceeds batch capacity {capacity}"
                )
            rows.append(pad_edges(EdgeList.from_arrays(s, d, n_nodes), capacity))
        b = len(rows)
        total = max(batch_pad if batch_pad is not None else b, b)
        src = jnp.stack([r.src for r in rows]
                        + [jnp.zeros((capacity,), INT)] * (total - b))
        dst = jnp.stack([r.dst for r in rows]
                        + [jnp.zeros((capacity,), INT)] * (total - b))
        mask = jnp.stack([r.mask for r in rows]
                         + [jnp.zeros((capacity,), bool)] * (total - b))
        return BatchedEdgeList(src, dst, mask, n_nodes)

    def delete_edges(self, deletions) -> "BatchedEdgeList":
        """Tombstone per-graph deletion keys out of the batch in ONE vmapped
        device dispatch (DESIGN.md §Decremental).

        ``deletions``: iterable of per-graph ``(ksrc, kdst)`` endpoint-pair
        arrays (or ``None`` for no deletions in that row), at most one entry
        per batch row. Every live copy of a matched pair is masked out; the
        buffers keep their shapes, so downstream batched programs reuse
        their compiled executables.
        """
        dels = list(deletions)
        if len(dels) > self.batch_size:
            raise ValueError(
                f"{len(dels)} deletion lists for a batch of {self.batch_size}")
        empty = (np.zeros(0, np.int32), np.zeros(0, np.int32))
        keys = [empty if sd is None
                else (np.asarray(sd[0], np.int32), np.asarray(sd[1], np.int32))
                for sd in dels]
        kcap = admission_capacity(max((len(s) for s, _ in keys), default=1), 1)
        kel = BatchedEdgeList.from_graphs(keys, self.n_nodes, capacity=kcap,
                                          batch_pad=self.batch_size)
        mask, _ = _batched_tombstone(self.src, self.dst, self.mask,
                                     kel.src, kel.dst, kel.mask)
        return BatchedEdgeList(self.src, self.dst, mask, self.n_nodes)


#: jit caches per (capacity, key-capacity, batch) shape — the batched
#: tombstone compiles once per bucket like every other engine program.
_batched_tombstone = jax.jit(jax.vmap(tombstone_mask))


def make_analysis_fn(n_nodes: int, kind: str = "bridges",
                     final: str = "device", on_trace=None,
                     with_delete: bool = False,
                     certificate: str | None = None):
    """The un-vmapped query core for one analysis kind, registry-driven.

    ``(src, dst, mask) -> (answer, rounds, full)``: the answer is the
    kind's declared device buffers (see ``Analysis.out_struct`` / DESIGN.md
    §Analysis registry), or — with ``final='host'`` — the kind's sparse
    certificate, on which the caller runs the kind's sequential host
    reference afterwards; ``rounds`` (int32[2]) counts the certificate's
    forest rounds and ``full`` (int32[2]) those of them whose edge scan ran
    over the whole buffer (``Certificate.build_ex``), zeros where none is
    built.

    Every kind follows the same registry-declared shape: pick the buffer
    the kind's ``device_input`` names (its certificate for the 2-edge
    kinds, the raw input buffer for the vertex kinds — every tour
    primitive is polylog-round, so the O(diameter) SFS certificate is
    only built where a bounded exchange format is actually needed), take
    one shared ``tour_state`` pass over it, and apply the kind's
    final-stage test. This single function is the pipeline body for BOTH
    the engine's single-graph programs and, lifted by ``jax.vmap``, the
    batched ones.

    ``with_delete=True`` prepends a tombstone pass: the function takes
    three extra ``(ksrc, kdst, kmask)`` deletion-key buffers and answers
    on the graph minus every matched pair (DESIGN.md §Decremental) — the
    one-shot spelling of deletion, on every substrate.

    ``certificate`` overrides the kind's declared certificate type
    (resolved via the certificate registry, ``core.certs``); it only
    matters where a certificate is actually built — ``final='host'`` and
    the ``device_input='certificate'`` kinds. Callers are expected to have
    validated the override (``BridgeEngine`` does).
    """
    analysis = get_analysis(kind)
    if final not in ("device", "host"):
        raise ValueError(f"unknown final stage {final!r}")
    cert_cap = certificate_capacity(n_nodes)
    out_cap = max(n_nodes - 1, 1)
    certify = certificate_fn(certificate if certificate is not None
                             else analysis.certificate)

    cert_label = certificate if certificate is not None else analysis.certificate

    def one(src, dst, mask, *keys):
        # named_scope labels match the host span taxonomy 1:1 (DESIGN.md
        # §Observability) — jaxpr metadata only, never part of a cache key
        if on_trace is not None:
            on_trace()
        if with_delete:
            with jax.named_scope("stage/tombstone"):
                mask, _ = tombstone_mask(src, dst, mask, *keys)
        buf = EdgeList(src, dst, mask, n_nodes)
        rounds = full = jnp.zeros((2,), INT)
        if final == "host" or analysis.device_input == "certificate":
            with jax.named_scope(f"stage/certificate_build/{cert_label}"):
                buf, rounds, full = certify(buf, capacity=cert_cap)
        if final == "host":
            return (buf.src, buf.dst, buf.mask), rounds, full
        with jax.named_scope(f"stage/final/{analysis.kind}"):
            st = tour_state(buf.src, buf.dst, buf.mask, n_nodes)
            with jax.named_scope("emit"):
                out = analysis.device_fn(buf.src, buf.dst, buf.mask,
                                         n_nodes, st, out_cap)
        return out, rounds, full

    return one


def make_batched_pipeline(n_nodes: int, final: str = "device", on_trace=None,
                          kind: str = "bridges", with_delete: bool = False,
                          certificate: str | None = None):
    """jit(vmap(one-graph analysis)) over the leading batch axis."""
    return jax.jit(jax.vmap(make_analysis_fn(n_nodes, kind, final, on_trace,
                                             with_delete=with_delete,
                                             certificate=certificate)))
