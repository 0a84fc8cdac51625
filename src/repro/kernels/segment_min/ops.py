"""Public segment_min op: the XLA scatter-min by default, the Pallas kernel
when a caller sets ``use_pallas`` (see ``repro.kernels.kernel_path``)."""
from __future__ import annotations

from repro.kernels import kernel_path
from repro.kernels.segment_min.kernel import segment_min_pallas
from repro.kernels.segment_min.ref import segment_min_ref


def segment_min(keys, ids, num_segments: int, use_pallas: bool = False):
    """min(keys) per segment id; empty segments -> INF32."""
    path = kernel_path(use_pallas)
    if path == "oracle":
        return segment_min_ref(keys, ids, num_segments)
    return segment_min_pallas(keys, ids, num_segments,
                              interpret=path == "interpret")
