# Pallas TPU kernels for the compute hot-spots:
#   boruvka_round   — the Borůvka and scan-first-search round reductions (the
#                     paper's certificate inner loop)
#   segment_min     — unsorted segment-min (roots, Euler-tour discovery)
#   flash_attention — blocked online-softmax attention (LM archs)
#   embedding_bag   — ragged gather+pool over big tables (recsys)
#
# Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (public
# wrapper that dispatches on ``use_pallas``), ref.py (pure-jnp oracle).
import jax


def kernel_path(use_pallas: bool = False) -> str:
    """What a connectivity op runs for this ``use_pallas`` setting, as a
    record string; the ops dispatch through it, so it names what ran.

    ``oracle``: the XLA path (the ``ref.py`` functions), the default on
    every platform. ``pallas``: the compiled kernel, when ``use_pallas`` is
    set on a TPU. ``interpret``: the kernel under the Pallas interpreter,
    when ``use_pallas`` is set where there is no TPU (the parity tests).
    """
    if not use_pallas:
        return "oracle"
    return "pallas" if jax.default_backend() == "tpu" else "interpret"
