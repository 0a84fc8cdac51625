"""The hot-path Pallas kernels compile for a v5e chip at the paper's width.

The chip is described, not attached (``jax.experimental.topologies``), so
these tests run on a host with no TPU; they show what the chip's compiler
accepts, not results or times. The topology is described only inside the
module fixture below: a library load at import or collection time would
take libtpu's lock in every test worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.boruvka_round.kernel import (
    boruvka_round_pallas,
    frontier_round_pallas,
)
from repro.kernels.segment_min.kernel import segment_min_pallas

#: the paper's vertex count and one streamed chunk of edges
N, E = 100_000, 1 << 20
#: one v5e chip's HBM
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # libtpu logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's compile can be written to the persistent cache
        # but never read back: keep the cache off around these compiles
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _programs(sharding):
    def spec(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)

    edges = (spec(E), spec(E), spec(E, jnp.bool_))
    return {
        "boruvka_round": (
            lambda s, d, m, lb: boruvka_round_pallas(s, d, m, lb, N),
            edges + (spec(N),)),
        "frontier_round": (
            lambda s, d, m, f, v: frontier_round_pallas(s, d, m, f, v, N),
            edges + (spec(N, jnp.bool_), spec(N, jnp.bool_))),
        "segment_min": (
            lambda k, i: segment_min_pallas(k, i, N),
            (spec(E), spec(E))),
    }


@pytest.mark.parametrize("kernel",
                         ["boruvka_round", "frontier_round", "segment_min"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args = _programs(one_chip)[kernel]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
