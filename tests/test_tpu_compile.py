"""Compile the served-path Pallas kernels for a described TPU v5e chip.

Interpret mode cannot see what the chip's compiler refuses (slices not
aligned to the (8, 128) tiling, more VMEM than a kernel may use, ops Mosaic
cannot lower).  These tests compile each kernel on the served search and
bulk-build path for one chip of a described ``v5e:2x2`` topology, at the
shapes ``chip_smoke.py`` serves: 1M rows x 768-d float tables, PQ m=96
k=256 code tables, 768-bit BQ word tables, L = width·M0 ids per call.  No
chip is needed; nothing runs.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hnsw_search import HNSWGraph, search
from repro.kernels.beam_gather import (beam_gather_adc_kernel,
                                       beam_gather_hamming_kernel,
                                       beam_gather_kernel)
from repro.kernels.bulk_prune import pair_gather_kernel
from repro.kernels.pq_adc import pq_adc_kernel

N, D = 1_000_000, 768          # the deployment's corpus
PQ_M, PQ_K = 96, 256           # 8 dims per sub-space
BQ_WORDS = 768 // 32           # one bit per dim
TABLE_LANES = 128              # m and W pad to one lane tile
L = 4 * 32                     # expansion_width x M0 ids per beam step


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                         # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the cache but cannot be read
    back without a chip; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _cases(s):
    """name -> (fn, argument shapes) at the served widths."""
    table = s((N, 1, D), jnp.float32)
    return {
        "beam_gather_l2": (
            lambda q, ids, t: beam_gather_kernel(q, ids, t, mode="l2"),
            (s((D,), jnp.float32), s((L,), jnp.int32), table)),
        "beam_gather_dot": (
            lambda q, ids, t: beam_gather_kernel(q, ids, t, mode="dot"),
            (s((D,), jnp.float32), s((L,), jnp.int32), table)),
        "beam_gather_dot_entry": (     # the entry point's one-row call
            lambda q, ids, t: beam_gather_kernel(q, ids, t, mode="dot"),
            (s((D,), jnp.float32), s((1,), jnp.int32), table)),
        "beam_gather_adc": (
            beam_gather_adc_kernel,
            (s((PQ_M, PQ_K), jnp.float32), s((L,), jnp.int32),
             s((N, 1, TABLE_LANES), jnp.int32))),
        "beam_gather_hamming": (
            beam_gather_hamming_kernel,
            (s((BQ_WORDS,), jnp.uint32), s((L,), jnp.int32),
             s((N, 1, TABLE_LANES), jnp.uint32))),
        "pair_gather": (               # vmapped as the bulk prune calls it
            lambda ids, t: jax.vmap(
                lambda r: pair_gather_kernel(r, t, mode="dot"))(ids),
            (s((512, 60), jnp.int32), table)),
        "pq_adc_dense": (              # off the served path, compiles too
            pq_adc_kernel,
            (s((32, PQ_M, PQ_K), jnp.float32), s((N, PQ_M), jnp.uint8))),
    }


@pytest.mark.parametrize("name", ["beam_gather_l2", "beam_gather_dot",
                                  "beam_gather_dot_entry", "beam_gather_adc",
                                  "beam_gather_hamming", "pair_gather",
                                  "pq_adc_dense"])
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _cases(s)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("metric", ["dot", "adc", "hamming"])
def test_served_search_compiles_for_v5e(metric, one_chip,
                                        no_persistent_cache, monkeypatch):
    """The whole vmapped HNSW search program, 32 queries, ef=128, with the
    trip counters the engine fetches beside the answers.  The process's
    backend is the CPU, so the test steers the kernel dispatch to the TPU
    branch (compiled kernels) itself."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    search.clear_cache()

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    codes = {"dot": None,
             "adc": s((N, 1, TABLE_LANES), jnp.int32),
             "hamming": s((N, 1, TABLE_LANES), jnp.uint32)}[metric]
    q_codes = {"dot": None,
               "adc": s((32, PQ_M, PQ_K), jnp.float32),
               "hamming": s((32, BQ_WORDS), jnp.uint32)}[metric]
    g = HNSWGraph(vectors=s((N, 1, D), jnp.float32),
                  adj0=s((N, 32), jnp.int32),
                  upper_ids=s((62_500,), jnp.int32),
                  upper_vecs=s((62_500, D), jnp.float32),
                  entry_global=s((), jnp.int32), codes=codes)
    compiled = search.lower(g, s((32, D), jnp.float32), k=10, ef=128,
                            max_level=4, metric=metric, expansion_width=4,
                            q_codes=q_codes, with_iters=True).compile()
    search.clear_cache()
    # the entry point's one-row call and the per-step block call
    assert compiled.as_text().count("tpu_custom_call") >= 2
