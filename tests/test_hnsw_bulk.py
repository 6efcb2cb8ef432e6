"""Device-parallel bulk HNSW builder: invariants, recall parity vs the
incremental builder at equal ef, determinism, engine/collection wiring.

The contract under test (ISSUE 9): `bulk_build_device` produces a
`PackedHNSW` interchangeable with the incremental builder's — same graph
invariants (degree caps, no self-loops/dups, navigable base layer), search
recall within 0.02 of incremental at equal ef — while building in batched
device phases instead of one-at-a-time inserts.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HNSWConfig, bulk_build_device, exact_knn, recall_at_k
from repro.core.engine import EngineConfig, QuantixarEngine
from repro.core.hnsw_build import (PAD, build as incremental_build,
                                   knn_ids_dists, preprocess_vectors)
from repro.core.hnsw_bulk import MIN_DEVICE_N, _bfs_reachable
from repro.core.hnsw_search import search, to_device
from repro.data.synthetic import embedding_like, gaussian_mixture

N, DIM = 1200, 24
K = 10

# small coarse_cluster so the coarse mode actually multi-clusters at N=1200
LEVEL_CFG = dict(bulk_mode="level", build_batch=256)
COARSE_CFG = dict(bulk_mode="coarse", coarse_cluster=300)


@pytest.fixture(scope="module")
def corpus():
    return gaussian_mixture(N, DIM, n_clusters=20, scale=0.2, seed=0)


@pytest.fixture(scope="module")
def queries():
    return gaussian_mixture(40, DIM, n_clusters=20, scale=0.2, seed=9)


@pytest.fixture(scope="module", params=["level", "coarse"])
def packed(request, corpus):
    kw = LEVEL_CFG if request.param == "level" else COARSE_CFG
    return bulk_build_device(
        corpus, HNSWConfig(M=12, metric="cosine", seed=0, **kw))


def _search_recall(packed, corpus, queries, metric, ef=64):
    g, max_level, dev_metric = to_device(packed)
    qn = preprocess_vectors(queries, metric)
    _, ids = search(g, jnp.asarray(qn), k=K, ef=ef, max_level=max_level,
                    metric=dev_metric)
    gt = exact_knn(queries, corpus, K, metric=metric)
    return recall_at_k(np.asarray(ids), gt)


class TestGraphInvariants:
    """Parametrized over both bulk modes via the `packed` fixture."""

    def test_degrees_bounded(self, packed):
        assert (packed.adj0 != PAD).sum(1).max() <= packed.config.m0

    def test_no_duplicate_neighbours(self, packed):
        """Required by the device search's scatter-add visited trick."""
        for row in packed.adj0:
            real = row[row != PAD]
            assert len(set(real.tolist())) == len(real)

    def test_no_self_loops(self, packed):
        for i, row in enumerate(packed.adj0):
            assert i not in row[row != PAD]

    def test_neighbour_ids_in_range(self, packed):
        real = packed.adj0[packed.adj0 != PAD]
        assert real.min() >= 0 and real.max() < packed.n

    def test_entry_point_valid(self, packed):
        assert 0 <= packed.entry_global < packed.n
        assert packed.levels[packed.entry_global] == packed.max_level

    def test_connected_at_base(self, packed):
        """Post-repair the base layer must be >=99% reachable from entry."""
        seen = _bfs_reachable(packed.adj0, packed.entry_global)
        assert seen.mean() >= 0.99

    def test_level_distribution_geometric(self, packed):
        share_upper = (packed.levels >= 1).mean()
        assert 0.02 < share_upper < 0.25   # ~1/M ± slack

    def test_build_info_populated(self, packed):
        info = packed.build_info
        assert info["builder_mode"] in ("level", "coarse")
        assert info["build_repaired"] >= 0


class TestRecallParity:
    """Bulk recall within 0.02 of incremental at equal ef (the ISSUE gate)."""

    @pytest.mark.parametrize("metric", ["l2", "cosine"])
    def test_vs_incremental(self, corpus, queries, metric):
        """Default auto config (what `builder="bulk"` users get)."""
        cfg = dict(M=12, ef_construction=80, metric=metric, seed=0)
        inc = incremental_build(corpus, HNSWConfig(**cfg))
        blk = bulk_build_device(corpus, HNSWConfig(**cfg))
        r_inc = _search_recall(inc, corpus, queries, metric)
        r_blk = _search_recall(blk, corpus, queries, metric)
        assert r_blk >= r_inc - 0.02, (r_blk, r_inc)

    @pytest.mark.parametrize("kw", [LEVEL_CFG, COARSE_CFG],
                             ids=["level", "coarse"])
    def test_forced_mode_recall_floor(self, corpus, queries, kw):
        blk = bulk_build_device(
            corpus, HNSWConfig(M=12, metric="cosine", seed=0, **kw))
        assert _search_recall(blk, corpus, queries, "cosine") > 0.9


class TestEmbeddingScaleRecall:
    def test_recall_at_ef128_on_embedding_like(self):
        """768-d three-scale embedding clusters at 20k rows, bulk-built with
        the defaults (coarse mode, 2-3 clusters).  A greedy descent over the
        builder's member-kNN upper layers ended no nearer than a random
        entry here (recall@10 0.925); the entry scan of the layer>=1
        members gives 0.99."""
        n, nq = 20_000, 200
        data = embedding_like(n + nq, 768, seed=0)
        corpus, queries = data[:n], data[n:]
        packed = bulk_build_device(
            corpus, HNSWConfig(metric="cosine", seed=0, expansion_width=4))
        assert packed.build_info["builder_mode"] == "coarse"
        g, max_level, dev_metric = to_device(packed)
        _, ids = search(g, jnp.asarray(preprocess_vectors(queries, "cosine")),
                        k=K, ef=128, max_level=max_level, metric=dev_metric,
                        expansion_width=4)
        gt = exact_knn(queries, corpus, K, metric="cosine")
        assert recall_at_k(np.asarray(ids), gt) >= 0.95


class TestDeterminism:
    @pytest.mark.parametrize("kw", [LEVEL_CFG, COARSE_CFG],
                             ids=["level", "coarse"])
    def test_same_seed_same_graph(self, corpus, kw):
        cfg = HNSWConfig(M=12, metric="l2", seed=3, **kw)
        a = bulk_build_device(corpus, cfg)
        b = bulk_build_device(corpus, cfg)
        assert (a.adj0 == b.adj0).all()
        assert (a.levels == b.levels).all()
        assert (a.upper_ids == b.upper_ids).all()
        assert a.entry_global == b.entry_global


class TestModeSelection:
    def test_auto_picks_coarse_above_threshold(self, corpus):
        p = bulk_build_device(
            corpus, HNSWConfig(M=12, seed=0, coarse_threshold=1000,
                               coarse_cluster=300))
        assert p.build_info["builder_mode"] == "coarse"
        assert p.build_info["build_clusters"] >= 2

    def test_auto_picks_level_below_threshold(self, corpus):
        p = bulk_build_device(
            corpus[:400], HNSWConfig(M=12, seed=0, coarse_threshold=1000,
                                     build_batch=128))
        assert p.build_info["builder_mode"] == "level"
        assert p.build_info["build_batches"] >= 2

    def test_tiny_corpus_falls_back_to_reference(self, corpus):
        tiny = corpus[:MIN_DEVICE_N - 2]
        p = bulk_build_device(tiny, HNSWConfig(M=8, seed=0))
        assert p.build_info["builder_mode"] == "ref_small_n"
        assert p.n == len(tiny)

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            HNSWConfig(bulk_mode="turbo")


class TestProgressCallback:
    @pytest.mark.parametrize("kw", [LEVEL_CFG, COARSE_CFG],
                             ids=["level", "coarse"])
    def test_phases_reported_monotone(self, corpus, kw):
        calls = []
        bulk_build_device(corpus, HNSWConfig(M=12, seed=0, **kw),
                          progress=lambda *a: calls.append(a))
        assert calls, "progress callback never fired"
        for phase, done, total in calls:
            assert isinstance(phase, str) and 0 <= done <= total
        per_phase = {}
        for phase, done, _ in calls:
            assert done >= per_phase.get(phase, 0)   # monotone within phase
            per_phase[phase] = done

    def test_incremental_build_progress(self, corpus):
        calls = []
        incremental_build(corpus[:300],
                          HNSWConfig(M=8, ef_construction=40, seed=0),
                          progress=lambda *a: calls.append(a))
        assert calls and calls[-1][1] == 300


class TestChunkedExactKnn:
    """`knn_ids_dists` must be exact regardless of chunking (the fix for
    the seed builder's O(n^2)-memory self-join)."""

    def test_matches_unchunked(self):
        rng = np.random.RandomState(5)
        q = rng.randn(70, 16).astype(np.float32)
        x = rng.randn(450, 16).astype(np.float32)
        ref_ids, ref_d = knn_ids_dists(q, x, 9, metric="l2",
                                       chunk=4096, corpus_chunk=10 ** 9)
        for chunk, cchunk in [(16, 64), (70, 33), (7, 450), (70, 1)]:
            ids, d = knn_ids_dists(q, x, 9, metric="l2", chunk=chunk,
                                   corpus_chunk=cchunk)
            np.testing.assert_allclose(d, ref_d, rtol=1e-5, atol=1e-5)
            assert (ids == ref_ids).mean() > 0.999  # ties may reorder

    def test_dot_metric(self):
        rng = np.random.RandomState(6)
        q = rng.randn(20, 8).astype(np.float32)
        x = rng.randn(100, 8).astype(np.float32)
        ids, d = knn_ids_dists(q, x, 5, metric="dot", chunk=8,
                               corpus_chunk=17)
        want = -(q @ x.T)
        np.testing.assert_allclose(
            d, np.sort(want, axis=1)[:, :5], rtol=1e-5, atol=1e-5)
        assert (np.take_along_axis(want, ids, axis=1)
                == np.sort(want, axis=1)[:, :5]).all()


class TestEngineWiring:
    @pytest.mark.parametrize("quant", ["none", "pq", "bq"])
    def test_bulk_builder_with_quantization(self, corpus, queries, quant):
        from repro.core.pq import PQConfig
        eng = QuantixarEngine(EngineConfig(
            dim=DIM, metric="cosine", quantization=quant, builder="bulk",
            pq=PQConfig(m=8),
            hnsw=HNSWConfig(M=12, seed=0, **COARSE_CFG)))
        eng.add(corpus)
        eng.build()
        d, ids = eng.search(queries, k=K)
        gt = exact_knn(queries, corpus, K, metric="cosine")
        floor = 0.9 if quant == "none" else 0.7
        assert recall_at_k(np.asarray(ids), gt) > floor
        st = eng.stats()
        assert st["builder"] == "bulk"
        assert st["builder_mode"] == "coarse"

    def test_bulk_ref_builder_selectable(self, corpus, queries):
        eng = QuantixarEngine(EngineConfig(
            dim=DIM, metric="cosine", builder="bulk_ref",
            hnsw=HNSWConfig(M=12, seed=0)))
        eng.add(corpus[:300])
        eng.build()
        _, ids = eng.search(queries, k=5)
        gt = exact_knn(queries, corpus[:300], 5, metric="cosine")
        assert recall_at_k(np.asarray(ids), gt) > 0.85

    def test_invalid_builder_rejected(self):
        with pytest.raises(ValueError):
            EngineConfig(dim=8, builder="magic")

    def test_build_progress_threaded(self, corpus):
        phases = []
        eng = QuantixarEngine(EngineConfig(
            dim=DIM, builder="bulk",
            hnsw=HNSWConfig(M=12, seed=0, **COARSE_CFG)))
        eng.add(corpus)
        eng.build(progress=lambda ph, d, t: phases.append(ph))
        assert phases, "Engine.build() dropped the progress callback"


class TestCollectionCompact:
    def test_compact_rebuilds_through_bulk(self, corpus, queries):
        from repro.api import Collection, CollectionSchema, VectorField
        col = Collection(CollectionSchema(
            name="bulk-compact",
            vector=VectorField(dim=DIM, metric="cosine", builder="bulk",
                               hnsw=HNSWConfig(M=12, seed=0, **COARSE_CFG))))
        try:
            ids = [f"e{i}" for i in range(N)]
            col.upsert(ids, corpus)
            col.delete(ids[::10])
            assert col.tombstones == len(ids[::10])
            reclaimed = col.compact()
            assert reclaimed == len(ids[::10])
            assert col.tombstones == 0
            d, rows = col.search(queries[:4], k=5)
            assert rows.shape == (4, 5) and (rows >= 0).all()
            # stats after the (lazy) rebuild expose the bulk build_info
            st = col.stats()
            assert st["builder"] == "bulk"
            assert st["builder_mode"] == "coarse"
        finally:
            col.close()
