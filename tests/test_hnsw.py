"""HNSW: graph invariants, recall vs brute force, device/host parity."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HNSWConfig, build, bulk_build, exact_knn, recall_at_k
from repro.core.hnsw_build import PAD, preprocess_vectors
from repro.core.hnsw_search import search, search_numpy_reference, to_device
from repro.data.synthetic import gaussian_mixture

N, DIM = 1200, 24


@pytest.fixture(scope="module")
def corpus():
    return gaussian_mixture(N, DIM, n_clusters=20, scale=0.2, seed=0)


@pytest.fixture(scope="module")
def queries():
    return gaussian_mixture(40, DIM, n_clusters=20, scale=0.2, seed=9)


@pytest.fixture(scope="module")
def packed(corpus):
    return build(corpus, HNSWConfig(M=12, ef_construction=80,
                                    metric="cosine", seed=0))


@pytest.fixture(scope="module")
def packed_bulk(corpus):
    return bulk_build(corpus, HNSWConfig(M=12, metric="cosine", seed=0))


class TestGraphInvariants:
    def test_degrees_bounded(self, packed):
        deg0 = (packed.adj0 != PAD).sum(1)
        assert deg0.max() <= packed.config.m0

    def test_no_duplicate_neighbours(self, packed):
        """Required by the device search's scatter-add visited trick."""
        for row in packed.adj0:
            real = row[row != PAD]
            assert len(set(real.tolist())) == len(real)

    def test_no_self_loops(self, packed):
        for i, row in enumerate(packed.adj0):
            assert i not in row[row != PAD]

    def test_entry_point_valid(self, packed):
        assert 0 <= packed.entry_global < packed.n
        assert packed.levels[packed.entry_global] == packed.max_level

    def test_mostly_connected_at_base(self, packed):
        """BFS from entry reaches nearly every node (navigability)."""
        seen = {packed.entry_global}
        frontier = [packed.entry_global]
        while frontier:
            nxt = []
            for node in frontier:
                for nb in packed.adj0[node]:
                    if nb != PAD and nb not in seen:
                        seen.add(int(nb))
                        nxt.append(int(nb))
            frontier = nxt
        assert len(seen) > 0.98 * packed.n

    def test_level_distribution_geometric(self, packed):
        share_upper = (packed.levels >= 1).mean()
        assert 0.02 < share_upper < 0.25   # ~1/M ± slack


class TestSearch:
    def test_recall_faithful_builder(self, packed, corpus, queries):
        g, max_level, metric = to_device(packed)
        qn = preprocess_vectors(queries, "cosine")
        _, ids = search(g, jnp.asarray(qn), k=10, ef=64,
                        max_level=max_level, metric=metric)
        gt = exact_knn(queries, corpus, 10, metric="cosine")
        assert recall_at_k(np.asarray(ids), gt) > 0.9

    def test_recall_bulk_builder(self, packed_bulk, corpus, queries):
        g, max_level, metric = to_device(packed_bulk)
        qn = preprocess_vectors(queries, "cosine")
        _, ids = search(g, jnp.asarray(qn), k=10, ef=64,
                        max_level=max_level, metric=metric)
        gt = exact_knn(queries, corpus, 10, metric="cosine")
        assert recall_at_k(np.asarray(ids), gt) > 0.9

    def test_ef_improves_recall(self, packed, corpus, queries):
        g, max_level, metric = to_device(packed)
        qn = preprocess_vectors(queries, "cosine")
        gt = exact_knn(queries, corpus, 10, metric="cosine")

        def r(ef):
            _, ids = search(g, jnp.asarray(qn), k=10, ef=ef,
                            max_level=max_level, metric=metric)
            return recall_at_k(np.asarray(ids), gt)

        assert r(96) >= r(12) - 0.02

    def test_jax_matches_numpy_reference(self, packed, queries):
        g, max_level, metric = to_device(packed)
        qn = preprocess_vectors(queries[:10], "cosine")
        _, ids_jax = search(g, jnp.asarray(qn), k=10, ef=48,
                            max_level=max_level, metric=metric)
        _, ids_np = search_numpy_reference(packed, queries[:10], 10, 48)
        overlap = np.mean([len(set(a.tolist()) & set(b.tolist())) / 10
                           for a, b in zip(np.asarray(ids_jax), ids_np)])
        assert overlap > 0.95, overlap

    def test_l2_metric_build_and_search(self, corpus, queries):
        packed = build(corpus[:600],
                       HNSWConfig(M=8, ef_construction=60, metric="l2"))
        g, max_level, metric = to_device(packed)
        _, ids = search(g, jnp.asarray(queries), k=5, ef=48,
                        max_level=max_level, metric=metric)
        gt = exact_knn(queries, corpus[:600], 5, metric="l2")
        assert recall_at_k(np.asarray(ids), gt) > 0.85

    def test_k_greater_than_ef_rejected(self, packed, queries):
        g, max_level, metric = to_device(packed)
        with pytest.raises(ValueError):
            search(g, jnp.asarray(queries), k=20, ef=10,
                   max_level=max_level, metric=metric)

    def test_state_dict_roundtrip(self, packed, queries):
        from repro.core.hnsw_build import PackedHNSW
        state = packed.state_dict()
        packed2 = PackedHNSW.from_state_dict(state, packed.config)
        g1, ml1, m1 = to_device(packed)
        g2, ml2, m2 = to_device(packed2)
        qn = preprocess_vectors(queries[:5], "cosine")
        _, i1 = search(g1, jnp.asarray(qn), k=5, ef=32, max_level=ml1,
                       metric=m1)
        _, i2 = search(g2, jnp.asarray(qn), k=5, ef=32, max_level=ml2,
                       metric=m2)
        assert (np.asarray(i1) == np.asarray(i2)).all()
