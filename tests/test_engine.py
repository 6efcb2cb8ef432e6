"""QuantixarEngine: the composition matrix, MEVS, rescore, persistence."""

import numpy as np
import pytest

from repro.core import (And, EngineConfig, Not, Or, Predicate,
                        QuantixarEngine, exact_knn)
from repro.core.bq import BQConfig
from repro.core.hnsw_build import HNSWConfig
from repro.core.pq import PQConfig
from repro.data.synthetic import gaussian_mixture

N, DIM = 1000, 32


@pytest.fixture(scope="module")
def corpus():
    return gaussian_mixture(N, DIM, n_clusters=10, scale=0.2, seed=0)


@pytest.fixture(scope="module")
def queries():
    return gaussian_mixture(16, DIM, n_clusters=10, scale=0.2, seed=3)


@pytest.fixture(scope="module")
def meta():
    return [{"cat": int(i % 5), "score": float(i) / N} for i in range(N)]


def _recall(ids, gt):
    return np.mean([len(set(a.tolist()) & set(b.tolist())) / gt.shape[1]
                    for a, b in zip(ids, gt)])


def _engine(corpus, meta, **kw):
    kw.setdefault("hnsw", HNSWConfig(M=12, ef_construction=60))
    kw.setdefault("pq", PQConfig(m=8, k=32, iters=8))
    kw.setdefault("bq", BQConfig(bits=256))
    kw.setdefault("builder", "bulk")
    eng = QuantixarEngine(EngineConfig(dim=DIM, **kw))
    eng.add(corpus, meta)
    eng.build()
    return eng


@pytest.mark.parametrize("index", ["flat", "hnsw", "ivf"])
@pytest.mark.parametrize("quant", ["none", "pq", "bq"])
def test_composition_matrix(corpus, queries, meta, index, quant):
    """Every index × quantization combination reaches sane recall."""
    eng = _engine(corpus, meta, index=index, quantization=quant)
    _, ids = eng.search(queries, 10)
    gt = exact_knn(queries, corpus, 10, metric="cosine")
    floor = 1.0 if (index, quant) == ("flat", "none") else \
        0.6 if index == "ivf" else 0.7
    r = _recall(ids, gt)
    assert r >= floor - 1e-9, (index, quant, r)


class TestMEVS:
    def test_equality_filter(self, corpus, queries, meta):
        eng = _engine(corpus, meta, index="hnsw")
        _, ids = eng.search(queries, 5, flt=Predicate("cat", "eq", 2))
        valid = ids[ids >= 0]
        assert len(valid) and all(meta[i]["cat"] == 2 for i in valid)

    def test_filter_then_search_is_exact_at_low_selectivity(
            self, corpus, queries, meta):
        """The paper's MEVS semantics: filter first, then exact search."""
        eng = _engine(corpus, meta, index="hnsw")
        flt = And([Predicate("cat", "eq", 1),
                   Predicate("score", "lt", 0.2)])   # ~4% selectivity
        d, ids = eng.search(queries, 5, flt=flt)
        mask = eng.metadata.evaluate(flt)
        allowed = np.where(mask)[0]
        sub = corpus[allowed]
        gt_local = exact_knn(queries, sub, 5, metric="cosine")
        gt = allowed[gt_local]
        assert _recall(ids, gt) > 0.99

    def test_boolean_operators(self, corpus, meta):
        eng = _engine(corpus, meta, index="flat")
        m_or = eng.metadata.evaluate(Or([Predicate("cat", "eq", 0),
                                         Predicate("cat", "eq", 1)]))
        m_not = eng.metadata.evaluate(Not(Predicate("cat", "eq", 0)))
        assert m_or.sum() == sum(1 for r in meta if r["cat"] in (0, 1))
        assert m_not.sum() == sum(1 for r in meta if r["cat"] != 0)

    def test_in_and_range_ops(self, corpus, meta):
        eng = _engine(corpus, meta, index="flat")
        m = eng.metadata.evaluate(Predicate("cat", "in", [2, 3]))
        assert m.sum() == sum(1 for r in meta if r["cat"] in (2, 3))
        m2 = eng.metadata.evaluate(Predicate("score", "ge", 0.5))
        assert m2.sum() == sum(1 for r in meta if r["score"] >= 0.5)


class TestRescore:
    def test_rescore_improves_bq_recall(self, corpus, queries, meta):
        gt = exact_knn(queries, corpus, 10, metric="cosine")
        base_cfg = dict(index="flat", quantization="bq",
                        bq=BQConfig(bits=64))
        eng_no = _engine(corpus, meta, rescore=False, **base_cfg)
        eng_yes = _engine(corpus, meta, rescore=True, **base_cfg)
        _, ids_no = eng_no.search(queries, 10)
        _, ids_yes = eng_yes.search(queries, 10)
        assert _recall(ids_yes, gt) >= _recall(ids_no, gt)


class TestPersistence:
    def test_state_roundtrip_identical_results(self, corpus, queries, meta):
        eng = _engine(corpus, meta, index="hnsw", quantization="pq")
        d1, i1 = eng.search(queries, 10)
        eng2 = QuantixarEngine.from_state_dict(eng.config, eng.state_dict())
        d2, i2 = eng2.search(queries, 10)
        assert (i1 == i2).all()
        np.testing.assert_allclose(d1, d2, rtol=1e-5, atol=1e-5)

    def test_metadata_survives_roundtrip(self, corpus, queries, meta):
        eng = _engine(corpus, meta, index="flat")
        eng2 = QuantixarEngine.from_state_dict(eng.config, eng.state_dict())
        _, ids = eng2.search(queries, 5, flt=Predicate("cat", "eq", 4))
        valid = ids[ids >= 0]
        assert len(valid) and all(meta[i]["cat"] == 4 for i in valid)


class TestValidation:
    def test_dim_mismatch_rejected(self, corpus):
        eng = QuantixarEngine(EngineConfig(dim=16))
        with pytest.raises(ValueError):
            eng.add(corpus)   # 32-dim into 16-dim engine

    def test_empty_build_rejected(self):
        eng = QuantixarEngine(EngineConfig(dim=8))
        with pytest.raises(RuntimeError):
            eng.build()

    def test_bad_config_rejected(self):
        with pytest.raises(ValueError):
            EngineConfig(dim=8, index="lsh-forest")
        with pytest.raises(ValueError):
            EngineConfig(dim=8, quantization="int4")

    def test_stats(self, corpus, meta):
        eng = _engine(corpus, meta, index="hnsw", quantization="pq")
        s = eng.stats()
        assert s["n"] == N and s["compression"] == 16.0
        assert s["build_seconds"] > 0


class TestIVF:
    """Beyond-paper IVF index (+ IVF-PQ composition)."""

    def test_nprobe_recall_knob(self, corpus, queries):
        from repro.core import IVFConfig
        gt = exact_knn(queries, corpus, 10, metric="cosine")

        def recall_at(nprobe):
            eng = QuantixarEngine(EngineConfig(
                dim=DIM, index="ivf",
                ivf=IVFConfig(nlist=32, nprobe=nprobe)))
            eng.add(corpus)
            eng.build()
            _, ids = eng.search(queries, 10)
            return _recall(ids, gt)

        low, high = recall_at(2), recall_at(16)
        assert high > low and high > 0.9, (low, high)

    def test_ivf_pq_composition(self, corpus, queries):
        eng = QuantixarEngine(EngineConfig(dim=DIM, index="ivf",
                                           quantization="pq"))
        eng.add(corpus)
        eng.build()
        gt = exact_knn(queries, corpus, 10, metric="cosine")
        _, ids = eng.search(queries, 10)
        assert _recall(ids, gt) > 0.5

    def test_ivf_mevs_filter(self, corpus, queries, meta):
        eng = QuantixarEngine(EngineConfig(dim=DIM, index="ivf"))
        eng.add(corpus, meta)
        eng.build()
        _, ids = eng.search(queries, 5, flt=Predicate("cat", "eq", 1))
        valid = ids[ids >= 0]
        assert len(valid) and all(meta[i]["cat"] == 1 for i in valid)

    def test_ivf_lists_cover_corpus(self, corpus):
        from repro.core import IVFConfig
        from repro.core.ivf import IVFIndex, PAD
        import jax.numpy as jnp
        ivf = IVFIndex(IVFConfig(nlist=16))
        ivf.train(jnp.asarray(corpus))
        ivf.build_lists(jnp.asarray(corpus))
        lists = np.asarray(ivf.lists)
        members = lists[lists != PAD]
        assert len(members) == len(corpus)            # every row assigned
        assert len(set(members.tolist())) == len(corpus)   # exactly once

    def test_ivf_persistence(self, corpus, queries):
        eng = QuantixarEngine(EngineConfig(dim=DIM, index="ivf"))
        eng.add(corpus)
        eng.build()
        d1, i1 = eng.search(queries, 10)
        eng2 = QuantixarEngine.from_state_dict(eng.config, eng.state_dict())
        d2, i2 = eng2.search(queries, 10)
        assert (i1 == i2).all()


# kwargs of `_engine`, the filter of every search, and whether an add()
# grows the store the pass reads (IVF lists cover sealed rows only: its
# new rows ride the delta segment's own device matrix)
RESIDENT = {
    "flat": (dict(index="flat"), None, True),
    "flat-pq": (dict(index="flat", quantization="pq"), None, True),
    "flat-bq": (dict(index="flat", quantization="bq"), None, True),
    "ivf": (dict(index="ivf"), None, False),
    "hnsw-filtered": (dict(index="hnsw"),
                      And([Predicate("cat", "eq", 1),
                           Predicate("score", "lt", 0.2)]), True),
}
NEW_ROWS = 8


def _store(eng):
    """The whole-corpus store whose device copy the engine's pass reads."""
    if eng.config.index == "ivf":
        return eng._ivf_effective
    if eng.config.quantization != "none":
        return eng._code_chunks
    return eng._vectors


def _resident(eng):
    return tuple(eng.stats()[f"resident_{k}"]
                 for k in ("uploads", "appends", "hits"))


class TestResidentCopy:
    """Whole-corpus stores stay on the device between passes."""

    @pytest.mark.parametrize("case", RESIDENT)
    def test_second_search_uploads_only_queries(self, corpus, queries,
                                                meta, case):
        kw, flt, _ = RESIDENT[case]
        eng = _engine(corpus, meta, **kw)
        h0 = eng.h2d_bytes
        d1, i1 = eng.search(queries, 10, flt=flt, rescore=False)
        first = eng.h2d_bytes - h0
        d2, i2 = eng.search(queries, 10, flt=flt, rescore=False)
        second = eng.h2d_bytes - h0 - first
        assert second == queries.nbytes + (0 if flt is None else N)
        assert first == second + _store(eng).view().nbytes
        assert _resident(eng) == (1, 0, 1)
        assert np.array_equal(d1, d2) and np.array_equal(i1, i2)

    @pytest.mark.parametrize("case", RESIDENT)
    def test_added_rows_are_found_and_uploaded_alone(self, corpus, queries,
                                                     meta, case):
        kw, flt, grows = RESIDENT[case]
        eng = _engine(corpus, meta, **kw)
        eng.search(queries, 10, flt=flt)
        held = _store(eng).view().nbytes
        new = gaussian_mixture(NEW_ROWS, DIM, n_clusters=4, scale=0.2,
                               seed=7)
        eng.add(new, [{"cat": 1, "score": 0.0}] * NEW_ROWS)
        h0 = eng.h2d_bytes
        d, ids = eng.search(new, 10, flt=flt)
        after_add = eng.h2d_bytes - h0
        eng.search(new, 10, flt=flt)
        steady = eng.h2d_bytes - h0 - after_add
        grown = _store(eng).view().nbytes - held
        assert (grown > 0) == grows
        delta = 0 if grows else eng._delta_cache[2].nbytes
        assert after_add - steady == grown + delta
        assert _resident(eng) == (1, int(grows), 2 - int(grows))
        assert np.array_equal(ids[:, 0], np.arange(N, N + NEW_ROWS))
        # an engine restored from the same state holds no copy yet
        fresh = QuantixarEngine.from_state_dict(eng.config, eng.state_dict())
        d2, ids2 = fresh.search(new, 10, flt=flt)
        assert np.array_equal(d, d2) and np.array_equal(ids, ids2)


def _collection(kw):
    from repro.api import Database, VectorField
    return Database().create_collection(
        name="c", vector=VectorField(dim=DIM, pq=PQConfig(m=8, k=32, iters=8),
                                     **kw))


def _rebuilt(op, kw, corpus, meta, queries):
    """An engine taken through ``op`` after searches that made and
    extended the device copies of its stores."""
    new = gaussian_mixture(NEW_ROWS, DIM, n_clusters=4, scale=0.2, seed=7)
    if op == "compact":
        col = _collection(kw)
        ids = [str(i) for i in range(N)]
        col.upsert(ids, corpus)
        col.seal()
        col.search(queries, 10)
        col.upsert([f"n{i}" for i in range(NEW_ROWS)], new)
        col.search(queries, 10)
        col.delete(ids[::7])
        col.compact()                  # a new engine over the live rows
        return col._engine
    eng = _engine(corpus, meta, **kw)
    eng.search(queries, 10)
    eng.add(new, [None] * NEW_ROWS)
    eng.search(queries, 10)
    if op == "build":
        eng.build()                    # retrains codes and index
    elif op == "seal":
        eng.seal()                     # rebuilds the index only
    return eng


@pytest.mark.parametrize("case", ["flat", "flat-pq", "ivf-pq"])
@pytest.mark.parametrize("op", ["build", "seal", "compact", "state_dict"])
def test_rebuilt_engine_answers_like_a_fresh_one(corpus, queries, meta,
                                                 case, op):
    """A copy made before a rebuild, a compaction or a save never answers
    for the stores that replaced it: the engine answers exactly as one
    restored from its state, which holds no copy."""
    index, _, quant = case.partition("-")
    eng = _rebuilt(op, dict(index=index, quantization=quant or "none"),
                   corpus, meta, queries)
    fresh = QuantixarEngine.from_state_dict(eng.config, eng.state_dict())
    d1, i1 = eng.search(queries, 10)
    d2, i2 = fresh.search(queries, 10)
    assert np.array_equal(d1, d2) and np.array_equal(i1, i2)
    assert fresh.resident_uploads == 1 and fresh.resident_hits == 0
