"""Wide-beam HNSW traversal: width parity, code-domain dispatch, regression.

Covers the PR-4 acceptance surface:
  * device vs numpy-reference parity across expansion_width ∈ {1, 2, 4}
    for all three quantization modes;
  * width=1 reproduces the seed single-pop traversal bit-for-bit (the seed
    loop is re-implemented verbatim below as the golden);
  * filtered (masked) search under wide beams;
  * dispatch-level proof that quantized traversal routes distances through
    the fused beam-gather kernel path (adc / hamming), not float32
    reconstruction gathers;
  * iteration-counter drop (the perf claim's mechanism) and the
    expansion_width knob across engine / Query / wire protocol.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HNSWConfig, build, exact_knn, recall_at_k
from repro.core import bq as bq_mod
from repro.core import pq as pq_mod
from repro.core.engine import EngineConfig, QuantixarEngine
from repro.core.hnsw_build import PAD, preprocess_vectors
import repro.core.hnsw_search as hs
from repro.core.hnsw_search import search, search_numpy_reference, to_device
from repro.data.synthetic import gaussian_mixture

N, DIM = 900, 24
WIDTHS = (1, 2, 4)


@pytest.fixture(scope="module")
def corpus():
    return gaussian_mixture(N, DIM, n_clusters=15, scale=0.25, seed=3)


@pytest.fixture(scope="module")
def queries():
    return gaussian_mixture(24, DIM, n_clusters=15, scale=0.25, seed=11)


@pytest.fixture(scope="module")
def packed(corpus):
    return build(corpus, HNSWConfig(M=10, ef_construction=64,
                                    metric="cosine", seed=0))


# ---------------------------------------------------------------------------
# Traversal-level: width parity, iteration counters, bit-for-bit regression
# ---------------------------------------------------------------------------

class TestWideBeamTraversal:
    @pytest.mark.parametrize("width", WIDTHS)
    def test_matches_numpy_reference(self, packed, queries, width):
        g, ml, metric = to_device(packed)
        qn = preprocess_vectors(queries, "cosine")
        _, ids = search(g, jnp.asarray(qn), k=10, ef=48, max_level=ml,
                        metric=metric, expansion_width=width)
        _, ids_np = search_numpy_reference(packed, queries, 10, 48,
                                           expansion_width=width)
        overlap = np.mean([len(set(a.tolist()) & set(b.tolist())) / 10
                           for a, b in zip(np.asarray(ids), ids_np)])
        assert overlap > 0.95, (width, overlap)

    def test_recall_stable_across_widths(self, packed, corpus, queries):
        g, ml, metric = to_device(packed)
        qn = preprocess_vectors(queries, "cosine")
        gt = exact_knn(queries, corpus, 10, metric="cosine")

        def rec(width):
            _, ids = search(g, jnp.asarray(qn), k=10, ef=64, max_level=ml,
                            metric=metric, expansion_width=width)
            return recall_at_k(np.asarray(ids), gt)

        base = rec(1)
        for w in WIDTHS[1:]:
            assert abs(rec(w) - base) <= 0.01, (w, rec(w), base)

    def test_iteration_counter_drops(self, packed, queries):
        g, ml, metric = to_device(packed)
        qn = jnp.asarray(preprocess_vectors(queries, "cosine"))

        def iters(width):
            _, _, it = search(g, qn, k=10, ef=64, max_level=ml,
                              metric=metric, expansion_width=width,
                              with_iters=True)
            return np.asarray(it)

        i1, i4 = iters(1), iters(4)
        assert i1.shape == (len(qn),)
        assert i4.mean() * 2 <= i1.mean(), (i1.mean(), i4.mean())

    def test_width1_bitforbit_matches_seed_loop(self, packed, queries):
        """The seed's single-pop loop, re-implemented verbatim, must equal
        width=1 of the wide-beam loop — distances and ids exactly.  The seed
        loop reads the proxy rows out of the shipped gather table and takes
        queries zero-padded to its lane width, as search() does."""
        g, ml, metric = to_device(packed)
        ef, k = 48, 10
        max_iters = 4 * ef
        n = g.vectors.shape[0]
        n_words = (n + 31) // 32
        vecs = g.vectors[:, 0, :]              # (N, Dp)

        def dist_rows(q, rows):                # the seed's row distances
            if metric == "l2":
                d = rows - q[None, :]
                return jnp.sum(d * d, axis=-1)
            return -jnp.dot(rows, q, precision=jax.lax.Precision.HIGHEST)

        def seed_beam(q, ep_global):           # seed _beam_search_base
            cand_d = jnp.full((ef,), jnp.inf).at[0].set(
                dist_rows(q, vecs[ep_global][None, :])[0])
            cand_id = jnp.full((ef,), -1, dtype=jnp.int32).at[0].set(
                ep_global)
            expanded = jnp.zeros((ef,), dtype=bool)
            visited = jnp.zeros((n_words,), dtype=jnp.uint32).at[
                ep_global // 32].set(
                jnp.uint32(1) << (ep_global % 32).astype(jnp.uint32))

            def cond(state):
                cand_d, _, expanded, _, it = state
                frontier = jnp.any(~expanded & jnp.isfinite(cand_d))
                return frontier & (it < max_iters)

            def body(state):
                cand_d, cand_id, expanded, visited, it = state
                masked = jnp.where(~expanded, cand_d, jnp.inf)
                c = jnp.argmin(masked)
                expanded = expanded.at[c].set(True)
                node = cand_id[c]
                nbrs = g.adj0[node]
                valid = nbrs != PAD
                safe = jnp.maximum(nbrs, 0)
                word = safe // 32
                bit = (safe % 32).astype(jnp.uint32)
                seen = (visited[word] >> bit) & jnp.uint32(1)
                fresh = valid & (seen == 0)
                add_val = jnp.where(fresh, jnp.uint32(1) << bit,
                                    jnp.uint32(0))
                visited = visited.at[word].add(add_val)
                rows = vecs[safe]
                d = jnp.where(fresh, dist_rows(q, rows), jnp.inf)
                new_id = jnp.where(fresh, nbrs, -1)
                merged_d = jnp.concatenate([cand_d, d])
                merged_id = jnp.concatenate([cand_id, new_id])
                merged_exp = jnp.concatenate([expanded, ~fresh])
                neg_top, sel = jax.lax.top_k(-merged_d, ef)
                return (-neg_top, merged_id[sel], merged_exp[sel], visited,
                        it + 1)

            state = (cand_d, cand_id, expanded, visited,
                     jnp.array(0, jnp.int32))
            cand_d, cand_id, _, _, _ = jax.lax.while_loop(cond, body, state)
            return cand_d, cand_id

        @jax.jit
        def seed_search(qs):                   # seed search(), ml/metric fixed
            def one(q, ep):
                d, ids = seed_beam(q, ep)
                return d[:k], ids[:k]

            # the layer-0 entry rule is shared; the loops under test differ
            return jax.vmap(one)(qs, hs._entry_points(g, qs, ml, metric))

        qn = jnp.asarray(preprocess_vectors(queries, "cosine"))
        d_seed, ids_seed = seed_search(
            jnp.pad(qn, ((0, 0), (0, vecs.shape[1] - qn.shape[1]))))
        d_new, ids_new = search(g, qn, k=k, ef=ef, max_level=ml,
                                metric=metric, expansion_width=1)
        assert (np.asarray(ids_seed) == np.asarray(ids_new)).all()
        assert np.array_equal(np.asarray(d_seed), np.asarray(d_new))

    def test_adc_hamming_require_codes(self, packed, queries):
        g, ml, _ = to_device(packed)           # no codes shipped
        qn = jnp.asarray(preprocess_vectors(queries, "cosine"))
        with pytest.raises(ValueError, match="needs g.codes"):
            search(g, qn, k=5, ef=16, max_level=ml, metric="adc")


# ---------------------------------------------------------------------------
# Quantized traversal: device vs reference per width, code-domain dispatch
# ---------------------------------------------------------------------------

def _quantized_engine(corpus, quant):
    eng = QuantixarEngine(EngineConfig(
        dim=corpus.shape[1], quantization=quant, builder="bulk",
        pq=pq_mod.PQConfig(m=8, k=16, iters=5),
        bq=bq_mod.BQConfig(bits=64)))
    eng.add(corpus)
    eng.build()
    return eng


def _proxy_queries(eng, queries):
    """The float-proxy queries + code payload engine._hnsw_pass derives."""
    cfg = eng.config
    if cfg.quantization == "pq":
        q = preprocess_vectors(queries, "cosine")
        lut = pq_mod.build_adc_lut(jnp.asarray(queries), eng._pq.codebooks,
                                   normalize_inputs=True)
        return q, lut, "adc"
    if cfg.quantization == "bq":
        packed_q = eng._bq.encode(jnp.asarray(queries))
        signs = np.asarray(bq_mod.unpack_bits(packed_q, cfg.bq.bits),
                           dtype=np.float32) * 2.0 - 1.0
        return signs, packed_q, "hamming"
    return preprocess_vectors(queries, "cosine"), None, None


class TestQuantizedWideBeam:
    @pytest.mark.parametrize("quant", ["none", "pq", "bq"])
    @pytest.mark.parametrize("width", WIDTHS)
    def test_device_matches_reference(self, corpus, queries, quant, width):
        """Code-domain device traversal == float-proxy numpy oracle: the ADC
        identity (PQ) and the Hamming/-dot affine map (BQ) make the orderings
        equal, so per-width id overlap with the width-aware oracle is high
        for every quantization mode."""
        eng = _quantized_engine(corpus, quant)
        g, ml, metric = eng._device_graph
        q, q_codes, mode = _proxy_queries(eng, queries)
        _, ids = search(g, jnp.asarray(q), k=10, ef=48, max_level=ml,
                        metric=mode or metric, expansion_width=width,
                        q_codes=q_codes)
        _, ids_np = search_numpy_reference(eng._packed, q, 10, 48,
                                           expansion_width=width)
        overlap = np.mean([len(set(a.tolist()) & set(b.tolist())) / 10
                           for a, b in zip(np.asarray(ids), ids_np)])
        assert overlap > 0.9, (quant, width, overlap)

    @pytest.mark.parametrize("quant", ["pq", "bq"])
    def test_engine_recall_across_widths(self, corpus, queries, quant):
        eng = _quantized_engine(corpus, quant)
        gt = exact_knn(queries, corpus, 10, metric="cosine")
        recalls = {}
        for w in WIDTHS:
            _, ids = eng.search(queries, 10, expansion_width=w)
            recalls[w] = recall_at_k(ids, gt)
        assert recalls[4] >= recalls[1] - 0.01, recalls

    @pytest.mark.parametrize("quant,op_name", [
        ("pq", "beam_gather_adc"), ("bq", "beam_gather_hamming")])
    def test_dispatches_through_fused_kernel_path(self, corpus, queries,
                                                  quant, op_name,
                                                  monkeypatch):
        """Quantized traversal must route every layer-0 distance block
        through the fused gather kernel dispatcher (ref oracle on CPU,
        Pallas on TPU) — never the float path."""
        calls = {"fused": 0, "float": 0}
        fused = getattr(hs.ops, op_name)
        float_path = hs.ops.beam_gather_distances

        def spy_fused(*a, **kw):
            calls["fused"] += 1
            return fused(*a, **kw)

        def spy_float(*a, **kw):
            calls["float"] += 1
            return float_path(*a, **kw)

        monkeypatch.setattr(hs.ops, op_name, spy_fused)
        monkeypatch.setattr(hs.ops, "beam_gather_distances", spy_float)
        search.clear_cache()                   # force a fresh trace
        eng = _quantized_engine(corpus, quant)
        eng.search(queries, 5, rescore=False)
        assert calls["fused"] > 0, calls       # counted at trace time
        assert calls["float"] == 0, calls

    def test_float_engine_dispatches_float_path(self, corpus, queries,
                                                monkeypatch):
        calls = {"float": 0}
        float_path = hs.ops.beam_gather_distances

        def spy(*a, **kw):
            calls["float"] += 1
            return float_path(*a, **kw)

        monkeypatch.setattr(hs.ops, "beam_gather_distances", spy)
        search.clear_cache()
        eng = _quantized_engine(corpus, "none")
        eng.search(queries, 5)
        assert calls["float"] > 0

    def test_graph_ships_codes(self, corpus):
        """Codes ship as a gather table: (N, 1, 128k) with 8-bit PQ codes
        widened to int32, lanes past the code width zero, rows unchanged."""
        for quant, dtype in (("pq", np.int32), ("bq", np.uint32)):
            eng = _quantized_engine(corpus, quant)
            g = eng._device_graph[0]
            assert g.codes is not None
            assert g.codes.dtype == dtype
            n, one, wp = g.codes.shape
            assert (n, one, wp % 128) == (eng._packed.n, 1, 0)
            codes = np.asarray(eng._codes)
            table = np.asarray(g.codes)[:, 0, :]
            w = codes.shape[1]
            assert (table[:, :w] == codes[:n]).all()
            assert (table[:, w:] == 0).all()


# ---------------------------------------------------------------------------
# Filtered (masked) search under wide beams
# ---------------------------------------------------------------------------

class TestFilteredWideBeam:
    @pytest.mark.parametrize("width", [1, 4])
    def test_masked_search_respects_mask(self, corpus, queries, width):
        eng = QuantixarEngine(EngineConfig(dim=DIM, builder="bulk"))
        eng.add(corpus)
        eng.build()
        rng = np.random.RandomState(0)
        mask = rng.rand(N) < 0.4               # above the flat-route cutoff
        d, ids = eng.search(queries, 10, mask=mask, expansion_width=width)
        live = ids[ids >= 0]
        assert mask[live].all()                # nothing masked leaks out
        # recall vs the exact masked ground truth
        allowed = np.where(mask)[0]
        gt_local = exact_knn(queries, corpus[allowed], 10, metric="cosine")
        gt = allowed[gt_local]
        assert recall_at_k(ids, gt) > 0.85

    def test_width_override_wires_through_engine(self, corpus, queries):
        eng = QuantixarEngine(EngineConfig(dim=DIM, builder="bulk"))
        assert eng.effective_expansion_width() == 4          # hnsw default
        assert eng.effective_expansion_width(2) == 2         # per-query
        eng.config.expansion_width = 3                       # engine-level
        assert eng.effective_expansion_width() == 3
        with pytest.raises(ValueError, match="expansion_width"):
            eng.effective_expansion_width(0)


# ---------------------------------------------------------------------------
# Config / wire-protocol threading
# ---------------------------------------------------------------------------

class TestWidthThreading:
    def test_hnsw_config_validates(self):
        with pytest.raises(ValueError, match="expansion_width"):
            HNSWConfig(expansion_width=0)

    def test_schema_roundtrip(self):
        from repro.api.schema import CollectionSchema, VectorField
        schema = CollectionSchema(
            name="c", vector=VectorField(
                dim=8, hnsw=HNSWConfig(expansion_width=2)))
        restored = CollectionSchema.from_dict(schema.to_dict())
        assert restored.vector.hnsw.expansion_width == 2

    def test_search_request_roundtrip(self):
        from repro.api import requests as rq
        req = rq.Search(collection="c", vector=[0.0, 1.0], k=3,
                        expansion_width=2)
        decoded = rq.decode_request(req.to_dict())
        assert decoded.expansion_width == 2
        # absent on the wire -> None (schema default applies server-side)
        d = req.to_dict()
        del d["body"]["expansion_width"]
        assert rq.decode_request(d).expansion_width is None

    def test_query_builder_validates(self):
        from repro.api import CollectionSchema, Database, VectorField
        from repro.api.schema import SchemaError
        db = Database()
        col = db.create_collection(CollectionSchema(
            name="t", vector=VectorField(dim=4, builder="bulk")))
        col.upsert(["a", "b"], np.eye(4, dtype=np.float32)[:2])
        with pytest.raises(SchemaError, match="expansion_width"):
            col.query(np.ones(4)).expansion_width(0)
        hits = col.query(np.eye(4)[0]).top_k(1).expansion_width(2).run()
        assert hits[0].id == "a"
        db.close()
