"""The plain reference against a numpy brute force, ties included."""

import numpy as np
import pytest

from bench import check, reference


def brute(corpus, queries, k, allowed=None):
    c = corpus / np.linalg.norm(corpus, axis=1, keepdims=True)
    q = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    s = q.astype(np.float64) @ c.astype(np.float64).T
    if allowed is not None:
        s[:, ~allowed] = -np.inf
    return np.argsort(-s, axis=1, kind="stable")[:, :k], np.sort(s)[:, ::-1]


@pytest.fixture
def data():
    rng = np.random.default_rng(7)
    corpus = rng.standard_normal((3000, 96)).astype(np.float32)
    queries = rng.standard_normal((40, 96)).astype(np.float32)
    return corpus, queries


def test_matches_brute_force(data):
    corpus, queries = data
    ids, sims = reference.exact_topk(corpus, queries, 10)
    want, _ = brute(corpus, queries, 10)
    assert (np.sort(ids, 1) == np.sort(want, 1)).mean() > 0.999
    kth = reference.kth_sims(corpus, queries, ids)
    _, s = brute(corpus, queries, 10)
    np.testing.assert_allclose(kth, s[:, 9], atol=1e-6)


def test_filter_and_fewer_allowed_than_k(data):
    corpus, queries = data
    allowed = np.zeros(len(corpus), bool)
    allowed[-5:] = True
    ids, sims = reference.exact_topk(corpus, queries, 10, allowed)
    assert (ids[:, 5:] == -1).all() and np.isinf(sims[:, 5:]).all()
    assert set(ids[:, :5].ravel()) <= set(range(len(corpus) - 5,
                                              len(corpus)))


def test_ties_at_the_kth_place_are_not_misses(data):
    """A duplicate of the k-th row may stand in for it: the comparison holds
    a served row against the k-th similarity, not the k-th id."""
    corpus, queries = data
    ids, _ = reference.exact_topk(corpus, queries[:1], 10)
    twin = np.concatenate([corpus, corpus[ids[0, 9]][None]])
    top, _ = reference.exact_topk(twin, queries[:1], 10)
    kth = reference.kth_sims(twin, queries[:1], top)
    served = top.copy()
    # swap the k-th row for its duplicate, whichever the scan put there
    other = {ids[0, 9], len(corpus)} - {served[0, 9]}
    served[0, 9] = other.pop()
    sims = reference.pair_sims(twin, queries[:1], np.zeros(10, int),
                               served[0])
    answers = {"ok": np.ones(1, bool), "query": np.zeros(1, int),
               "ids": served, "scores": (1.0 - sims)[None]}
    got = check.compare(answers, twin, queries[:1], top, kth, None, 10)
    assert got["rank_gap"] < 1e-9 and got["bad_rows"] == 0
    assert got["score_gap"] < 1e-9


def test_high_precision_control_is_an_approximation(data):
    corpus, queries = data
    ids, sims = reference.exact_topk(corpus, queries, 10, precision="high")
    ref_ids, ref_sims = reference.exact_topk(corpus, queries, 10)
    assert np.abs(sims - ref_sims).max() > 0
    with pytest.raises(ValueError):
        reference.exact_topk(corpus, queries, 10, precision="default")
