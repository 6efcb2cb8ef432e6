"""The comparison that decides ``correct``.

Every answer the window received is held against the plain reference
(``reference.py``) over the same rows and filter.  The numbers, all "lower
is better", each compared with the limit that ``bench/limits/<cell>.json``
gives it (a cell compares the numbers its limits file lists):

  unanswered   requests of the window that got no answer (an error or none)
  bad_rows     returned rows that cannot be right whatever the search:
               missing (fewer than k, or than the filter allows), unknown,
               outside the filter, or repeated within one answer
  score_gap    widest gap between a served score and the reference's float64
               cosine distance of that same row; the wire's score is
               ``1 - cos`` from the flat scan and ``-cos`` from the HNSW
               traversal, so each answer's constant offset (0 or -1) is
               taken from its own scores first
  rank_gap     widest gap by which a served row's float64 similarity lies
               below the reference's k-th best (exact answers: 0 up to f32
               rounding; an approximate index misses rows by design)
  exact_gap    the wider of the two: for a cell whose answers are exact,
               one number that a wrong row and a wrong score both fail
  recall_short the share of due rows that the answers missed, ``1 -
               recall``, where recall is the mean recall@k of the answers
               (strict id match; ``recall`` itself is an end-to-end
               metric): a traversal that stops early or walks the wrong
               lists returns valid rows with exact scores, and only this
               number fails it

A number with no limit in the cell's file is still printed.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from . import reference

OFFSETS = np.array([0.0, -1.0])        # score = (1 - cos) + offset


def compare(responses: Dict[str, np.ndarray], corpus: np.ndarray,
            queries: np.ndarray, top_ids: np.ndarray, kth: np.ndarray,
            allowed: Optional[np.ndarray], k: int) -> Dict[str, float]:
    """The numbers above for ``responses`` (arrays: ``ok`` (R,), ``query``
    (R,), ``ids`` (R, k) with -1 where no row came, ``scores`` (R, k))."""
    ok = responses["ok"].astype(bool)
    qidx = responses["query"][ok]
    ids = responses["ids"][ok]
    scores = responses["scores"][ok]
    n = len(corpus)
    out = {"unanswered": float(np.count_nonzero(~ok))}
    if len(ids) == 0:
        out.update(bad_rows=0.0, score_gap=0.0, rank_gap=0.0, exact_gap=0.0,
                   recall=0.0, recall_short=1.0)
        return out

    present = (ids >= 0) & (ids < n)
    want = np.count_nonzero(top_ids[qidx] >= 0, axis=1)   # rows due
    bad = ~present & (np.arange(k)[None, :] < want[:, None])
    if allowed is not None:
        bad |= present & ~allowed[np.clip(ids, 0, n - 1)]
    srt = np.sort(np.where(present, ids, -1 - np.arange(k)[None, :]), axis=1)
    repeated = np.zeros_like(bad)
    repeated[:, 1:] = srt[:, 1:] == srt[:, :-1]
    out["bad_rows"] = float(np.count_nonzero(bad) + np.count_nonzero(repeated))

    # float64 similarity of each distinct (query, row) pair served
    rows_q = np.repeat(qidx, k)[present.reshape(-1)]
    rows = ids[present]
    key, inverse = np.unique(rows_q * n + rows, return_inverse=True)
    sims = reference.pair_sims(corpus, queries, key // n, key % n)[inverse]
    sim = np.full(ids.shape, np.nan)
    sim[present] = sims

    dist = 1.0 - sim
    resid = scores - dist
    centre = np.nanmedian(np.where(present, resid, np.nan), axis=1)
    offset = OFFSETS[np.argmin(np.abs(centre[:, None] - OFFSETS[None, :]),
                               axis=1)]
    gap = np.abs(resid - offset[:, None])
    out["score_gap"] = float(np.max(np.where(present, gap, 0.0)))
    out["rank_gap"] = float(max(0.0, np.max(np.where(
        present, kth[qidx][:, None] - sim, -np.inf))))
    out["exact_gap"] = max(out["score_gap"], out["rank_gap"])

    found = 0
    for lo in range(0, len(ids), 1024):
        got, truth = ids[lo:lo + 1024], top_ids[qidx[lo:lo + 1024]]
        found += int(((got[:, :, None] == truth[:, None, :]).any(axis=2)
                      & (got >= 0)).sum())
    out["recall"] = found / max(1, int(want.sum()))
    out["recall_short"] = 1.0 - out["recall"]
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, Any]
          ) -> Dict[str, Dict[str, float]]:
    """{name: {"value", "limit"}} for every number with a limit."""
    return {name: {"value": numbers[name], "limit": float(spec["limit"])}
            for name, spec in limits["limits"].items()}


def passed(judged: Dict[str, Dict[str, float]]) -> bool:
    return all(v["value"] <= v["limit"] for v in judged.values())
