"""Faults planted under the timed path, each where it would arise.

Each entry of ``FAULTS`` names the attribute it replaces and makes the
replacement from the original.  ``test_run.py`` plants those of
``ANY_CELL`` and ``TRAVERSAL`` and sees ``correct`` come out false;
``read_fault.py`` reads any of them on the chip, where the limits' upper
readings come from:

  altered_answer  each query's best row is replaced by the next row
  half_batch      half of each batch is left out: its second half gets the
                  answers of its first half
  one_trip        the HNSW traversal stops after one expansion trip: too
                  few rows, and poor recall
  worst_of_beam   the traversal hands on the k worst rows of its beam,
                  not the k best: valid rows, exact scores, poor recall
  early_exit      it stops after 12 trips (at 100K rows: a few answers
                  short of k rows, recall about 0.91)
  ef_to_k         its beam is cut from the query's ``ef`` to k: a loss of
                  recall too small for any limit to tell from sound runs,
                  left to the ``recall`` metric's bound

``plant(mp, name)`` sets one with a ``pytest.MonkeyPatch``.  The check's own
reference search runs outside the engine, so only the program's answers are
broken; warm-up searches go through the fault as well, so nothing compiles
inside the window.
"""

from __future__ import annotations

import functools

import numpy as np

from repro.core import engine


def altered_answer(orig):
    def search(self, queries, k, **kw):
        d, ids = orig(self, queries, k, **kw)
        ids = np.array(ids)
        ids[:, 0] = (ids[:, 0] + 1) % len(self)
        return d, ids
    return search


def half_batch(orig):
    def search(self, queries, k, **kw):
        queries = np.asarray(queries)
        half = max(1, len(queries) // 2)
        d, ids = orig(self, queries[:half], k, **kw)
        rep = np.arange(len(queries)) % half
        return np.asarray(d)[rep], np.asarray(ids)[rep]
    return search


def one_trip(orig):
    return functools.partial(orig, max_iters=1)


def early_exit(orig):
    return functools.partial(orig, max_iters=12)


def worst_of_beam(orig):
    # the beam comes best first and the engine keeps its first k columns
    def search(*args, **kw):
        d, ids = orig(*args, **kw)
        return d[:, ::-1], ids[:, ::-1]
    return search


def ef_to_k(orig):
    def hnsw_pass(self, queries, k, ef, mask, expansion_width=None):
        return orig(self, queries, k, k, mask, expansion_width)
    return hnsw_pass


FAULTS = {
    "altered_answer": (engine.QuantixarEngine, "search", altered_answer),
    "half_batch": (engine.QuantixarEngine, "search", half_batch),
    "one_trip": (engine, "hnsw_search", one_trip),
    "early_exit": (engine, "hnsw_search", early_exit),
    "worst_of_beam": (engine, "hnsw_search", worst_of_beam),
    "ef_to_k": (engine.QuantixarEngine, "_hnsw_pass", ef_to_k),
}
# every cell can have these; a traversal fault only a cell whose answers
# come from the traversal, which is one whose limits compare recall_short
ANY_CELL = ("altered_answer", "half_batch")
TRAVERSAL = ("one_trip", "worst_of_beam")


def plant(mp, name: str) -> None:
    target, attr, make = FAULTS[name]
    mp.setattr(target, attr, make(getattr(target, attr)))
