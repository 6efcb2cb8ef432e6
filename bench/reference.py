"""The plain reference: exact top-k by cosine over the live rows.

Brute force in ``jax.numpy`` over the harness's own copy of the corpus, in
blocks of queries so that the (block, rows) similarity matrix fits beside
the corpus.  It imports nothing of the program under test.  Similarities of
the rows it picks are then recomputed in float64 on the host; the k-th of
them is what a served row is held against (a row that ties it within
float32 rounding is as good as any).

``precision`` is that of the similarity matmul: ``"highest"`` is float32 as
the configurations state it; ``"high"`` is the three-pass bfloat16 product
(``a_hi*b_hi + a_hi*b_lo + a_lo*b_hi``, float32 accumulation), the next
precision down (what the TPU runs for ``Precision.HIGH``).  It is spelled out rather than asked of the compiler so that
it means the same on every backend; it is the control that the comparison
in ``check.py`` has to refuse.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

QUERY_BLOCK = 128


@functools.lru_cache(maxsize=None)
def _block_topk_fn():
    import jax
    import jax.numpy as jnp

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))

    def sims(q, c, precision):
        if precision == "highest":
            return jnp.matmul(q, c.T, precision=jax.lax.Precision.HIGHEST)
        if precision != "high":
            raise ValueError(f"precision {precision!r}")
        bf = jnp.bfloat16

        def split(x):
            # hi: x with its low 16 bits cleared, a bfloat16 exactly; lo:
            # the rest, rounded to bfloat16.  Cleared by bits, not by a
            # float32 -> bfloat16 -> float32 round trip, which the
            # compiler may fold away (lo would then be 0)
            bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
            hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                              jnp.float32)
            return hi.astype(bf), (x - hi).astype(bf)

        (q_hi, q_lo), (c_hi, c_lo) = split(q), split(c)

        def mm(a, b):
            return jnp.matmul(a, b.T, preferred_element_type=jnp.float32)

        return mm(q_hi, c_hi) + (mm(q_hi, c_lo) + mm(q_lo, c_hi))

    @functools.partial(jax.jit, static_argnames=("k", "precision"))
    def block_topk(q, c_unit, allowed, *, k, precision):
        s = sims(unit(q), c_unit, precision)
        s = jnp.where(allowed[None, :], s, -jnp.inf)
        return jax.lax.top_k(s, k)

    @jax.jit
    def unit_rows(c):
        return unit(c)

    return unit_rows, block_topk


def exact_topk(corpus: np.ndarray, queries: np.ndarray, k: int,
               allowed: Optional[np.ndarray] = None,
               precision: str = "highest") -> Tuple[np.ndarray, np.ndarray]:
    """(ids (Q, k) int64, similarities (Q, k) float32) of the k most
    cosine-similar allowed rows per query, best first; id -1 (similarity
    -inf) where fewer than k rows are allowed."""
    import jax
    import jax.numpy as jnp

    unit_rows, block_topk = _block_topk_fn()
    c_unit = unit_rows(jnp.asarray(corpus))
    mask = jnp.asarray(np.ones(len(corpus), bool) if allowed is None
                       else allowed)
    ids, sims = [], []
    for lo in range(0, len(queries), QUERY_BLOCK):
        block = np.asarray(queries[lo:lo + QUERY_BLOCK], np.float32)
        pad = QUERY_BLOCK - len(block)
        if pad:                        # one compiled shape for every block
            block = np.concatenate([block, np.repeat(block[:1], pad, 0)])
        s, i = block_topk(jnp.asarray(block), c_unit, mask, k=k,
                          precision=precision)
        s, i = jax.device_get((s, i))
        s = np.asarray(s[:QUERY_BLOCK - pad], np.float32)
        i = np.asarray(i[:QUERY_BLOCK - pad], np.int64)
        ids.append(np.where(np.isfinite(s), i, -1))   # fewer allowed than k
        sims.append(s)
    del c_unit
    return np.concatenate(ids), np.concatenate(sims)


def unit64(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def pair_sims(corpus: np.ndarray, queries: np.ndarray, query_idx: np.ndarray,
              rows: np.ndarray) -> np.ndarray:
    """float64 cosine similarity of each (query_idx[i], rows[i]) pair."""
    return np.einsum("id,id->i", unit64(queries[query_idx]),
                     unit64(corpus[rows]))


def kth_sims(corpus: np.ndarray, queries: np.ndarray,
             top_ids: np.ndarray) -> np.ndarray:
    """(Q,) float64: the k-th best similarity per query, recomputed in float64
    over the rows the reference picked (ties at the k-th place are then one
    number, whatever float32 order the scan gave them)."""
    q, k = top_ids.shape
    s = pair_sims(corpus, queries, np.repeat(np.arange(q), k),
                  np.maximum(top_ids, 0).reshape(-1)).reshape(q, k)
    return np.where(top_ids >= 0, s, np.inf).min(axis=1)
