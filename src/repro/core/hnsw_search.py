"""Jittable HNSW search — the TPU-native adaptation (DESIGN.md §2).

The CPU algorithm's dynamic structures are re-expressed as fixed-shape tensor
ops so the whole search jits, vmaps over query batches, and shards:

  upper-layer descent          -> one exact scan of the layer-1 members
                                  (a batched matmul); the nearest is the
                                  layer-0 entry point
  candidate min-heap / results -> one fused (ef,) candidate buffer maintained
                                  by ``lax.top_k`` over (ef + B·M0) merged rows
  visited hash-set             -> packed bitmask, ``ceil(N/32)`` uint32 words,
                                  per-word OR-updated in a static B-step
                                  unrolled scatter sequence (each popped row's
                                  bits land before the next row's membership
                                  test, so neighbours shared across the block
                                  are visited exactly once)
  per-neighbour distance calls -> one fused (B·M0,) gather-distance block per
                                  iteration (kernels/beam_gather.py)

**Wide-beam traversal**: each layer-0 iteration pops the top-``B`` unexpanded
candidates (``expansion_width``, static), gathers their adjacency rows into
one (B·M0,) id block, evaluates every distance in a single fused contraction,
and merges into the ``ef`` buffer with one ``top_k``.  The while-loop trip
count — the sequential bottleneck, since vmapped queries step the loop until
the *slowest* query finishes — drops ~B×, while per-iteration arithmetic
becomes one big MXU-friendly block instead of B small ones.  ``B=1``
reproduces the classic single-pop traversal bit-for-bit.

Distance evaluation is pluggable per graph payload (``metric``):

  "l2" / "dot"  float traversal over ``g.vectors``        (beam_gather)
  "adc"         PQ code-domain: per-query LUT over (N, m) uint codes
                (beam_gather_adc) — ADC == squared-L2-to-reconstruction
  "hamming"     BQ code-domain: packed XOR+popcount over (N, W) uint32
                words (beam_gather_hamming) — monotone affine in -dot of
                the ±1 sign vectors

so quantized engines traverse in code domain; the entry scan over the
upper-layer members keeps using the float proxy vectors.  Every corpus-sized
array ships as a gather table (kernels/beam_gather.gather_table: rows
(N, 1, Wp), lanes zero-padded to 128) so the kernels can DMA single rows;
queries are zero-padded to the same width once, on entry.  The loop trip
count is bounded (``max_iters``), giving XLA a fully static program;
``with_iters`` returns the per-query trip counter for
benchmarks/observability.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..kernels.beam_gather import gather_table, pad_lanes
from .flat import flat_search
from .hnsw_build import PAD, PackedHNSW, make_dist_fn, preprocess_vectors

Array = jax.Array
INF = jnp.inf

DEFAULT_EXPANSION_WIDTH = 4
ENTRY_CHUNK = 8192   # upper members scored per step of the entry scan


class HNSWGraph(NamedTuple):
    """Device-resident packed graph (all jnp arrays; static meta travels
    separately as jit-static args)."""

    vectors: Array      # (N, 1, Dp) float32 gather table, metric-preprocessed
    adj0: Array         # (N, M0) int32, PAD = -1
    upper_ids: Array    # (U,) int32 upper-layer members (entry candidates)
    upper_vecs: Array   # (U, Dp) float32 their proxy rows (the entry scan)
    entry_global: Array  # () int32, the entry of a graph without layers
    # (N, 1, mp) int32 PQ codes / (N, 1, Wp) uint32 packed BQ words (tables)
    codes: Optional[Array] = None


def to_device(packed: PackedHNSW,
              codes: Optional[np.ndarray] = None) -> Tuple[HNSWGraph, int, str]:
    """Returns (graph arrays, static max_level, static metric).

    ``codes`` optionally ships the quantized corpus (PQ uint codes or packed
    BQ uint32 words) alongside the float proxy vectors, enabling the
    code-domain traversal modes ("adc"/"hamming") of :func:`search`.  Both
    ship as gather tables.
    """
    table = gather_table(np.asarray(packed.vectors, dtype=np.float32))
    upper_ids = jnp.asarray(packed.upper_ids)
    g = HNSWGraph(
        vectors=table,
        adj0=jnp.asarray(packed.adj0),
        upper_ids=upper_ids,
        upper_vecs=table[upper_ids, 0],
        entry_global=jnp.asarray(packed.entry_global, dtype=jnp.int32),
        codes=None if codes is None else gather_table(np.asarray(codes)),
    )
    metric = "l2" if packed.config.metric == "l2" else "dot"
    return g, int(packed.max_level), metric


def _entry_points(g: HNSWGraph, queries: Array, max_level: int,
                  metric: str) -> Array:
    """(Q,) layer-0 entry points: for each query, the upper-layer member
    nearest to it, found by one exact scan of the layer-1 members.

    It stands in for the greedy per-layer descent of the paper, so no
    upper-layer links are stored.  A descent over the bulk builder's
    member-kNN upper layers stalled far from the query (no nearer than a
    random entry), and the ef-bounded layer-0 beam then missed neighbours it
    finds from a near entry.  The scan is one (Q, D) x (D, U) matmul over
    ``g.upper_vecs``, streamed in U-chunks; its answer is at least as near as
    any descent's, which also ends on a layer-1 member.
    """
    u = g.upper_ids.shape[0]
    if max_level == 0 or u == 0:
        return jnp.broadcast_to(g.entry_global, (queries.shape[0],))
    _, best = flat_search(queries, g.upper_vecs, 1, metric=metric,
                          chunk=ENTRY_CHUNK if u > ENTRY_CHUNK else None)
    return g.upper_ids[best[:, 0]]


def _make_block_dist(g: HNSWGraph, q: Array, q_code: Optional[Array],
                     metric: str):
    """The per-query fused distance evaluator: (L,) safe ids -> (L,) f32."""
    if metric == "adc":
        return lambda ids: ops.beam_gather_adc(q_code, ids, g.codes)
    if metric == "hamming":
        return lambda ids: ops.beam_gather_hamming(
            q_code, ids, g.codes).astype(jnp.float32)
    return lambda ids: ops.beam_gather_distances(q, ids, g.vectors,
                                                 mode=metric)


def _beam_search_base(g: HNSWGraph, ep_global: Array, ef: int, width: int,
                      max_iters: int, n_words: int,
                      block_dist) -> Tuple[Array, Array, Array]:
    """Fixed-ef wide-beam search on layer 0.

    Returns (dists (ef,), ids (ef,), iterations ()).
    """
    m0 = g.adj0.shape[1]
    l = width * m0

    # init: buffer holds just the entry point
    cand_d = jnp.full((ef,), INF).at[0].set(block_dist(ep_global[None])[0])
    cand_id = jnp.full((ef,), -1, dtype=jnp.int32).at[0].set(ep_global)
    expanded = jnp.zeros((ef,), dtype=bool)
    visited = jnp.zeros((n_words,), dtype=jnp.uint32).at[ep_global // 32].set(
        jnp.uint32(1) << (ep_global % 32).astype(jnp.uint32))

    def cond(state):
        cand_d, _, expanded, _, it = state
        frontier = jnp.any(~expanded & jnp.isfinite(cand_d))
        return frontier & (it < max_iters)

    def body(state):
        cand_d, cand_id, expanded, visited, it = state
        # pop the top-B nearest unexpanded candidates in one shot
        masked = jnp.where(~expanded, cand_d, INF)
        neg_d, sel = jax.lax.top_k(-masked, width)
        pop_ok = jnp.isfinite(neg_d)
        # surplus sel slots (pop_ok False) are INF: either empty (-1 id,
        # marking them expanded is moot) or already-expanded (idempotent)
        expanded = expanded.at[sel].set(True)
        nodes = jnp.where(pop_ok, cand_id[sel], PAD)        # (B,)

        adj_rows = g.adj0[jnp.maximum(nodes, 0)]            # (B, M0)
        adj_rows = jnp.where(pop_ok[:, None], adj_rows, PAD)
        # per-word OR-reduction of the visited bits, unrolled over the B
        # popped rows (B is static and small): each row's bits land before
        # the next row's membership test, so a neighbour shared by several
        # popped candidates is fresh exactly once.  Within one row bits are
        # unique (adjacency rows are duplicate-free — graph invariant,
        # tested) and previously 0 by the fresh mask, so add == or.
        fresh_rows = []
        for b in range(width):                              # static unroll
            nbrs_b = adj_rows[b]
            valid_b = nbrs_b != PAD
            safe_b = jnp.maximum(nbrs_b, 0)
            word_b = safe_b // 32
            bit_b = (safe_b % 32).astype(jnp.uint32)
            seen_b = (visited[word_b] >> bit_b) & jnp.uint32(1)
            fresh_b = valid_b & (seen_b == 0)
            add_b = jnp.where(fresh_b, jnp.uint32(1) << bit_b, jnp.uint32(0))
            visited = visited.at[word_b].add(add_b)
            fresh_rows.append(fresh_b)
        nbrs = adj_rows.reshape(l)
        fresh = jnp.stack(fresh_rows).reshape(l)
        safe = jnp.maximum(nbrs, 0)

        d = jnp.where(fresh, block_dist(safe), INF)         # (B·M0,) fused
        new_id = jnp.where(fresh, nbrs, -1)

        merged_d = jnp.concatenate([cand_d, d])
        merged_id = jnp.concatenate([cand_id, new_id])
        merged_exp = jnp.concatenate([expanded, ~fresh])  # stale -> never expand

        neg_top, keep = jax.lax.top_k(-merged_d, ef)
        return (-neg_top, merged_id[keep], merged_exp[keep], visited, it + 1)

    state = (cand_d, cand_id, expanded, visited, jnp.array(0, jnp.int32))
    cand_d, cand_id, _, _, iters = jax.lax.while_loop(cond, body, state)
    return cand_d, cand_id, iters


@functools.partial(
    jax.jit,
    static_argnames=("k", "ef", "max_iters", "max_level", "metric",
                     "expansion_width", "with_iters"))
def search(g: HNSWGraph, queries: Array, *, k: int, ef: int,
           max_level: int, metric: str = "dot",
           expansion_width: int = DEFAULT_EXPANSION_WIDTH,
           max_iters: Optional[int] = None,
           q_codes: Optional[Array] = None,
           with_iters: bool = False):
    """Batched HNSW search.

    Args:
      g: device graph from :func:`to_device`.
      queries: (Q, D) — pre-normalize for cosine (to_device stores the corpus
        normalized; use metric="dot").  For code-domain metrics this is the
        float proxy used by the entry scan (PQ: the normalized
        query; BQ: the ±1 sign vector).
      k: neighbours to return (k <= ef).
      ef: beam width (result-buffer size).
      max_level: static top layer of the graph.
      metric: "dot" | "l2" (cosine == dot on normalized inputs), or the
        code-domain modes "adc" / "hamming" (require ``g.codes`` +
        ``q_codes``).
      expansion_width: candidates popped (and adjacency rows fused) per
        layer-0 iteration; 1 == classic single-pop traversal.
      max_iters: expansion-iteration budget; default 4*ef.
      q_codes: per-query code-domain payload — (Q, m, k) ADC LUTs for
        metric="adc", (Q, W) packed uint32 query codes for "hamming".
      with_iters: additionally return the (Q,) int32 layer-0 loop-trip
        counters (the benchmark/observability hook).

    Returns:
      (distances (Q, k) ascending raw scores, ids (Q, k) int32; -1 = unfilled)
      [, iterations (Q,) if with_iters].
    """
    if max_iters is None:
        max_iters = 4 * ef
    if k > ef:
        raise ValueError(f"k={k} > ef={ef}")
    if metric in ("adc", "hamming") and (g.codes is None or q_codes is None):
        raise ValueError(f"metric {metric!r} needs g.codes and q_codes")
    # a beam can't pop more candidates than the buffer holds (tiny corpora)
    width = max(1, min(int(expansion_width), ef))
    entry_metric = {"adc": "l2", "hamming": "dot"}.get(metric, metric)
    n = g.vectors.shape[0]
    n_words = (n + 31) // 32
    queries = pad_lanes(queries.astype(jnp.float32), g.vectors.shape[-1])

    eps = _entry_points(g, queries, max_level, entry_metric)

    def one(q, qc, ep):
        block_dist = _make_block_dist(g, q, qc, metric)
        d, ids, iters = _beam_search_base(g, ep, ef, width, max_iters,
                                          n_words, block_dist)
        return d[:k], ids[:k], iters

    if q_codes is None:
        d, ids, iters = jax.vmap(lambda q, ep: one(q, None, ep))(queries, eps)
    else:
        d, ids, iters = jax.vmap(one)(queries, q_codes, eps)
    return (d, ids, iters) if with_iters else (d, ids)


def search_numpy_reference(packed: PackedHNSW, queries: np.ndarray, k: int,
                           ef: int,
                           expansion_width: int = DEFAULT_EXPANSION_WIDTH,
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """Host oracle mirroring the fixed-shape device algorithm (test parity),
    width-aware: pops ``expansion_width`` candidates per iteration, expands
    their neighbour rows as one first-occurrence-deduplicated block, and
    merges with a single stable top-ef selection — the same visit order and
    tie-breaking as the device wide-beam loop."""
    metric = packed.config.metric
    vecs = packed.vectors
    dist = make_dist_fn(vecs, metric)
    q_all = preprocess_vectors(queries, metric)
    width = max(1, int(expansion_width))
    out_d = np.full((len(q_all), k), np.inf, dtype=np.float32)
    out_i = np.full((len(q_all), k), -1, dtype=np.int32)

    width = min(width, ef)                     # mirror the device clamp
    upper = packed.upper_ids.astype(np.int64)
    for qi, q in enumerate(q_all):
        # entry: the nearest upper-layer member (first among ties)
        ep = int(upper[np.argmin(dist(q, upper))]) \
            if packed.max_level > 0 and len(upper) else packed.entry_global
        # wide beam
        cand_d = np.full((ef,), np.inf, np.float32)
        cand_i = np.full((ef,), -1, np.int64)
        expanded = np.zeros((ef,), bool)
        cand_d[0] = dist(q, np.array([ep], np.int64))[0]
        cand_i[0] = ep
        visited = {ep}
        for _ in range(4 * ef):
            masked = np.where(~expanded, cand_d, np.inf)
            pops = [int(c) for c in np.argsort(masked, kind="stable")[:width]
                    if np.isfinite(masked[c])]
            if not pops:
                break
            block: list = []
            for c in pops:
                expanded[c] = True
                nbrs = packed.adj0[cand_i[c]]
                # sequential visited update == the device block's
                # first-occurrence dedup in flattened row-major order
                fresh = [int(e) for e in nbrs
                         if e != PAD and e not in visited]
                visited.update(fresh)
                block.extend(fresh)
            if not block:
                continue
            ds = dist(q, np.asarray(block, np.int64))
            md = np.concatenate([cand_d, ds])
            mi = np.concatenate([cand_i, block])
            me = np.concatenate([expanded, np.zeros(len(block), bool)])
            keep = np.argsort(md, kind="stable")[:ef]
            cand_d, cand_i, expanded = md[keep], mi[keep], me[keep]
        out_d[qi] = cand_d[:k]
        out_i[qi] = cand_i[:k]
    return out_d, out_i


def recall_at_k(found_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """Mean fraction of true k-NN recovered (ann-benchmarks style).

    Vectorized: true ids are unique per row, so counting, for each true id,
    whether it appears among the first k found ids equals the per-row set
    intersection size — no Python loop over Q."""
    found = np.asarray(found_ids)
    true = np.asarray(true_ids)
    k = true.shape[1]
    hits = (true[:, :, None] == found[:, None, :k]).any(axis=2).sum()
    return float(hits) / (true.shape[0] * k)
