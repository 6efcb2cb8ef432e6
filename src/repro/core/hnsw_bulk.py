"""Device-parallel bulk HNSW construction.

The incremental builder (hnsw_build.py) inserts one node at a time — each
insert beam-searches the graph built so far, so construction is inherently
serial and dominates total indexing cost (~109 s for 6k vectors vs ~7 s of
search sweep in BENCH_hnsw.json).  This module rebuilds the same packed
structure with batched, device-friendly phases; every per-node Python loop
of the seed ``bulk_build`` is lifted to fixed-shape jitted array programs:

  * **Vectorized Alg-4 prune** (`_prune_batch`): SELECT-NEIGHBORS-HEURISTIC
    for a whole batch at once — candidate lists are distance-sorted, the
    candidate×candidate pair-distance matrix comes from the fused
    ``pair_gather`` kernel (kernels/bulk_prune.py), and a masked
    ``lax.scan`` walks the C candidate slots maintaining the selected set,
    exactly the "closer to q than to every selected neighbour" rule with
    keepPruned fill-up.
  * **Deterministic scatter/cap symmetrize** (`_merge_cap`): forward +
    reverse edges and the existing adjacency are merged as one edge list,
    deduplicated by (target, source), ranked per target by (distance, id)
    with composed stable sorts, and scattered back capped at M — the
    intra-batch conflict resolution pass, fully on device.
  * **Level-wise batched inserts** (`_bulk_level`): nodes are inserted in
    descending-level order; the first batch bootstraps the graph (and all
    upper-layer nodes) from exact kNN, each following batch runs vmapped
    wide-beam searches (hnsw_search.search — the fused ``beam_gather``
    kernels) over the *frozen prefix* graph to collect candidates, plus an
    intra-batch kNN block so batch-mates can link to each other.
  * **Two-phase coarse mode** (`_bulk_coarse`): for cold-start bulk loads
    the beam searches are replaced by k-means coarse clustering (the
    ``ivf.py``/``pq.py`` machinery) → intra-cluster exact kNN (each node
    sees the union of its two nearest clusters, so boundary nodes get
    cross-cluster candidates) → one global prune + symmetrize → boundary
    nodes (smallest assignment margin) re-linked through batched beam
    searches over the built graph.  Build cost scales ~O(n·k·d) instead of
    the O(n²) brute-force self-join.

Both modes share the level sampling (the layer>=1 members are the search's
entry candidates; no upper-layer links are built) and connectivity repair,
and produce a `PackedHNSW` interchangeable with the incremental builder's
output.  Mode "auto" picks coarse at
``coarse_threshold`` rows and level-wise below it; corpora too small for
fixed-shape batching fall back to the numpy reference ``bulk_build``.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..kernels.beam_gather import gather_table
from .flat import flat_search
from .hnsw_build import (PAD, HNSWConfig, PackedHNSW, ProgressFn, bulk_build,
                         knn_ids_dists, preprocess_vectors)
from .hnsw_search import HNSWGraph
from .hnsw_search import search as beam_search
from .pq import _fit_one_subspace

logger = logging.getLogger(__name__)

INF = np.float32(np.inf)

PRUNE_CHUNK = 512        # nodes pruned per jitted call (fixed shape)
MIN_DEVICE_N = 32        # below this the numpy reference builder is used
STITCH_EF = 64           # beam width cap for cross-cluster stitching
KMEANS_ITERS = 8
KNN_BLOCK = 1024         # query rows per device kNN call
KNN_BUCKET = 4096        # member sets pad to a multiple (bounds recompiles)


# ---------------------------------------------------------------------------
# vectorized Alg-4 SELECT-NEIGHBORS-HEURISTIC
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "mode", "keep_pruned"))
def _prune_batch(corpus: jax.Array, q_ids: jax.Array, cand_ids: jax.Array,
                 cand_d: jax.Array, *, m: int, mode: str,
                 keep_pruned: bool) -> Tuple[jax.Array, jax.Array]:
    """Batched diversification prune: (B, C) candidates -> (B, m) selected.

    ``corpus`` is the gather table (N, 1, Dp) of the preprocessed vectors.

    Candidate j survives iff it is closer to the query than to every
    already-selected neighbour (the paper's Alg 4), evaluated as a masked
    scan over the distance-sorted candidate slots; the candidate-pair
    distances come from the fused pair-gather kernel.  PAD / self /
    duplicate / out-of-range candidates are masked out first.  Returns
    (ids PAD-padded, raw scores inf-padded), both in selection order.
    """
    b, c = cand_ids.shape
    n = corpus.shape[0]
    sentinel = jnp.int32(n)
    rows = jnp.arange(b)[:, None]

    invalid = (cand_ids < 0) | (cand_ids >= n) \
        | (cand_ids == q_ids[:, None].astype(jnp.int32))
    # duplicate candidates: cluster ids (invalid -> sentinel) with a stable
    # sort, flag repeats, scatter the flags back to original slots
    ids_key = jnp.where(invalid, sentinel, cand_ids)
    o_id = jnp.argsort(ids_key, axis=1)
    sid = jnp.take_along_axis(ids_key, o_id, axis=1)
    dup_s = jnp.concatenate(
        [jnp.zeros((b, 1), bool),
         (sid[:, 1:] == sid[:, :-1]) & (sid[:, 1:] < sentinel)], axis=1)
    invalid = invalid | jnp.zeros((b, c), bool).at[rows, o_id].set(dup_s)

    d = jnp.where(invalid, jnp.inf, cand_d.astype(jnp.float32))
    o_d = jnp.argsort(d, axis=1)                   # stable: ties keep order
    cid = jnp.take_along_axis(cand_ids, o_d, axis=1)
    cd = jnp.take_along_axis(d, o_d, axis=1)
    valid = jnp.isfinite(cd)

    safe = jnp.where(valid, cid, 0)
    pair = jax.vmap(
        lambda r: ops.pair_gather_distances(r, corpus, mode=mode))(safe)

    def step(carry, j):
        sel, nsel = carry                          # (B, C) bool, (B,) int32
        dj = cd[:, j]
        pj = jnp.take(pair, j, axis=1)             # (B, C): d(cand_j, ·)
        dmin = jnp.min(jnp.where(sel, pj, jnp.inf), axis=1)
        ok = valid[:, j] & (nsel < m) & ((nsel == 0) | (dj < dmin))
        sel = sel.at[:, j].set(ok)
        return (sel, nsel + ok.astype(jnp.int32)), None

    init = (jnp.zeros((b, c), bool), jnp.zeros((b,), jnp.int32))
    (sel, nsel), _ = jax.lax.scan(step, init, jnp.arange(c))

    # final order: selected (already distance-sorted) first, then — with
    # keepPruned — the pruned survivors by distance, invalid slots last
    idx = jnp.broadcast_to(jnp.arange(c)[None, :], (b, c))
    if keep_pruned:
        key = jnp.where(sel, idx,
                        jnp.where(valid, c + idx, 2 * c + idx))
        limit = jnp.minimum(m, valid.sum(axis=1))
    else:
        key = jnp.where(sel, idx, 2 * c + idx)
        limit = jnp.minimum(m, nsel)
    o_f = jnp.argsort(key, axis=1)
    fid = jnp.take_along_axis(cid, o_f, axis=1)[:, :m]
    fd = jnp.take_along_axis(cd, o_f, axis=1)[:, :m]
    pos_ok = jnp.arange(m)[None, :] < limit[:, None]
    # slot priority: 0 = heuristically selected (diverse — must survive
    # later degree capping), 1 = keepPruned fill (nearest, replaceable)
    pri = (jnp.arange(m)[None, :] >= nsel[:, None]).astype(jnp.int32)
    return (jnp.where(pos_ok, fid, PAD).astype(jnp.int32),
            jnp.where(pos_ok, fd, jnp.inf),
            jnp.where(pos_ok, pri, 1))


def _prune_chunks(corpus_dev: jax.Array, q_ids: np.ndarray,
                  cand_ids: np.ndarray, cand_d: np.ndarray, *, m: int,
                  mode: str, keep_pruned: bool, chunk: int = PRUNE_CHUNK
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Run `_prune_batch` over fixed-size chunks (one compile per shape)."""
    nq, c = cand_ids.shape
    n = int(corpus_dev.shape[0])
    step = min(chunk, nq)
    out_i = np.full((nq, m), PAD, dtype=np.int32)
    out_d = np.full((nq, m), INF, dtype=np.float32)
    out_p = np.ones((nq, m), dtype=np.int32)
    for lo in range(0, nq, step):
        hi = min(lo + step, nq)
        real = hi - lo
        qs = q_ids[lo:hi].astype(np.int32)
        ci = cand_ids[lo:hi]
        cd = cand_d[lo:hi]
        if real < step:                            # pad the tail chunk
            qs = np.concatenate([qs, np.full(step - real, n, np.int32)])
            ci = np.vstack([ci, np.full((step - real, c), PAD, np.int32)])
            cd = np.vstack([cd, np.full((step - real, c), INF, np.float32)])
        si, sd, sp = _prune_batch(corpus_dev, jnp.asarray(qs),
                                  jnp.asarray(ci), jnp.asarray(cd), m=m,
                                  mode=mode, keep_pruned=keep_pruned)
        out_i[lo:hi] = np.asarray(si)[:real]
        out_d[lo:hi] = np.asarray(sd)[:real]
        out_p[lo:hi] = np.asarray(sp)[:real]
    return out_i, out_d, out_p


# ---------------------------------------------------------------------------
# deterministic scatter/cap symmetrize (intra-batch conflict resolution)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m",))
def _merge_cap(adj: jax.Array, adj_d: jax.Array, adj_p: jax.Array,
               new_tgt: jax.Array, new_src: jax.Array, new_d: jax.Array,
               new_p: jax.Array, *, m: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Merge incoming directed edges into the adjacency, capped at m.

    adj / adj_d / adj_p are (N+1, m) — row N is a scratch row absorbing
    masked writes.  Existing rows and the incoming (tgt, src, dist,
    priority) edges form one edge list; (target, source) duplicates are
    dropped keeping the best copy, entries are ranked per target by
    (priority, distance, source id) via composed stable sorts, and ranks
    < m are scattered back.  Priority 0 marks heuristically-selected
    (Alg 4) edges, 1 marks keepPruned fill and reverse edges: ranking
    priority first means degree capping evicts nearest-fill edges before
    the diverse long-range links the heuristic chose — the same outcome
    as the incremental builder's `_shrink` re-prune, without re-running
    the heuristic per overflow.  Every result row is self-loop-free and
    duplicate-free regardless of how many same-batch nodes targeted the
    same neighbour.
    """
    np1, _ = adj.shape
    scratch = np1 - 1

    ex_tgt = jnp.broadcast_to(
        jnp.arange(np1, dtype=jnp.int32)[:, None], adj.shape).reshape(-1)
    tgt = jnp.concatenate([ex_tgt, new_tgt.astype(jnp.int32)])
    src = jnp.concatenate([adj.reshape(-1), new_src.astype(jnp.int32)])
    dd = jnp.concatenate([adj_d.reshape(-1).astype(jnp.float32),
                          new_d.astype(jnp.float32)])
    pri = jnp.concatenate([adj_p.reshape(-1).astype(jnp.int32),
                           new_p.astype(jnp.int32)])
    bad = (src < 0) | (src >= scratch) | (tgt < 0) | (tgt >= scratch) \
        | (src == tgt) | ~jnp.isfinite(dd)
    tgt = jnp.where(bad, scratch, tgt)
    src_k = jnp.where(bad, scratch, src)
    dd = jnp.where(bad, jnp.inf, dd)
    pri = jnp.where(bad, 1, pri)

    # dedup by (target, source): stable lexicographic sort on
    # (target, source, priority, distance), flag adjacent repeats, scatter
    # the flags back.  The surviving copy is the best (priority, distance)
    # one — a reverse duplicate must not demote a selected edge to fill.
    o = jnp.argsort(dd)
    o = o[jnp.argsort(pri[o])]
    o = o[jnp.argsort(src_k[o])]
    perm = o[jnp.argsort(tgt[o])]
    t_s, s_s = tgt[perm], src_k[perm]
    dup_s = jnp.concatenate(
        [jnp.zeros((1,), bool),
         (t_s[1:] == t_s[:-1]) & (s_s[1:] == s_s[:-1]) & (t_s[1:] < scratch)])
    dup = jnp.zeros_like(dup_s).at[perm].set(dup_s)
    tgt = jnp.where(dup, scratch, tgt)
    dd = jnp.where(dup, jnp.inf, dd)
    pri = jnp.where(dup, 1, pri)

    # rank per target by (priority, distance, source id): composed sorts
    o = jnp.argsort(src_k)
    tgt, src, dd, pri = tgt[o], src[o], dd[o], pri[o]
    o = jnp.argsort(dd)
    tgt, src, dd, pri = tgt[o], src[o], dd[o], pri[o]
    o = jnp.argsort(pri)
    tgt, src, dd, pri = tgt[o], src[o], dd[o], pri[o]
    o = jnp.argsort(tgt)
    tgt, src, dd, pri = tgt[o], src[o], dd[o], pri[o]
    pos = jnp.arange(tgt.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), tgt[1:] != tgt[:-1]])
    group_start = jax.lax.cummax(jnp.where(first, pos, 0))
    rank = pos - group_start

    keep = (rank < m) & (tgt < scratch) & jnp.isfinite(dd)
    row = jnp.where(keep, tgt, scratch)
    col = jnp.where(keep, rank, 0)
    out = jnp.full((np1, m), PAD, jnp.int32).at[row, col].set(
        jnp.where(keep, src, PAD))
    out_d = jnp.full((np1, m), jnp.inf, jnp.float32).at[row, col].set(
        jnp.where(keep, dd, jnp.inf))
    out_p = jnp.ones((np1, m), jnp.int32).at[row, col].set(
        jnp.where(keep, pri, 1))
    return out, out_d, out_p


def _edges_both_ways(sel_ids: np.ndarray, sel_d: np.ndarray,
                     sel_p: np.ndarray, node_ids: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """Pruned selections -> forward + reverse directed edge arrays.

    Forward edges carry the prune's slot priority (0 = heuristic pick);
    reverse edges are always priority 1 — they were not chosen by the
    target's own diversification, so they compete as fill."""
    m = sel_ids.shape[1]
    tgt_f = np.repeat(node_ids.astype(np.int32), m)
    src_f = sel_ids.reshape(-1)
    d_f = sel_d.reshape(-1)
    p_f = sel_p.reshape(-1).astype(np.int32)
    return (np.concatenate([tgt_f, src_f]),
            np.concatenate([src_f, tgt_f]),
            np.concatenate([d_f, d_f]),
            np.concatenate([p_f, np.ones_like(p_f)]))


# ---------------------------------------------------------------------------
# shared phases: levels, entry candidates, candidate helpers, repair
# ---------------------------------------------------------------------------

def _knn_device(table: jax.Array, q_ids: np.ndarray, x_ids: np.ndarray,
                k: int, mode: str) -> Tuple[np.ndarray, np.ndarray]:
    """Exact kNN of rows ``q_ids`` among rows ``x_ids`` of the gather table,
    on the device: ``knn_ids_dists`` semantics (ids index ``x_ids``, raw
    scores ascending, k <= len(x_ids)).

    Shapes are fixed per call — queries in KNN_BLOCK-row blocks, the member
    set padded to a KNN_BUCKET multiple and masked — so a build compiles a
    handful of scans, not one per cluster.
    """
    nx = len(x_ids)
    nxp = -(-nx // KNN_BUCKET) * KNN_BUCKET
    xs = table[jnp.asarray(np.pad(x_ids, (0, nxp - nx))), 0]
    live = jnp.asarray(np.arange(nxp) < nx)
    out_i = np.empty((len(q_ids), k), np.int32)
    out_d = np.empty((len(q_ids), k), np.float32)
    for lo in range(0, len(q_ids), KNN_BLOCK):
        blk = q_ids[lo:lo + KNN_BLOCK]
        qs = table[jnp.asarray(np.pad(blk, (0, KNN_BLOCK - len(blk)))), 0]
        d, i = flat_search(qs, xs, k, metric=mode, mask=live)
        out_d[lo:lo + len(blk)] = np.asarray(d)[:len(blk)]
        out_i[lo:lo + len(blk)] = np.asarray(i)[:len(blk)]
    return out_i, out_d


def _sample_levels(n: int, cfg: HNSWConfig,
                   rng: np.random.RandomState) -> np.ndarray:
    lv = np.minimum((-np.log(np.maximum(rng.random_sample(n), 1e-12))
                     * cfg.mL).astype(np.int64), 127).astype(np.int8)
    if not (lv >= 1).any():
        lv[0] = 1                                  # guarantee a hierarchy
    return lv


def _rowwise_dists(vecs: np.ndarray, row_ids: np.ndarray,
                   nbr_ids: np.ndarray, metric: str,
                   chunk: int = 2048) -> np.ndarray:
    """d(vecs[row_ids[i]], vecs[nbr_ids[i, j]]) -> (len, r) raw scores."""
    out = np.empty(nbr_ids.shape, dtype=np.float32)
    r = nbr_ids.shape[1]
    for lo in range(0, len(row_ids), chunk):
        hi = min(lo + chunk, len(row_ids))
        a = vecs[row_ids[lo:hi]]
        b = vecs[nbr_ids[lo:hi].reshape(-1)].reshape(hi - lo, r, -1)
        if metric == "l2":
            diff = b - a[:, None, :]
            out[lo:hi] = np.einsum("crd,crd->cr", diff, diff)
        else:
            out[lo:hi] = -np.einsum("cd,crd->cr", a, b)
    return out


def _upper_members(levels: np.ndarray) -> Tuple[np.ndarray, int, int]:
    """(upper_ids, max_level, entry_global): the layer>=1 members, which
    search scans for its layer-0 entry, and the top layer's first node."""
    max_level = int(levels.max())
    upper_ids = np.where(levels >= 1)[0].astype(np.int32)
    entry_global = int(upper_ids[levels[upper_ids] >= max_level][0])
    return upper_ids, max_level, entry_global


def _prefix_graph(table: jax.Array, adj0: jax.Array,
                  graph_meta) -> HNSWGraph:
    """The device graph the build's own beam searches traverse."""
    upper_ids, _, entry_global = graph_meta
    ids = jnp.asarray(upper_ids)
    return HNSWGraph(vectors=table, adj0=adj0, upper_ids=ids,
                     upper_vecs=table[ids, 0],
                     entry_global=jnp.asarray(entry_global, jnp.int32))


def _bfs_reachable(adj0: np.ndarray, entry: int) -> np.ndarray:
    n = adj0.shape[0]
    seen = np.zeros(n, dtype=bool)
    frontier = np.array([entry], dtype=np.int64)
    seen[entry] = True
    while len(frontier):
        nxt = adj0[frontier].reshape(-1)
        nxt = nxt[nxt >= 0]
        nxt = np.unique(nxt)
        nxt = nxt[~seen[nxt]]
        seen[nxt] = True
        frontier = nxt
    return seen


def _repair_connectivity(vecs: np.ndarray, adj0: np.ndarray,
                         adj0_d: np.ndarray, entry: int,
                         mode: str) -> int:
    """Attach components unreachable from the entry point: every stranded
    node gets a bidirectional link to its nearest reachable node (replacing
    the farthest slot when the row is full).  Mutates adj0/adj0_d in place;
    returns the number of repaired nodes."""
    seen = _bfs_reachable(adj0, entry)
    lost = np.where(~seen)[0]
    if len(lost) == 0:
        return 0
    anchors = np.where(seen)[0]
    ids, dd = knn_ids_dists(vecs[lost], vecs[anchors], 1, metric=mode)
    near = anchors[ids[:, 0]]
    for u, a, d in zip(lost, near, dd[:, 0]):
        for node, other in ((int(a), int(u)), (int(u), int(a))):
            row = adj0[node]
            if other in row:
                continue
            slot = int(np.argmax(row == PAD)) if (row == PAD).any() \
                else row.shape[0] - 1
            row[slot] = other
            adj0_d[node, slot] = d
    return int(len(lost))


# ---------------------------------------------------------------------------
# mode 1: level-wise batched inserts over the frozen prefix
# ---------------------------------------------------------------------------

def _bulk_level(vecs: np.ndarray, corpus_dev: jax.Array, cfg: HNSWConfig,
                rng: np.random.RandomState, levels: np.ndarray, graph_meta,
                mode: str, progress: Optional[ProgressFn]) -> Tuple[
                    np.ndarray, np.ndarray, Dict]:
    upper_ids, max_level, _ = graph_meta
    n, _ = vecs.shape
    m0 = cfg.m0
    ef_build = cfg.ef_build or cfg.ef_construction
    k_base = min(m0 + cfg.M, n - 1)
    r = min(cfg.M, 8, n - 1)

    adj = jnp.full((n + 1, m0), PAD, jnp.int32)
    adj_d = jnp.full((n + 1, m0), jnp.inf, jnp.float32)
    adj_p = jnp.ones((n + 1, m0), jnp.int32)

    # descending-level insertion order puts every upper-layer node (entry
    # point included) into the bootstrap set, so beam descents always land
    # on linked prefix nodes
    order = np.argsort(-levels.astype(np.int64), kind="stable")
    batch = min(cfg.build_batch, n)
    b0 = min(n, max(batch, len(upper_ids)))
    boot = order[:b0]

    def add_edges(sel_i, sel_d, sel_p, node_ids, m):
        nonlocal adj, adj_d, adj_p
        tgt, src, dd, pp = _edges_both_ways(sel_i, sel_d, sel_p, node_ids)
        adj, adj_d, adj_p = _merge_cap(
            adj, adj_d, adj_p, jnp.asarray(tgt), jnp.asarray(src),
            jnp.asarray(dd), jnp.asarray(pp), m=m)

    # ---- bootstrap: exact kNN + prune among the first b0 nodes
    kb = min(k_base + 1, b0)
    loc_ids, loc_d = knn_ids_dists(vecs[boot], vecs[boot], kb, metric=mode)
    cand_i = boot[loc_ids].astype(np.int32)
    cand_d = loc_d
    if r > 0:
        rnd = boot[rng.randint(0, b0, size=(b0, r))].astype(np.int32)
        cand_i = np.concatenate([cand_i, rnd], axis=1)
        cand_d = np.concatenate(
            [cand_d, _rowwise_dists(vecs, boot, rnd, mode)], axis=1)
    sel_i, sel_d, sel_p = _prune_chunks(corpus_dev, boot, cand_i, cand_d,
                                        m=m0, mode=mode,
                                        keep_pruned=cfg.keep_pruned)
    add_edges(sel_i, sel_d, sel_p, boot, m0)
    if progress is not None:
        progress("insert", b0, n)
    logger.debug("bulk level: bootstrap %d/%d", b0, n)

    # ---- batched level-wise growth over the frozen prefix
    g = _prefix_graph(corpus_dev, adj[:n], graph_meta)
    k_beam = min(k_base, ef_build)
    k_intra = min(8, batch - 1) if batch > 1 else 0
    width = max(cfg.expansion_width, 8)
    n_batches = 0
    for lo in range(b0, n, batch):
        hi = min(lo + batch, n)
        bids = order[lo:hi]
        if len(bids) < batch:                      # pad the tail batch
            bids = np.concatenate(
                [bids, np.full(batch - len(bids), n, np.int64)])
        q = vecs[np.minimum(bids, n - 1)]
        g = g._replace(adj0=adj[:n])
        bd, bi = beam_search(g, jnp.asarray(q), k=k_beam, ef=ef_build,
                             max_level=max_level, metric=mode,
                             expansion_width=width)
        cand_i = [np.asarray(bi)]
        cand_d = [np.asarray(bd)]
        if k_intra > 0:
            ii, idd = knn_ids_dists(q, q, k_intra + 1, metric=mode)
            cand_i.append(bids[ii].astype(np.int32))
            cand_d.append(idd)
        if r > 0:
            rnd = order[rng.randint(0, hi, size=(batch, r))].astype(np.int32)
            cand_i.append(rnd)
            cand_d.append(_rowwise_dists(
                vecs, np.minimum(bids, n - 1), rnd, mode))
        ci = np.concatenate(cand_i, axis=1)
        cd = np.concatenate(cand_d, axis=1)
        sel_i, sel_d, sel_p = _prune_chunks(corpus_dev, bids, ci, cd, m=m0,
                                            mode=mode,
                                            keep_pruned=cfg.keep_pruned,
                                            chunk=batch)
        pad_rows = bids >= n
        sel_i[pad_rows] = PAD
        sel_d[pad_rows] = INF
        add_edges(sel_i, sel_d, sel_p, bids.astype(np.int32), m0)
        n_batches += 1
        if progress is not None:
            progress("insert", hi, n)
        logger.debug("bulk level: %d/%d inserted", hi, n)

    adj0 = np.array(adj[:n])
    adj0_d = np.array(adj_d[:n])
    return adj0, adj0_d, {"build_batches": n_batches + 1,
                          "build_bootstrap": int(b0)}


# ---------------------------------------------------------------------------
# mode 2: two-phase coarse build (cluster -> link -> stitch)
# ---------------------------------------------------------------------------

def _coarse_candidates(vecs: np.ndarray, table: jax.Array,
                       cfg: HNSWConfig, rng: np.random.RandomState,
                       mode: str, progress: Optional[ProgressFn]
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """k-means cluster the corpus, then exact-kNN each node against the
    union of its two nearest clusters.  Returns (cand_ids, cand_d,
    boundary_margin, nlist); margin is the assignment-score gap (small =
    near a cluster boundary = stitch candidate)."""
    n, _ = vecs.shape
    nlist = max(1, int(round(n / cfg.coarse_cluster)))
    # candidate pool per node: one full adjacency row of slots plus half the
    # construction beam.  Priority-aware merge capping preserves the
    # heuristic's diverse picks, so the pool does not need to match the full
    # ef_construction beam — prune time grows roughly linearly with kc.
    ef_b = cfg.ef_build or cfg.ef_construction
    kc = min(max(cfg.m0 + cfg.M, ef_b // 2) + 2, n)

    if nlist <= 1:
        all_ids = np.arange(n)
        ids, dd = _knn_device(table, all_ids, all_ids, kc, mode)
        return ids, dd, np.zeros(n, np.float32), 1

    samp = rng.choice(n, size=min(n, max(nlist * 64, 4096)), replace=False)
    cent = np.asarray(_fit_one_subspace(
        jax.random.PRNGKey(cfg.seed), jnp.asarray(vecs[samp]), nlist,
        KMEANS_ITERS))
    if progress is not None:
        progress("cluster", nlist, nlist)

    # two nearest centroids per node: boundary nodes see both clusters
    a1 = np.empty(n, np.int32)
    a2 = np.empty(n, np.int32)
    margin = np.empty(n, np.float32)
    cc = (cent * cent).sum(1)
    for lo in range(0, n, 8192):
        hi = min(lo + 8192, n)
        blk = vecs[lo:hi]
        if mode == "l2":
            d = ((blk * blk).sum(1)[:, None] + cc[None, :]
                 - 2.0 * blk @ cent.T)
        else:
            d = -(blk @ cent.T)
        top2 = np.argpartition(d, 1, axis=1)[:, :2]
        dt = np.take_along_axis(d, top2, axis=1)
        swap = dt[:, 0] > dt[:, 1]
        top2[swap] = top2[swap][:, ::-1]
        dt[swap] = dt[swap][:, ::-1]
        a1[lo:hi], a2[lo:hi] = top2[:, 0], top2[:, 1]
        margin[lo:hi] = dt[:, 1] - dt[:, 0]

    cand_i = np.full((n, kc), PAD, dtype=np.int32)
    cand_d = np.full((n, kc), INF, dtype=np.float32)
    for c in range(nlist):
        prim = np.where(a1 == c)[0]
        if len(prim) == 0:
            continue
        mem = np.where((a1 == c) | (a2 == c))[0]
        kk = min(kc, len(mem))
        loc, dd = _knn_device(table, prim, mem, kk, mode)
        cand_i[prim, :kk] = mem[loc].astype(np.int32)
        cand_d[prim, :kk] = dd
        if progress is not None:
            progress("link", c + 1, nlist)
        logger.debug("bulk coarse: cluster %d/%d linked (%d members)",
                     c + 1, nlist, len(mem))
    return cand_i, cand_d, margin, nlist


def _bulk_coarse(vecs: np.ndarray, corpus_dev: jax.Array, cfg: HNSWConfig,
                 rng: np.random.RandomState, levels: np.ndarray, graph_meta,
                 mode: str, progress: Optional[ProgressFn]
                 ) -> Tuple[np.ndarray, np.ndarray, Dict]:
    _, max_level, _ = graph_meta
    n, _ = vecs.shape
    m0 = cfg.m0
    r = min(cfg.M, 8, n - 1)

    t0 = time.perf_counter()
    cand_i, cand_d, margin, nlist = _coarse_candidates(
        vecs, corpus_dev, cfg, rng, mode, progress)
    t_cand = time.perf_counter()
    if r > 0:
        rnd = rng.randint(0, n, size=(n, r)).astype(np.int32)
        cand_i = np.concatenate([cand_i, rnd], axis=1)
        cand_d = np.concatenate(
            [cand_d, _rowwise_dists(vecs, np.arange(n), rnd, mode)], axis=1)

    all_ids = np.arange(n, dtype=np.int32)
    sel_i, sel_d, sel_p = _prune_chunks(corpus_dev, all_ids, cand_i, cand_d,
                                        m=m0, mode=mode,
                                        keep_pruned=cfg.keep_pruned)
    t_prune = time.perf_counter()
    if progress is not None:
        progress("prune", n, n)

    adj = jnp.full((n + 1, m0), PAD, jnp.int32)
    adj_d = jnp.full((n + 1, m0), jnp.inf, jnp.float32)
    adj_p = jnp.ones((n + 1, m0), jnp.int32)
    tgt, src, dd, pp = _edges_both_ways(sel_i, sel_d, sel_p, all_ids)
    adj, adj_d, adj_p = _merge_cap(
        adj, adj_d, adj_p, jnp.asarray(tgt), jnp.asarray(src),
        jnp.asarray(dd), jnp.asarray(pp), m=m0)
    adj.block_until_ready()
    t_merge = time.perf_counter()

    # ---- cross-cluster stitching: boundary nodes re-search the built graph
    n_stitch = int(round(cfg.stitch_frac * n)) if nlist > 1 else 0
    if n_stitch > 0:
        ef_st = max(min(cfg.ef_build or STITCH_EF, STITCH_EF), cfg.M)
        k_st = min(min(m0 + cfg.M, n - 1), ef_st)
        width = max(cfg.expansion_width, 8)
        boundary = np.argsort(margin, kind="stable")[:n_stitch]
        batch = min(cfg.build_batch, n_stitch)
        g = _prefix_graph(corpus_dev, adj[:n], graph_meta)
        for lo in range(0, n_stitch, batch):
            hi = min(lo + batch, n_stitch)
            bids = boundary[lo:hi]
            if len(bids) < batch:
                bids = np.concatenate(
                    [bids, np.full(batch - len(bids), n, np.int64)])
            q = vecs[np.minimum(bids, n - 1)]
            g = g._replace(adj0=adj[:n])
            bd, bi = beam_search(g, jnp.asarray(q), k=k_st, ef=ef_st,
                                 max_level=max_level, metric=mode,
                                 expansion_width=width)
            # merge beam hits with the node's existing row, re-prune
            ci = np.concatenate(
                [np.asarray(bi), np.asarray(adj[np.minimum(bids, n - 1)])],
                axis=1)
            cd = np.concatenate(
                [np.asarray(bd), np.asarray(adj_d[np.minimum(bids, n - 1)])],
                axis=1)
            sel_i, sel_d, sel_p = _prune_chunks(corpus_dev, bids, ci, cd,
                                                m=m0, mode=mode,
                                                keep_pruned=cfg.keep_pruned,
                                                chunk=batch)
            pad_rows = bids >= n
            sel_i[pad_rows] = PAD
            sel_d[pad_rows] = INF
            tgt, src, dd, pp = _edges_both_ways(sel_i, sel_d, sel_p,
                                                bids.astype(np.int32))
            adj, adj_d, adj_p = _merge_cap(
                adj, adj_d, adj_p, jnp.asarray(tgt), jnp.asarray(src),
                jnp.asarray(dd), jnp.asarray(pp), m=m0)
            if progress is not None:
                progress("stitch", hi, n_stitch)
        logger.debug("bulk coarse: stitched %d boundary nodes", n_stitch)

    adj0 = np.array(adj[:n])
    adj0_d = np.array(adj_d[:n])
    t_stitch = time.perf_counter()
    return adj0, adj0_d, {"build_clusters": nlist,
                          "build_stitched": n_stitch,
                          # host-clock seconds per phase (device work synced)
                          "build_s_candidates": t_cand - t0,
                          "build_s_prune": t_prune - t_cand,
                          "build_s_merge": t_merge - t_prune,
                          "build_s_stitch": t_stitch - t_merge}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def bulk_build_device(vectors: np.ndarray,
                      config: HNSWConfig = HNSWConfig(),
                      progress: Optional[ProgressFn] = None) -> PackedHNSW:
    """Device-parallel bulk HNSW build (the `builder="bulk"` engine path).

    Dispatches on ``config.bulk_mode``: "level" = batched level-wise
    inserts via beam descents over the frozen prefix; "coarse" = two-phase
    k-means clustering + intra-cluster linking + boundary stitching;
    "auto" picks coarse at ``coarse_threshold`` rows.  Corpora below
    ``MIN_DEVICE_N`` rows fall back to the numpy reference ``bulk_build``
    (fixed-shape batching has no leverage there).
    """
    cfg = config
    vecs = preprocess_vectors(vectors, cfg.metric)
    n = vecs.shape[0]
    if n < MIN_DEVICE_N:
        packed = bulk_build(vectors, cfg, progress=progress)
        packed.build_info = {"builder_mode": "ref_small_n"}
        return packed

    mode = cfg.bulk_mode
    if mode == "auto":
        mode = "coarse" if n >= cfg.coarse_threshold else "level"
    dev_metric = "l2" if cfg.metric == "l2" else "dot"

    rng = np.random.RandomState(cfg.seed)
    levels = _sample_levels(n, cfg, rng)
    table = gather_table(vecs)
    graph_meta = _upper_members(levels)
    upper_ids, max_level, entry_global = graph_meta

    build_fn = _bulk_coarse if mode == "coarse" else _bulk_level
    adj0, adj0_d, info = build_fn(vecs, table, cfg, rng, levels, graph_meta,
                                  dev_metric, progress)

    t_layer0 = time.perf_counter()
    repaired = _repair_connectivity(vecs, adj0, adj0_d, entry_global,
                                    dev_metric)
    if repaired:
        logger.info("bulk build: reattached %d stranded nodes", repaired)
    info.update({"builder_mode": mode, "build_repaired": repaired,
                 "build_s_repair": time.perf_counter() - t_layer0})

    return PackedHNSW(config=cfg, vectors=vecs, adj0=adj0,
                      upper_ids=upper_ids, levels=levels,
                      entry_global=entry_global, max_level=max_level,
                      build_info=info)
