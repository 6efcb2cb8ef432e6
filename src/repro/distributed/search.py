"""Distributed Quantixar search — the paper's engine on the production mesh.

Corpus rows are sharded over the batch axes (`pod`, `data`); vector *dims*
(float scan) or PQ *sub-spaces* / BQ *words* are sharded over `model`, so
both mesh axes contribute:

    local partial distances  (MXU GEMM / ADC gather / popcount per shard)
      → psum over `model`    (partial-dim contributions)
      → local top-k          (k per row shard)
      → all_gather over row shards (k·shards candidates — tiny)
      → global top-k merge   (exact: top-k of a union ⊇ top-k of whole set)

Exactness of the merge is property-tested (tests/test_distributed.py).  This
is the shard_map program the multi-pod dry-run lowers for the quantixar-db
cells, and the serving path for real deployments.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..launch.mesh import batch_axes, mesh_axis_sizes

Array = jax.Array


def _model_in_mesh(mesh: Mesh, feature_dim: int = 0) -> bool:
    """Use the model axis for the feature dim only when it divides evenly
    (e.g. BQ's 8 packed words cannot split 16 ways — replicated instead)."""
    if "model" not in mesh.axis_names:
        return False
    size = mesh_axis_sizes(mesh)["model"]
    return size > 1 and (feature_dim == 0 or feature_dim % size == 0)


def _merge_shard_topk(d: Array, k: int, rows) -> Tuple[Array, Array]:
    """Local (Q, N_local) distances -> exact global (Q, k) top-k.

    Works over any tuple of row axes (e.g. ('pod','data','model') in the
    rows-mode layout): the flattened shard index recovers global row ids.
    """
    n_local = d.shape[1]
    kk = min(k, n_local)
    neg, idx = jax.lax.top_k(-d, kk)
    shard = jax.lax.axis_index(rows[0])
    for ax in rows[1:]:
        shard = shard * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    gids = (idx + shard * n_local).astype(jnp.int32)
    cand_d = jax.lax.all_gather(-neg, rows, axis=1, tiled=True)
    cand_i = jax.lax.all_gather(gids, rows, axis=1, tiled=True)
    neg2, sel = jax.lax.top_k(-cand_d, k)
    return -neg2, jnp.take_along_axis(cand_i, sel, axis=1)


def _build(mesh: Mesh, local_distances: Callable, k: int,
           corpus_spec: P, query_spec: P, rows=None):
    rows = rows or batch_axes(mesh)

    def local(corpus, queries):
        d = local_distances(corpus, queries)
        return _merge_shard_topk(d, k, rows)

    # replication checking off: after the cross-shard all_gather + top_k the
    # outputs are value-identical on every shard (exactness property-tested),
    # but the static varying-axes checker cannot infer that through gather.
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(corpus_spec, query_spec),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    return jax.jit(fn,
                   in_shardings=(NamedSharding(mesh, corpus_spec),
                                 NamedSharding(mesh, query_spec)),
                   out_shardings=NamedSharding(mesh, P(None, None)))


def make_flat_search(mesh: Mesh, *, k: int, metric: str = "cosine",
                     dim: int = 0, mode: str = "rows"):
    """Sharded exact scan.

    mode="rows" (optimized, §Perf iteration 1): rows over ALL mesh axes
    (pod × data × model), feature dim replicated — no psum at all; the only
    collective is the tiny k-candidate all_gather.
    mode="dims" (paper-faithful 2D baseline): rows over (pod,data), feature
    dim over model with a psum of the (Q, N_local) partial-distance buffer —
    measured 50x more collective bytes; kept for the §Perf A/B record.
    cosine/dot assume pre-normalized inputs. Returns (dists, global ids)."""
    rows = batch_axes(mesh)
    use_model = mode == "dims" and _model_in_mesh(mesh, dim)
    if mode == "rows" and "model" in mesh.axis_names:
        rows = rows + ("model",)
    dim_ax = "model" if use_model else None

    def local_distances(corpus, queries):
        q = queries.astype(jnp.float32)
        x = corpus.astype(jnp.float32)
        if metric == "l2":
            part = (jnp.sum(q * q, 1)[:, None] + jnp.sum(x * x, 1)[None, :]
                    - 2.0 * q @ x.T)
        else:  # cosine/dot on pre-normalized vectors
            part = -(q @ x.T)
        if use_model:
            part = jax.lax.psum(part, "model")
        return jnp.maximum(part, 0.0) if metric == "l2" else part

    return _build(mesh, local_distances, k,
                  P(rows, dim_ax), P(None, dim_ax), rows=rows)


def make_pq_search(mesh: Mesh, *, k: int, m_subspaces: int = 0,
                   mode: str = "rows"):
    """Sharded PQ-ADC scan. codes (N, m), lut (Q, m, k_cb).

    mode="rows": rows over all axes, LUT replicated (Q·m·k_cb·4 ≈ 16 MB) —
    no psum. mode="dims": rows over (pod,data) + sub-spaces over model with
    a (Q, N_local) psum (baseline for the §Perf A/B)."""
    rows = batch_axes(mesh)
    use_model = mode == "dims" and _model_in_mesh(mesh, m_subspaces)
    if mode == "rows" and "model" in mesh.axis_names:
        rows = rows + ("model",)
    sub_ax = "model" if use_model else None

    def local_distances(codes, lut):
        c = codes.astype(jnp.int32)

        def per_sub(lut_i, c_i):
            return lut_i[:, c_i]

        part = jnp.sum(jax.vmap(per_sub, in_axes=(1, 1))(lut, c), axis=0)
        if use_model:
            part = jax.lax.psum(part, "model")
        return part

    return _build(mesh, local_distances, k,
                  P(rows, sub_ax), P(None, sub_ax, None), rows=rows)


def make_hamming_search(mesh: Mesh, *, k: int, words: int = 0,
                        mode: str = "rows"):
    """Sharded BQ scan (packed uint32 XOR+popcount). Same mode semantics as
    make_flat_search."""
    rows = batch_axes(mesh)
    use_model = mode == "dims" and _model_in_mesh(mesh, words)
    if mode == "rows" and "model" in mesh.axis_names:
        rows = rows + ("model",)
    word_ax = "model" if use_model else None

    def local_distances(codes, q_codes):
        x = jnp.bitwise_xor(q_codes[:, None, :], codes[None, :, :])
        part = jnp.sum(jax.lax.population_count(x), axis=-1).astype(jnp.int32)
        if use_model:
            part = jax.lax.psum(part, "model")
        return part.astype(jnp.float32)

    return _build(mesh, local_distances, k,
                  P(rows, word_ax), P(None, word_ax), rows=rows)
