"""Fused gather + pairwise-distance Pallas kernel for bulk HNSW pruning.

The vectorized Alg-4 diversification prune (core/hnsw_bulk.py) needs, for
every node in a batch, the full (C, C) distance matrix among that node's C
candidate neighbours: candidate j survives iff it is closer to the query
than to every already-selected candidate, so each scan step consults one
row of the pair matrix.

The memory pattern is the same data-dependent row gather as wide-beam
traversal (beam_gather.py) — candidate ids ride as a scalar-prefetch
argument, the corpus gather table stays in HBM (``memory_space=ANY``), and
the C rows are DMA'd into a VMEM scratch tile — but the fused math is a *self*
contraction: one (C, D) × (D, C) MXU matmul producing the full pair
matrix, instead of C separate query-row gathers.

C is small (≲128: m0 + M candidates plus random extras), so rows, the
pair matrix, and the per-row DMA semaphores all fit comfortably in VMEM
in a single grid step; batching over nodes happens outside via ``vmap``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .beam_gather import HIGHEST, _gather_rows, check_table


def _pair_kernel(ids_ref, corpus_ref, o_ref, rows, sems, *, c: int,
                 mode: str):
    r = _gather_rows(ids_ref, corpus_ref, rows, sems, c)   # (C, Dp)
    g = jax.lax.dot_general(r, r, (((1,), (1,)), ((), ())), precision=HIGHEST,
                            preferred_element_type=jnp.float32)   # (C, C)
    if mode == "l2":
        nn = jnp.sum(r * r, axis=-1)              # (C,)
        o_ref[...] = jnp.maximum(nn[:, None] + nn[None, :] - 2.0 * g, 0.0)
    else:  # dot
        o_ref[...] = -g


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def pair_gather_kernel(ids: jax.Array, corpus: jax.Array, *,
                       mode: str = "l2",
                       interpret: bool = False) -> jax.Array:
    """ids (C,) × corpus table (N, 1, Dp) -> (C, C) float32 pairwise
    distances."""
    if mode not in ("l2", "dot"):
        raise ValueError(f"mode {mode!r}")
    check_table(corpus, jnp.float32)
    table = corpus
    c = ids.shape[0]
    cp = -(-c // 8) * 8                            # sublane-align the tile
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, cp - c))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((cp, cp), lambda i, ids: (0, 0)),
        scratch_shapes=[pltpu.VMEM((cp, 1, table.shape[-1]), jnp.float32),
                        pltpu.SemaphoreType.DMA((cp,))],
    )
    out = pl.pallas_call(
        functools.partial(_pair_kernel, c=cp, mode=mode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cp, cp), jnp.float32),
        interpret=interpret,
    )(ids_p, table)
    return out[:c, :c]
