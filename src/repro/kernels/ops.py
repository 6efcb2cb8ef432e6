"""Public jit'd wrappers for the Pallas kernels.

Platform dispatch: on TPU the compiled kernels always run natively — never
in interpret mode and never through an oracle unless a caller passes
``force_ref=True`` (tests).  On other backends the library defaults to the
pure-jnp oracles in ref.py for speed; ``force_ref=False`` there runs the
kernel body in ``interpret=True`` mode (Python-evaluated, same BlockSpec
tiling), which is how tests validate the kernels off-chip.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .beam_gather import (beam_gather_adc_kernel, beam_gather_hamming_kernel,
                          beam_gather_kernel, check_table, pad_lanes)
from .bulk_prune import pair_gather_kernel
from .hamming import hamming_kernel
from .l2 import l2_distance_kernel
from .pq_adc import pq_adc_kernel
from .slstm import DEFAULT_CHUNK, slstm_sequence_kernel

Array = jax.Array


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_ref(force_ref: Optional[bool]) -> bool:
    # off-TPU, interpret-mode Pallas is correct but slow: default to the
    # oracle there.  Tests pass force_ref explicitly.
    return _interpret() if force_ref is None else force_ref


def _table_rows(table: Array, dtype) -> Array:
    """Gather table (N, 1, Wp) -> (N, Wp) for the oracles, which index rows
    directly."""
    check_table(table, dtype)
    return table[:, 0, :]


def l2_distances(queries: Array, corpus: Array, *,
                 force_ref: Optional[bool] = None, **tiles) -> Array:
    if _use_ref(force_ref):
        return ref.l2_distance_ref(queries, corpus)
    return l2_distance_kernel(queries, corpus, mode="l2",
                              interpret=_interpret(), **tiles)


def dot_distances(queries: Array, corpus: Array, *,
                  force_ref: Optional[bool] = None, **tiles) -> Array:
    if _use_ref(force_ref):
        return ref.dot_distance_ref(queries, corpus)
    return l2_distance_kernel(queries, corpus, mode="dot",
                              interpret=_interpret(), **tiles)


def pq_adc_distances(lut: Array, codes: Array, *,
                     force_ref: Optional[bool] = None, **tiles) -> Array:
    if _use_ref(force_ref):
        return ref.pq_adc_ref(lut, codes)
    return pq_adc_kernel(lut, codes, interpret=_interpret(), **tiles)


def hamming_distances(q_codes: Array, x_codes: Array, *,
                      force_ref: Optional[bool] = None, **tiles) -> Array:
    if _use_ref(force_ref):
        return ref.hamming_ref(q_codes, x_codes)
    return hamming_kernel(q_codes, x_codes, interpret=_interpret(), **tiles)


# ------------------------------------------------- wide-beam gather-distance
# Per-query fused (ids -> row gather -> distance) evaluators for the HNSW
# wide-beam traversal (core/hnsw_search.py).  Called under vmap/while_loop;
# same ref/kernel dispatch contract as the dense kernels above.  The corpus
# operand must be a gather table (beam_gather.gather_table, built once where
# the corpus ships); queries are zero-padded to the table's lane width.

def beam_gather_distances(q: Array, ids: Array, corpus: Array, *,
                          mode: str = "l2",
                          force_ref: Optional[bool] = None, **tiles) -> Array:
    """q (D,) × ids (L,) × corpus table (N, 1, Dp) -> (L,) float32
    (l2 | dot)."""
    if _use_ref(force_ref):
        rows = _table_rows(corpus, jnp.float32)
        q = pad_lanes(q, rows.shape[-1])
        if mode == "l2":
            return ref.beam_gather_l2_ref(q, ids, rows)
        return ref.beam_gather_dot_ref(q, ids, rows)
    return beam_gather_kernel(q, ids, corpus, mode=mode,
                              interpret=_interpret(), **tiles)


def pair_gather_distances(ids: Array, corpus: Array, *,
                          mode: str = "l2",
                          force_ref: Optional[bool] = None,
                          **tiles) -> Array:
    """ids (C,) × corpus table (N, 1, Dp) -> (C, C) float32 pairwise
    distances among the gathered rows (l2 | dot) — the bulk-prune pair
    matrix."""
    if _use_ref(force_ref):
        rows = _table_rows(corpus, jnp.float32)
        if mode == "l2":
            return ref.pair_gather_l2_ref(ids, rows)
        return ref.pair_gather_dot_ref(ids, rows)
    return pair_gather_kernel(ids, corpus, mode=mode,
                              interpret=_interpret(), **tiles)


def beam_gather_adc(lut: Array, ids: Array, codes: Array, *,
                    force_ref: Optional[bool] = None, **tiles) -> Array:
    """lut (m, k) × ids (L,) × code table (N, 1, mp) -> (L,) float32 ADC
    distances."""
    if _use_ref(force_ref):
        rows = _table_rows(codes, jnp.int32)[:, :lut.shape[0]]
        return ref.beam_gather_adc_ref(lut, ids, rows)
    return beam_gather_adc_kernel(lut, ids, codes,
                                  interpret=_interpret(), **tiles)


def beam_gather_hamming(q_code: Array, ids: Array, codes: Array, *,
                        force_ref: Optional[bool] = None, **tiles) -> Array:
    """q_code (W,) × ids (L,) × word table (N, 1, Wp) uint32 -> (L,) int32
    Hamming."""
    if _use_ref(force_ref):
        rows = _table_rows(codes, jnp.uint32)
        return ref.beam_gather_hamming_ref(
            pad_lanes(q_code, rows.shape[-1]), ids, rows)
    return beam_gather_hamming_kernel(q_code, ids, codes,
                                      interpret=_interpret(), **tiles)


# --------------------------------------------------------- sLSTM sequence
# Fused weight-resident sLSTM (models/recurrent.py learned-metric scorer);
# same ref/kernel dispatch contract as the distance kernels above.

def slstm_sequence(gates_x: Array, r: Array, b: Array, *, n_heads: int,
                   chunk: int = DEFAULT_CHUNK,
                   force_ref: Optional[bool] = None) -> Array:
    """gates_x (B, S, 4d) × r (4, H, blk, blk) × b (4d,) -> h (B, S, d)."""
    if _use_ref(force_ref):
        return ref.slstm_sequence_ref(gates_x, r, b, n_heads=n_heads)
    return slstm_sequence_kernel(gates_x, r, b, n_heads=n_heads,
                                 chunk=chunk, interpret=_interpret())
