"""PQ asymmetric-distance-computation Pallas kernel (DESIGN.md §2).

The paper's AVX2 "fast-scan" analogue on TPU: x86 PQ scan uses PSHUFB 16-way
LUT shuffles; the TPU has no byte-shuffle unit, but it has an MXU — so the
gather ``out[q,n] = Σ_i lut[q,i,codes[n,i]]`` is re-expressed as a dense
contraction against a one-hot expansion of the codes:

    onehot (m, k, TN) = (codes.T[:, None, :] == iota(k))
    out (TQ, TN)      = einsum('qmk,mkn->qn', lut_tile, onehot)

One-hot never leaves VMEM; the contraction runs on the MXU at (m·k) effective
depth.  For k ≤ 256 and m ≤ 64 the LUT tile (TQ·m·k·4 ≤ 8·64·256·4 = 512 KiB)
and code tile (TN·m·4 = 256·64·4 = 64 KiB) fit comfortably; the one-hot
expansion (TN·m·k·4 = 256·64·256·4 = 16 MiB) would NOT — so the kernel loops
over the m sub-spaces in chunks (``m_chunk``), keeping the live one-hot slab
at TN·m_chunk·k·4 ≤ 256·8·256·4 = 2 MiB (TN=512 overflows the v5e's scoped
VMEM at m=96).

Grid: (Q/TQ, N/TN); codes are streamed through VMEM tile by tile while each
query's LUT stays resident — exactly the paper's "LUT in registers, codes
streamed" SIMD scan, with VMEM playing the register-file role.  The codes
enter transposed, (m, N) int32, so each sub-space is one sublane row of the
tile and the one-hot slab stacks along sublanes (``adc_onehot_sum``, shared
with the wide-beam gather kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .beam_gather import adc_onehot_sum

DEFAULT_TQ = 8
DEFAULT_TN = 256
DEFAULT_M_CHUNK = 8


def _adc_kernel(lut_ref, codes_ref, o_ref, *, m_chunk: int):
    # (TQ, m, k) LUT tile × (m, TN) transposed code tile -> (TQ, TN)
    o_ref[...] = adc_onehot_sum(lut_ref[...], codes_ref[...], m_chunk)


@functools.partial(
    jax.jit,
    static_argnames=("tq", "tn", "m_chunk", "interpret"))
def pq_adc_kernel(
    lut: jax.Array,
    codes: jax.Array,
    *,
    tq: int = DEFAULT_TQ,
    tn: int = DEFAULT_TN,
    m_chunk: int = DEFAULT_M_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """ADC scan: lut (Q, m, k) float × codes (N, m) uint8/16 -> (Q, N) float32.

    Padding: queries pad with zero LUTs, codes pad with code 0 — padded rows /
    columns are sliced off before returning, so their values are irrelevant.
    Sub-spaces pad to a multiple of ``m_chunk`` with zero LUT rows, which
    add nothing.
    """
    q_n, m, k = lut.shape
    x_n, m2 = codes.shape
    if m != m2:
        raise ValueError(f"LUT has {m} sub-spaces, codes have {m2}")

    tq = min(tq, max(1, q_n))
    tn = min(tn, max(128, x_n))
    m_chunk = min(m_chunk, m)
    gq = -(-q_n // tq)
    gn = -(-x_n // tn)
    mp = -(-m // m_chunk) * m_chunk
    lut_p = jnp.pad(lut.astype(jnp.float32),
                    ((0, gq * tq - q_n), (0, mp - m), (0, 0)))
    codes_t = jnp.pad(codes.astype(jnp.int32).T,
                      ((0, mp - m), (0, gn * tn - x_n)))

    out = pl.pallas_call(
        functools.partial(_adc_kernel, m_chunk=m_chunk),
        grid=(gq, gn),
        in_specs=[
            pl.BlockSpec((tq, mp, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((mp, tn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tq, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gq * tq, gn * tn), jnp.float32),
        interpret=interpret,
    )(lut_p, codes_t)
    return out[:q_n, :x_n]
