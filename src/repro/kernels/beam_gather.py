"""Fused gather-distance Pallas kernels for wide-beam HNSW traversal.

The wide-beam search pops B candidates per iteration and evaluates all
B·M0 neighbour distances in one shot.  The memory pattern is a *row gather*
(neighbour ids are data-dependent) followed by a dense contraction — exactly
the shape scalar-prefetch Pallas was built for:

  * the (L,) id vector rides as a scalar-prefetch argument, available before
    the kernel body runs;
  * the corpus (vectors / PQ codes / packed BQ words) stays in HBM
    (``memory_space=ANY``) — it never fits in VMEM and only L rows of it are
    touched per call;
  * each grid step issues TB row-DMAs into a VMEM scratch tile (one DMA
    semaphore per row, started together so the copies overlap), waits, and
    fuses the distance arithmetic on the landed tile — gather and distance
    never round-trip through HBM.

**Gather tables.**  A DMA slice must cover whole (8, 128) tiles of the
array it reads, so a single row of an (N, W) matrix cannot be copied.  The
kernels therefore read a *gather table* (:func:`gather_table`): the rows
stored as (N, 1, Wp), W zero-padded to a multiple of 128 lanes and 8/16-bit
integer codes widened to int32.  XLA lays such an array out with (1, 128)
tiles — no padding in HBM — so ``table.at[row]`` is one aligned (1, Wp)
slice.  Tables are built once, where a corpus ships to the device
(``hnsw_search.to_device``, the bulk builder); zero lanes add nothing to an
L2, dot, Hamming or ADC sum.

Three variants share the structure, differing only in the fused math:

  ``beam_gather_kernel``          rows (TB, Dp) fp32   -> L2 / -dot  (VPU)
  ``beam_gather_adc_kernel``      rows (TB, mp) int32  -> LUT-sum ADC (MXU,
                                  one-hot codes contracted with the LUT)
  ``beam_gather_hamming_kernel``  rows (TB, Wp) uint32 -> XOR+popcount (VPU)

so quantized engines traverse the graph in *code domain* — the code table
is the only corpus-sized buffer the search touches, not a float32
reconstruction.

Block shapes: TB defaults to 128 rows; ids are padded to a TB multiple with
row 0 (a valid row — padded lanes are sliced off before returning).  VMEM per
step: the (TB, 1, Dp) scratch occupies one (8, 128)-tile row per gathered
row, TB·8·Dp·4 = 3 MiB at Dp=768 — inside the 16 MiB scoped default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TB = 128
DEFAULT_M_CHUNK = 8
LANES = 128
HIGHEST = jax.lax.Precision.HIGHEST


def gather_table(x) -> jax.Array:
    """(N, W) rows -> (N, 1, Wp) device gather table (module docstring).

    Accepts a NumPy or JAX matrix.  NumPy input is padded on the host, so
    shipping a corpus makes no device-side copy.
    """
    if x.ndim != 2:
        raise ValueError(f"gather_table takes (N, W) rows, got {x.shape}")
    n, w = x.shape
    xp = np if isinstance(x, np.ndarray) else jnp
    if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize < 4:
        x = x.astype(np.int32)
    wp = -(-w // LANES) * LANES
    if wp != w:
        x = xp.pad(x, ((0, 0), (0, wp - w)))
    return jnp.asarray(x.reshape(n, 1, wp))


def check_table(table: jax.Array, dtype) -> None:
    """Raise unless ``table`` is a (N, 1, Wp) gather table of ``dtype``.

    The kernels take tables only: converting a plain corpus inside a call
    would pad and copy all of it on every call of the search loop.
    """
    if (table.ndim != 3 or table.shape[1] != 1
            or table.shape[-1] % LANES or table.dtype != dtype):
        raise ValueError(f"expected a (N, 1, Wp) {jnp.dtype(dtype).name} "
                         f"gather table, got {table.shape} {table.dtype}; "
                         f"build it once with gather_table")


def pad_lanes(v: jax.Array, width: int) -> jax.Array:
    """Zero-pad the last axis of ``v`` to ``width`` (a table's Wp)."""
    extra = width - v.shape[-1]
    if extra == 0:
        return v
    return jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, extra)])


def _gather_rows(ids_ref, table_ref, rows, sems, tb: int):
    """DMA the tb table rows named by this grid step's id slice into the
    (tb, 1, Wp) VMEM scratch and return them as a (tb, Wp) value.

    All copies start before any is awaited (per-row semaphores), so the
    gathers overlap instead of serializing on HBM latency.
    """
    i = pl.program_id(0)

    def copy(t):
        return pltpu.make_async_copy(table_ref.at[ids_ref[i * tb + t]],
                                     rows.at[t], sems.at[t])

    def start(t, carry):
        copy(t).start()
        return carry

    def wait(t, carry):
        copy(t).wait()
        return carry

    jax.lax.fori_loop(0, tb, start, 0)
    jax.lax.fori_loop(0, tb, wait, 0)
    return rows[...].reshape(tb, rows.shape[-1])


def _pad_ids(ids: jax.Array, tb: int):
    """ids (L,) -> (ceil(L/tb)*tb,) int32, padded with row 0."""
    l = ids.shape[0]
    g = -(-l // tb)
    return jnp.pad(ids.astype(jnp.int32), (0, g * tb - l)), g


def _gather_call(body, ids: jax.Array, first, table: jax.Array, *, tb: int,
                 out_dtype, interpret: bool) -> jax.Array:
    """The grid shared by the three variants: ``first`` (a small operand
    kept whole in VMEM — query row or LUT) and the HBM table in, one (1, TB)
    block of distances out per step."""
    l = ids.shape[0]
    tb = min(tb, l)
    ids_p, g = _pad_ids(ids, tb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[pl.BlockSpec(first.shape, lambda i, ids: (0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tb), lambda i, ids: (0, i)),
        scratch_shapes=[pltpu.VMEM((tb, 1, table.shape[-1]), table.dtype),
                        pltpu.SemaphoreType.DMA((tb,))],
    )
    out = pl.pallas_call(
        functools.partial(body, tb=tb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, g * tb), out_dtype),
        interpret=interpret,
    )(ids_p, first, table)
    return out[0, :l]


# ------------------------------------------------------------------ L2 / dot
def _beam_kernel(ids_ref, q_ref, table_ref, o_ref, rows, sems, *,
                 tb: int, mode: str):
    r = _gather_rows(ids_ref, table_ref, rows, sems, tb)   # (TB, Dp)
    q = q_ref[...]                                         # (1, Dp)
    if mode == "l2":
        # same float ops as the traversal historically used (diff-square-sum,
        # not the norm expansion)
        d = r - q
        o_ref[...] = jnp.sum(d * d, axis=-1)[None, :]
    else:  # dot: an exact f32 lane reduction, not a one-row MXU pass
        o_ref[...] = -jnp.sum(r * q, axis=-1)[None, :]


@functools.partial(jax.jit, static_argnames=("mode", "tb", "interpret"))
def beam_gather_kernel(q: jax.Array, ids: jax.Array, corpus: jax.Array, *,
                       mode: str = "l2", tb: int = DEFAULT_TB,
                       interpret: bool = False) -> jax.Array:
    """q (D,) × ids (L,) × corpus table (N, 1, Dp) -> (L,) float32
    distances."""
    if mode not in ("l2", "dot"):
        raise ValueError(f"mode {mode!r}")
    check_table(corpus, jnp.float32)
    table = corpus
    q = pad_lanes(q.astype(jnp.float32), table.shape[-1])[None, :]
    return _gather_call(functools.partial(_beam_kernel, mode=mode), ids, q,
                        table, tb=tb, out_dtype=jnp.float32,
                        interpret=interpret)


# ---------------------------------------------------------------------- ADC
def adc_onehot_sum(lut: jax.Array, codes_t: jax.Array,
                   m_chunk: int) -> jax.Array:
    """sum_i lut[:, i, codes_t[i, n]] as MXU contractions (shared with the
    dense ``pq_adc`` scan).

    lut (TQ, m, k) f32, codes_t (m, TN) int32 — codes transposed so each
    sub-space is one sublane row.  Per chunk of ``m_chunk`` sub-spaces the
    one-hot slab (m_chunk·k, TN) is stacked along sublanes and contracted
    with the matching (TQ, m_chunk·k) LUT slice; HIGHEST precision keeps the
    LUT values f32-exact (the one-hot side is exact in any precision).
    m must be a multiple of ``m_chunk``.
    """
    tq, m, k = lut.shape
    tn = codes_t.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (k, tn), 0)
    acc = jnp.zeros((tq, tn), jnp.float32)
    for m0 in range(0, m, m_chunk):                      # static loop
        onehot = jnp.concatenate(
            [(iota == codes_t[j:j + 1, :]).astype(jnp.float32)
             for j in range(m0, m0 + m_chunk)], axis=0)  # (mc·k, TN)
        acc += jax.lax.dot_general(
            lut[:, m0:m0 + m_chunk, :].reshape(tq, m_chunk * k), onehot,
            (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
    return acc


def _beam_adc_kernel(ids_ref, lut_ref, table_ref, o_ref, rows, sems, *,
                     tb: int, m_chunk: int):
    codes = _gather_rows(ids_ref, table_ref, rows, sems, tb)   # (TB, mp)
    lut = lut_ref[...]                                         # (m8, k)
    m8 = lut.shape[0]
    o_ref[...] = adc_onehot_sum(lut[None], codes.T[:m8, :], m_chunk)


@functools.partial(jax.jit, static_argnames=("tb", "m_chunk", "interpret"))
def beam_gather_adc_kernel(lut: jax.Array, ids: jax.Array, codes: jax.Array,
                           *, tb: int = DEFAULT_TB,
                           m_chunk: int = DEFAULT_M_CHUNK,
                           interpret: bool = False) -> jax.Array:
    """lut (m, k) × ids (L,) × int32 code table (N, 1, mp) -> (L,) float32
    ADC.

    The LUT is zero-padded to a multiple of ``m_chunk`` sub-spaces; the
    padded sub-spaces read code 0 from the table's zero lanes (or real
    codes past m, weighted by zero LUT rows) and add nothing.
    """
    check_table(codes, jnp.int32)
    table = codes
    m = lut.shape[0]
    m_chunk = min(m_chunk, m)
    m8 = -(-m // m_chunk) * m_chunk
    if m8 > table.shape[-1]:
        raise ValueError(f"{m} sub-spaces do not fit a {table.shape[-1]}-lane "
                         f"code table in chunks of {m_chunk}")
    lut_p = jnp.pad(lut.astype(jnp.float32), ((0, m8 - m), (0, 0)))
    return _gather_call(
        functools.partial(_beam_adc_kernel, m_chunk=m_chunk), ids, lut_p,
        table, tb=tb, out_dtype=jnp.float32,
        interpret=interpret)


# ------------------------------------------------------------------ Hamming
def _beam_hamming_kernel(ids_ref, q_ref, table_ref, o_ref, rows, sems, *,
                         tb: int):
    r = _gather_rows(ids_ref, table_ref, rows, sems, tb)   # (TB, Wp)
    x = jnp.bitwise_xor(r, q_ref[...])
    pc = jax.lax.population_count(x).astype(jnp.int32)
    o_ref[...] = jnp.sum(pc, axis=-1)[None, :]


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def beam_gather_hamming_kernel(q_code: jax.Array, ids: jax.Array,
                               codes: jax.Array, *, tb: int = DEFAULT_TB,
                               interpret: bool = False) -> jax.Array:
    """q_code (W,) uint32 × ids (L,) × word table (N, 1, Wp) uint32
    -> (L,) int32."""
    if q_code.dtype != jnp.uint32:
        raise TypeError("packed BQ words must be uint32")
    check_table(codes, jnp.uint32)
    q = pad_lanes(q_code, codes.shape[-1])[None, :]
    return _gather_call(_beam_hamming_kernel, ids, q, codes, tb=tb,
                        out_dtype=jnp.int32, interpret=interpret)
