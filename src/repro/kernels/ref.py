"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematical definition with no tiling/padding tricks;
kernel tests sweep shapes & dtypes and assert_allclose against these.
Matmuls run at HIGHEST precision, so on a TPU the oracles stay f32-exact
instead of rounding operands to bf16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def _mm(a: Array, b: Array) -> Array:
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def l2_distance_ref(queries: Array, corpus: Array) -> Array:
    """(Q, D) × (N, D) -> (Q, N) squared L2, float32 accumulation."""
    q = queries.astype(jnp.float32)
    x = corpus.astype(jnp.float32)
    qq = jnp.sum(q * q, axis=1)
    xx = jnp.sum(x * x, axis=1)
    d = qq[:, None] + xx[None, :] - 2.0 * _mm(q, x.T)
    return jnp.maximum(d, 0.0)


def dot_distance_ref(queries: Array, corpus: Array) -> Array:
    """(Q, D) × (N, D) -> (Q, N) negative inner product, float32 accum."""
    return -_mm(queries.astype(jnp.float32), corpus.astype(jnp.float32).T)


def pq_adc_ref(lut: Array, codes: Array) -> Array:
    """ADC: lut (Q, m, k) float × codes (N, m) uint -> (Q, N) float32.

    out[q, n] = sum_i lut[q, i, codes[n, i]].
    """
    c = codes.astype(jnp.int32)

    def per_sub(lut_i, c_i):  # (Q, k), (N,) -> (Q, N)
        return lut_i[:, c_i]

    g = jax.vmap(per_sub, in_axes=(1, 1))(lut.astype(jnp.float32), c)
    return jnp.sum(g, axis=0)


def hamming_ref(q_codes: Array, x_codes: Array) -> Array:
    """Packed Hamming: (Q, W) uint32 × (N, W) uint32 -> (Q, N) int32."""
    x = jnp.bitwise_xor(q_codes[:, None, :], x_codes[None, :, :])
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def beam_gather_l2_ref(q: Array, ids: Array, corpus: Array) -> Array:
    """Fused gather-distance: q (D,) × ids (L,) × corpus (N, D) -> (L,).

    Row gather followed by squared L2, written as the *same* float ops the
    in-loop traversal used historically (rows - q, square, sum) so the
    wide-beam search at width=1 reproduces the single-pop path bit-for-bit.
    """
    rows = corpus[ids]                     # (L, D)
    d = rows - q[None, :]
    return jnp.sum(d * d, axis=-1)


def beam_gather_dot_ref(q: Array, ids: Array, corpus: Array) -> Array:
    """Fused gather-distance, negative inner product variant -> (L,)."""
    return -_mm(corpus[ids], q)


def beam_gather_adc_ref(lut: Array, ids: Array, codes: Array) -> Array:
    """Code-domain fused gather-ADC: lut (m, k) × ids (L,) × codes (N, m).

    out[l] = sum_i lut[i, codes[ids[l], i]] — the per-query PQ traversal
    distance, evaluated on uint codes instead of float32 reconstructions.
    """
    m = lut.shape[0]
    rows = codes[ids].astype(jnp.int32)    # (L, m)
    gathered = lut.astype(jnp.float32)[jnp.arange(m)[None, :], rows]
    return jnp.sum(gathered, axis=-1)


def beam_gather_hamming_ref(q_code: Array, ids: Array, codes: Array) -> Array:
    """Code-domain fused gather-Hamming: q_code (W,) uint32 × ids (L,) ×
    codes (N, W) uint32 -> (L,) int32 popcount distances."""
    rows = codes[ids]                      # (L, W)
    x = jnp.bitwise_xor(rows, q_code[None, :])
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def pair_gather_l2_ref(ids: Array, corpus: Array) -> Array:
    """Fused gather + pairwise squared L2: ids (C,) × corpus (N, D) ->
    (C, C).  The Alg-4 bulk prune consults row j to test whether candidate
    j is closer to the query than to any already-selected candidate.
    Norm-expansion form (never materializes a (C, C, D) diff tensor — this
    oracle runs under vmap over prune batches on the CPU fallback path)."""
    rows = corpus[ids]                     # (C, D)
    g = _mm(rows, rows.T)
    nn = jnp.sum(rows * rows, axis=-1)
    return jnp.maximum(nn[:, None] + nn[None, :] - 2.0 * g, 0.0)


def pair_gather_dot_ref(ids: Array, corpus: Array) -> Array:
    """Fused gather + pairwise negated inner product -> (C, C)."""
    rows = corpus[ids]
    return -_mm(rows, rows.T)


def slstm_sequence_ref(gates_x: Array, r: Array, b: Array,
                       n_heads: int) -> Array:
    """Stabilised exp-gate sLSTM over a sequence (scan of the model cell).

    gates_x (B, S, 4d), r (4, H, blk, blk), b (4d,) -> h (B, S, d).
    Semantics identical to repro.models.recurrent._slstm_cell.
    """
    b_sz, s, d4 = gates_x.shape
    d = d4 // 4
    blk = d // n_heads

    def step(state, g_t):
        h, c, n, m = state
        hh = h.reshape(b_sz, n_heads, blk)
        rec = jnp.einsum("bnk,gnkl->bgnl", hh,
                         r.astype(jnp.float32)).reshape(b_sz, 4 * d)
        pre = g_t.astype(jnp.float32) + rec + b
        gi, gf, gz, go = jnp.split(pre, 4, axis=-1)
        logf = jax.nn.log_sigmoid(gf)
        m_new = jnp.maximum(logf + m, gi)
        i_p = jnp.exp(gi - m_new)
        f_p = jnp.exp(logf + m - m_new)
        c_new = f_p * c + i_p * jnp.tanh(gz)
        n_new = f_p * n + i_p
        h_new = jax.nn.sigmoid(go) * c_new / jnp.maximum(n_new, 1e-6)
        return (h_new, c_new, n_new, m_new), h_new

    init = (jnp.zeros((b_sz, d)), jnp.zeros((b_sz, d)),
            jnp.zeros((b_sz, d)), jnp.full((b_sz, d), -1e30))
    _, hs = jax.lax.scan(step, init, gates_x.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2).astype(gates_x.dtype)
