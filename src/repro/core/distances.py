"""Distance / similarity metrics for Quantixar.

The paper (§I, §III-A) uses cosine similarity as the default metric — chosen
for resilience to the curse of dimensionality — with L2 and inner-product as
alternatives, and Hamming distance over binary-quantized codes.

All functions are batched and jit-friendly: queries ``(Q, D)`` against a corpus
``(N, D)`` produce a ``(Q, N)`` distance matrix.  Smaller distance == closer,
for every metric (similarities are negated) so that downstream top-k code is
metric-agnostic.

The hot pairwise paths are expressed as a single GEMM plus rank-1 corrections
(``‖x−y‖² = ‖x‖² + ‖y‖² − 2·x·y``) so that on TPU they lower onto the MXU; the
Pallas kernels in :mod:`repro.kernels` implement the same contraction with
explicit VMEM tiling for the perf-critical scan.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

Array = jax.Array
# f32 matmuls at full precision: the TPU's default rounds f32 operands to
# bf16, which would make the exact (flat) scan and the rescore inexact
HIGHEST = jax.lax.Precision.HIGHEST

#: Registry of metric name -> pairwise fn (queries (Q,D), corpus (N,D)) -> (Q,N)
_METRICS: Dict[str, Callable[[Array, Array], Array]] = {}


def register_metric(name: str):
    def deco(fn):
        _METRICS[name] = fn
        return fn

    return deco


def get_metric(name: str) -> Callable[[Array, Array], Array]:
    try:
        return _METRICS[name]
    except KeyError:  # pragma: no cover - defensive
        raise ValueError(f"unknown metric {name!r}; have {sorted(_METRICS)}")


def available_metrics():
    return sorted(_METRICS)


# ---------------------------------------------------------------------------
# Float metrics
# ---------------------------------------------------------------------------

def l2_norm_sq(x: Array, axis: int = -1) -> Array:
    return jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axis)


def normalize(x: Array, eps: float = 1e-12) -> Array:
    """Unit-normalize rows (cosine preprocessing)."""
    x = x.astype(jnp.float32)
    n = jnp.sqrt(jnp.maximum(l2_norm_sq(x), eps))
    return x / n[..., None]


@register_metric("l2")
def pairwise_l2(queries: Array, corpus: Array) -> Array:
    """Squared L2 distances, GEMM formulation (MXU-friendly)."""
    q = queries.astype(jnp.float32)
    x = corpus.astype(jnp.float32)
    # (Q,N) = q2[:,None] + x2[None,:] - 2 q @ x.T  -- one big matmul.
    qq = l2_norm_sq(q)  # (Q,)
    xx = l2_norm_sq(x)  # (N,)
    cross = jnp.matmul(q, x.T, precision=HIGHEST)  # (Q,N) on the MXU
    d = qq[:, None] + xx[None, :] - 2.0 * cross
    return jnp.maximum(d, 0.0)  # clamp fp error


@register_metric("dot")
def pairwise_dot(queries: Array, corpus: Array) -> Array:
    """Negative inner product (so smaller == more similar)."""
    return -jnp.matmul(queries.astype(jnp.float32),
                       corpus.astype(jnp.float32).T, precision=HIGHEST)


@register_metric("cosine")
def pairwise_cosine(queries: Array, corpus: Array) -> Array:
    """Cosine *distance* = 1 - cosine similarity. Default Quantixar metric."""
    return 1.0 + pairwise_dot(normalize(queries), normalize(corpus))


# ---------------------------------------------------------------------------
# Hamming (packed binary codes, uint32 words)
# ---------------------------------------------------------------------------

@register_metric("hamming")
def pairwise_hamming(q_codes: Array, x_codes: Array) -> Array:
    """Hamming distance between packed binary codes.

    Args:
      q_codes: ``(Q, W)`` uint32 packed codes.
      x_codes: ``(N, W)`` uint32 packed codes.
    Returns:
      ``(Q, N)`` int32 bit-difference counts.
    """
    x = jnp.bitwise_xor(q_codes[:, None, :], x_codes[None, :, :])
    return jnp.sum(jax.lax.population_count(x), axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Single-pair conveniences (used by HNSW inner loops)
# ---------------------------------------------------------------------------

def point_l2(q: Array, x: Array) -> Array:
    d = q.astype(jnp.float32) - x.astype(jnp.float32)
    return jnp.sum(d * d, axis=-1)


def point_cosine(q: Array, x: Array) -> Array:
    return 1.0 - (normalize(q) * normalize(x)).sum(-1)


def point_dot(q: Array, x: Array) -> Array:
    return -(q.astype(jnp.float32) * x.astype(jnp.float32)).sum(-1)


POINT_METRICS = {"l2": point_l2, "cosine": point_cosine, "dot": point_dot}


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def brute_force_topk(queries: Array, corpus: Array, k: int, metric: str = "cosine"):
    """Exact top-k: the paper's Flat Index primitive.

    Returns (distances (Q,k) ascending, indices (Q,k)).
    """
    d = get_metric(metric)(queries, corpus)
    neg_d, idx = jax.lax.top_k(-d, k)  # top_k is max-k; negate for min-k
    return -neg_d, idx
