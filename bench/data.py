"""Corpus and query pool drawn on the device from ``--seed``.

The arithmetic is that of ``repro.data.synthetic.embedding_like`` (topic /
subtopic / noise clusters over one power-law spectrum, the anisotropic
shape of sentence embeddings), written with ``jax.random`` so that the whole
draw is one jitted call on the chip instead of minutes of host work.  The
query pool is the tail of one ``rows + queries`` draw: the same clusters,
disjoint rows.  The same seed gives the same data on every run.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=None)
def _draw_fn():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=(
        "n", "dim", "topics", "subtopics", "decay", "sub_scale", "noise"))
    def draw(key, *, n, dim, topics, subtopics, decay, sub_scale, noise):
        k_top, k_sub, k_noise = jax.random.split(key, 3)
        std = jnp.arange(1, dim + 1, dtype=jnp.float32) ** (-decay / 2)
        centres = jax.random.normal(k_top, (topics, dim), jnp.float32) * std
        subs = jax.random.normal(k_sub, (topics, subtopics, dim),
                                 jnp.float32) * (std * sub_scale)
        chunk = block_rows(n)

        def block(i):
            key = jax.random.fold_in(k_noise, i)
            k_t, k_u, k_n = jax.random.split(key, 3)
            t = jax.random.randint(k_t, (chunk,), 0, topics)
            u = jax.random.randint(k_u, (chunk,), 0, subtopics)
            rows = jax.random.normal(k_n, (chunk, dim), jnp.float32)
            return rows * (std * noise) + centres[t] + subs[t, u]

        # block by block, so the device holds the output and one block
        out = jax.lax.map(block, jnp.arange(n // chunk))
        return out.reshape(n, dim)

    return draw


def block_rows(n: int, most: int = 1 << 16) -> int:
    """The largest divisor of ``n`` that is at most ``most``, a multiple of
    8 where one exists (whole sublane tiles: the blocks then stack into the
    output without a copy)."""
    divisors = [d for d in range(1, min(n, most) + 1) if n % d == 0]
    tiled = [d for d in divisors if d % 8 == 0]
    return max(tiled or divisors)


def draw(seed: int, rows: int, queries: int, dim: int,
         gen: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    """(corpus (rows, dim), query pool (queries, dim)) float32 on the host,
    drawn on the device in one call with the configuration's ``data``
    parameters."""
    import jax

    x = _draw_fn()(seed_key(seed), n=rows + queries, dim=dim,
                   topics=gen["topics"], subtopics=gen["subtopics"],
                   decay=float(gen["decay"]),
                   sub_scale=float(gen["sub_scale"]),
                   noise=float(gen["noise"]))
    host = np.asarray(jax.device_get(x))
    del x
    return host[:rows], host[rows:]
