"""Unigram counts of a 3M-word vocabulary, made on the device from the seed.

Zipf by rank (s = 1) over ``corpus_tokens`` words, times a seeded lognormal
factor, floored at ``min_count`` and sorted by count (word2vec sorts its
vocabulary so). The sampler draws from ``counts ** power``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic


def make(cfg: dict, seed: int) -> jax.Array:
    n = int(cfg["vocab_size"])
    harmonic = float(np.log(n) + np.euler_gamma + 0.5 / n)
    top = float(cfg["corpus_tokens"]) / harmonic

    @jax.jit
    def counts(key):
        r = jnp.arange(1, n + 1, dtype=jnp.float32)
        z = jax.random.normal(key, (n,), jnp.float32)
        c = jnp.floor(top / r * jnp.exp(jnp.float32(cfg["count_jitter_sigma"]) * z))
        c = jnp.maximum(c, jnp.float32(cfg["min_count"]))
        return -jnp.sort(-c)

    return counts(traffic.key(seed, 0))
