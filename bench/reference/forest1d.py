"""Plain reference for one discrete distribution sampled by inversion.

Interval ``i`` of weights ``w`` is ``[P_i, P_{i+1})`` with ``P`` the
float64 prefix sum of ``w`` over its total. An answer ``i`` to a uniform
``xi`` is judged by how far ``xi`` lies outside the reference's interval
``i`` (0 when inside). The control is this reference put in the program's
place one precision below float32: the CDF stored in bfloat16.
"""
from __future__ import annotations

import functools

import numpy as np


def cdf(weights) -> np.ndarray:
    """(n,) weights -> (n+1,) float64 CDF with exact 0 and 1 ends."""
    c = np.cumsum(np.asarray(weights, np.float64))
    return np.concatenate([[0.0], c / c[-1]])


def interval_gap(cdf64: np.ndarray, xi, idx) -> np.ndarray:
    """Per answer: distance from ``xi`` to the reference interval ``idx``;
    1 for an index outside the distribution."""
    xi = np.asarray(xi, np.float64)
    idx = np.asarray(idx, np.int64)
    n = len(cdf64) - 1
    ok = (idx >= 0) & (idx < n)
    i = np.where(ok, idx, 0)
    gap = np.maximum(np.maximum(cdf64[i] - xi, xi - cdf64[i + 1]), 0.0)
    return np.where(ok, gap, 1.0)


def cdf_gap(cdf_program, cdf64: np.ndarray) -> float:
    """Largest distance between the program's CDF and the reference's."""
    c = np.asarray(cdf_program, np.float64)
    if c.shape != cdf64.shape:
        return 1.0
    return float(np.max(np.abs(c - cdf64)))


@functools.cache
def _control():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def control_cdf(weights):
        c = jnp.cumsum(weights.astype(jnp.float32))
        c = jnp.concatenate([jnp.zeros((1,), jnp.float32), c / c[-1]])
        return c.astype(jnp.bfloat16)

    @jax.jit
    def control_sample(cdf_b, xi):
        i = jnp.searchsorted(cdf_b.astype(jnp.float32), xi, side="right") - 1
        return jnp.clip(i, 0, cdf_b.shape[0] - 2).astype(jnp.int32)

    return control_cdf, control_sample


def control_cdf(weights):
    """The control's state: the float32 prefix sum stored in bfloat16."""
    return _control()[0](weights)


def control_sample(cdf_b, xi):
    """The control's answers: inversion by bisection over the bf16 CDF."""
    return _control()[1](cdf_b, xi)
