"""Plain reference for a 2-D piecewise-constant map sampled as a product.

Row ``r`` is drawn by inversion of the float64 marginal over row sums, then
column ``c`` by inversion of row ``r``'s float64 conditional CDF. An answer
``(r, c)`` to a point ``(u, v)`` is judged by how far ``u`` lies outside
the marginal interval ``r`` and ``v`` outside row ``r``'s interval ``c``.
The control is this reference put in the program's place one precision
below float32: both CDFs stored in bfloat16.
"""
from __future__ import annotations

import functools

import numpy as np

from bench.reference import forest1d


class Tables:
    """Float64 marginal (H+1,) and conditional (H, W+1) CDFs of ``img``."""

    def __init__(self, img):
        img = np.asarray(img, np.float64)
        self.marginal = forest1d.cdf(img.sum(axis=1))
        c = np.cumsum(img, axis=1)
        self.conditional = np.concatenate(
            [np.zeros((img.shape[0], 1)), c / c[:, -1:]], axis=1)

    def gaps(self, u, v, row, col) -> tuple[float, float]:
        """Largest marginal and conditional interval gaps over the answers."""
        H, W1 = self.conditional.shape
        row = np.asarray(row, np.int64)
        col = np.asarray(col, np.int64)
        row_gap = forest1d.interval_gap(self.marginal, u, row)
        ok = (row >= 0) & (row < H) & (col >= 0) & (col < W1 - 1)
        r = np.where(ok, row, 0)
        c = np.where(ok, col, 0)
        v = np.asarray(v, np.float64)
        lo = self.conditional[r, c]
        hi = self.conditional[r, c + 1]
        col_gap = np.where(ok, np.maximum(np.maximum(lo - v, v - hi), 0.0), 1.0)
        return float(row_gap.max(initial=0.0)), float(col_gap.max(initial=0.0))


@functools.cache
def _control():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def control_tables(img):
        img = img.astype(jnp.float32)
        m = jnp.cumsum(img.sum(axis=1))
        marg = jnp.concatenate([jnp.zeros((1,), jnp.float32), m / m[-1]])
        c = jnp.cumsum(img, axis=1)
        cond = jnp.concatenate(
            [jnp.zeros((img.shape[0], 1), jnp.float32), c / c[:, -1:]], axis=1)
        return marg.astype(jnp.bfloat16), cond.astype(jnp.bfloat16)

    @jax.jit
    def control_sample(tables, u, v):
        marg, cond = tables
        H, W1 = cond.shape
        row = jnp.searchsorted(marg.astype(jnp.float32), u, side="right") - 1
        row = jnp.clip(row, 0, H - 1)
        lo = jnp.zeros_like(row)
        hi = jnp.full_like(row, W1 - 2)

        def body(_, s):
            lo, hi = s
            mid = (lo + hi + 1) >> 1
            ge = v >= cond[row, mid].astype(jnp.float32)
            return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid - 1)

        lo, _ = jax.lax.fori_loop(0, 32, body, (lo, hi))
        return row.astype(jnp.int32), lo.astype(jnp.int32)

    return control_tables, control_sample


def control_tables(img):
    """The control's state: float32 marginal and conditional CDFs in bf16."""
    return _control()[0](img)


def control_sample(tables, u, v):
    """The control's answers: bisection over the bf16 CDFs."""
    return _control()[1](tables, u, v)
