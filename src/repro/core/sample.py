"""Inverse-CDF samplers: the paper's Algorithm 2 plus every surveyed baseline.

All JAX samplers are batch-vectorized; divergence is handled by per-lane
predication inside a ``while_loop``, so the per-batch cost is the max lane
cost — exactly the warp-synchronized cost model (``average_32``) the paper
optimizes for. Numpy twins with exact *memory-load counting* live in
:mod:`repro.core.counting` and reproduce Table 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..trace import scope
from .forest import MAX_DEPTH, RadixForest


def sample_linear(cdf: jax.Array, xi: jax.Array) -> jax.Array:
    """O(n) linear scan (Sec. 2.1). For small n / reference only."""
    # i = #{k : cdf[k+1] <= xi}
    return jnp.sum(cdf[1:-1][None, :] <= xi[:, None], axis=-1).astype(jnp.int32)


def sample_binary(cdf: jax.Array, xi: jax.Array) -> jax.Array:
    """O(log n) bisection (Sec. 2.2)."""
    i = jnp.searchsorted(cdf[1:], xi, side="right").astype(jnp.int32)
    return jnp.clip(i, 0, cdf.shape[0] - 2)


# Trip cap of the degenerate-cell bisection: enough to halve any int32 span.
MAX_BISECT = 32


def _gather(a: jax.Array, i: jax.Array) -> jax.Array:
    return a[i]


def bisect_trip(cdf, xi, lo, hi, *, at=_gather):
    """One trip of balanced index bisection towards the i in [lo, hi] with
    ``cdf[i] <= xi < cdf[i+1]``; a lane with ``lo >= hi`` keeps its ``lo``.
    ``at(table, i)`` gathers a table at lane indices."""
    mid = (lo + hi + 1) >> 1
    ge = xi >= at(cdf, mid)
    return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid - 1)


def bisect(cdf, xi, lo, hi, *, at=_gather):
    """Balanced index bisection, the paper's logarithmic-worst-case guard for
    degenerate cells: ``(i, trips)``. The loop stops when every lane is
    resolved (``lo >= hi``), after at most :data:`MAX_BISECT` trips, with the
    answer of the fixed-trip loop; a caller gives an unflagged lane
    ``hi = lo`` so that it costs no trip."""

    def cond(state):
        lo, hi, it = state
        return jnp.any(lo < hi) & (it < MAX_BISECT)

    def body(state):
        lo, hi, it = state
        with scope("descent.bisect_trip"):
            return (*bisect_trip(cdf, xi, lo, hi, at=at), it + 1)

    lo, _, trips = jax.lax.while_loop(cond, body, (lo, hi, jnp.int32(0)))
    return lo, trips


def descent_trip(cdf, left, right, xi, j, *, at=_gather, node=None):
    """One trip of Algorithm 2's descent: a lane at node ``j >= 0`` moves to
    its left child iff ``xi < cdf[j]``, else to its right; a lane at a leaf
    (``j < 0``) stays. ``node(j)`` gives the index of node ``j`` in ``cdf``
    and in ``left``/``right`` (a shard holds its children in a window);
    by default both are ``j`` clipped into the child tables."""
    if node is None:
        key = child = jnp.clip(j, 0, left.shape[-1] - 1)
    else:
        key, child = node(j)
    go_left = xi < at(cdf, key)
    nxt = jnp.where(go_left, at(left, child), at(right, child))
    return jnp.where(j >= 0, nxt, j)


def descend(cdf, left, right, xi, j, *, at=_gather, node=None):
    """Algorithm 2's descent from node refs ``j`` (leaf refs ``~i`` are
    resolved): ``(idx, trips)``. The loop stops when its deepest lane
    reaches a leaf, after at most ``MAX_DEPTH`` trips; a lane at a leaf is
    left unchanged by a trip, so the answer is that of the fixed-trip loop.
    Every XLA sampler of the repo runs it, each with its own start nodes and
    gathers (:func:`descent_trip`)."""

    def cond(state):
        j, it = state
        return jnp.any(j >= 0) & (it < MAX_DEPTH)

    def body(state):
        j, it = state
        with scope("descent.trip"):
            return descent_trip(cdf, left, right, xi, j, at=at, node=node), it + 1

    j, trips = jax.lax.while_loop(cond, body, (j, jnp.int32(0)))
    return ~j, trips


def sample_cutpoint_binary(
    cdf: jax.Array, cell_first: jax.Array, xi: jax.Array
) -> jax.Array:
    """Cutpoint Method with in-cell binary search (Sec. 2.5): O(1) average,
    O(log n) worst case. ``cell_first`` as built by the forest constructor
    ((m+1,), conservative last = first of next cell)."""
    m = cell_first.shape[0] - 1
    g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    return bisect(cdf, xi, cell_first[g], cell_first[g + 1])[0]


def sample_cutpoint_linear(
    cdf: jax.Array, cell_first: jax.Array, xi: jax.Array, max_scan: int
) -> jax.Array:
    """Cutpoint Method with in-cell linear search (Sec. 2.5, original)."""
    m = cell_first.shape[0] - 1
    g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    i = cell_first[g]

    def body(_, i):
        done = xi < cdf[jnp.clip(i + 1, 0, cdf.shape[0] - 1)]
        return jnp.where(done, i, i + 1)

    return jax.lax.fori_loop(0, max_scan, body, i)


@functools.partial(jax.jit, static_argnames=("use_fallback",))
def sample_forest(
    forest: RadixForest, xi: jax.Array, use_fallback: bool = True
) -> jax.Array:
    """Algorithm 2: guide-table lookup, then radix-tree descent.

    Node index doubles as CDF index: descend left iff ``xi < cdf[j]``.
    Leaf refs have the MSB set (two's complement ~i). Lanes in degenerate
    cells (``forest.fallback``) use balanced index bisection instead — the
    paper's logarithmic-worst-case guard.
    """
    m = forest.m
    g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    j = forest.table[g]
    if use_fallback:  # pre-resolve fallback lanes
        flagged = forest.fallback[g] & (j >= 0)
        lo = forest.cell_first[g]
        hi = jnp.where(flagged, forest.cell_first[g + 1], lo)
        j = jnp.where(flagged, ~bisect(forest.cdf, xi, lo, hi)[0], j)
    return descend(forest.cdf, forest.left, forest.right, xi, j)[0]


def sample_forest_with_stats(forest: RadixForest, xi: jax.Array):
    """As :func:`sample_forest` without the fallback, but also returns
    per-lane node-visit counts (loads beyond the guide-table load) — the
    Table-1 instrumentation. Each lane runs :func:`descend` alone (a vmap),
    so its trip count is its visit count."""
    m = forest.m
    g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    return jax.vmap(
        lambda x, j: descend(forest.cdf, forest.left, forest.right, x, j)
    )(xi, forest.table[g])
