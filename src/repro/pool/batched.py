"""Fused batched forest construction: B distributions in one launch.

The paper parallelizes construction *within* one distribution; the serving
north star needs thousands of *small* distributions built concurrently
(per-request token priors, per-cell densities, per-client mixtures), where a
launch per distribution wastes the machine. Hübschle-Schneider & Sanders
(2019) make the case that bulk/batched queries are the right granularity for
parallel samplers; this module applies the same logic to *construction*: the
whole build core (chunked CDF scan -> separator distances -> nearest-greater
descent -> cell trees) is data-parallel per distribution, so ``jax.vmap``
over a stacked ``(B, n)`` weight matrix turns B builds into one fused
program whose every row is **bit-identical** to an independent
``core.build_forest`` call (the differential tests in ``tests/test_pool.py``
pin this per weight family and size).

:class:`BatchedForest` is the packed-table layout Lehmann et al. (2021) show
batched GPU sampling wants: all B forests stacked row-major, so the batched
sampling kernel (:func:`repro.kernels.forest_sample.forest_sample_batched`)
resolves a mixed ``(dist_id, uniform)`` batch with flat row-offset gathers —
one launch, no per-distribution dispatch.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.alias import AliasTable
from repro.core.cdf import build_cdf
from repro.core.forest import RadixForest, forest_from_cdf


class BatchedAlias(NamedTuple):
    """B stacked packed alias tables over a shared size class — the
    O(1)-per-draw twin of :class:`BatchedForest` for PRNG tenants.

    Row ``b`` is exactly the :class:`repro.core.alias.AliasTable` of
    distribution ``b`` (``alias`` entries are row-local cell indices).
    Half the footprint of a forest row (8 bytes/cell) and two gathers per
    draw; the price is a non-monotone map, so QMC tenants stay on the
    forest stack."""

    q: jax.Array      # (B, n) f32 split point within each cell
    alias: jax.Array  # (B, n) i32 second interval of each cell

    @property
    def batch(self) -> int:
        return self.q.shape[0]

    @property
    def n(self) -> int:
        return self.q.shape[1]

    def row(self, b: int) -> AliasTable:
        """Single-distribution view (differential tests; serving drains
        through the batched kernel)."""
        return AliasTable(self.q[b], self.alias[b])


class BatchedForest(NamedTuple):
    """B stacked radix forests over a shared (n, m) shape class.

    Row ``b`` is exactly the :class:`repro.core.forest.RadixForest` of
    distribution ``b``: all references (node ids, leaf refs ``~i``, guide
    entries) are *row-local*, so sampling returns per-distribution interval
    indices. Stacking is the whole point — one compiled program per (B, n, m)
    shape serves every distribution in the batch."""

    cdf: jax.Array         # (B, n+1) f32
    table: jax.Array       # (B, m)   i32
    left: jax.Array        # (B, n)   i32
    right: jax.Array       # (B, n)   i32
    cell_first: jax.Array  # (B, m+1) i32
    fallback: jax.Array    # (B, m)   bool

    @property
    def batch(self) -> int:
        return self.left.shape[0]

    @property
    def n(self) -> int:
        return self.left.shape[1]

    @property
    def m(self) -> int:
        return self.table.shape[1]

    def row(self, b: int) -> RadixForest:
        """Single-distribution view (host-side debugging / differential
        tests; sampling should go through the batched kernel instead)."""
        return RadixForest(*(x[b] for x in self))


@functools.partial(jax.jit, static_argnames=("m", "fallback_slack"))
def build_forest_batched_from_cdf(
    cdf: jax.Array, m: int, fallback_slack: int = 2
) -> BatchedForest:
    """(B, n+1) stacked CDFs -> B forests in one fused program."""
    f = jax.vmap(lambda c: forest_from_cdf(c, m, fallback_slack))(
        jnp.asarray(cdf, jnp.float32)
    )
    return BatchedForest(*f)


@functools.partial(jax.jit, static_argnames=("m", "fallback_slack"))
def build_forest_batched(
    weights: jax.Array, m: int, fallback_slack: int = 2
) -> BatchedForest:
    """The fused end-to-end batched build: (B, n) weights -> B forests.

    Each row runs the *same* chunked-scan CDF + forest build as
    ``core.build_forest`` (the scan grid is per-row, so vmapping does not
    reassociate any addition) — row ``b`` of the result is bit-identical to
    ``build_forest(weights[b], m)``."""
    f = jax.vmap(lambda w: forest_from_cdf(build_cdf(w), m, fallback_slack))(
        jnp.asarray(weights, jnp.float32)
    )
    return BatchedForest(*f)


@jax.jit
def batched_from_row_forest(rows, cdf_rows: jax.Array) -> BatchedForest:
    """Rewrap a flat :class:`repro.core.forest2d.RowForest` as a
    :class:`BatchedForest` — one-pass multi-row construction feeding the
    batched descent kernel.

    The flat builder emits *global* references (leaf ``~i`` and node ids
    index the flat ``(R*W,)`` arrays, guide entries index ``(R*m,)`` cells);
    the batched kernel wants *row-local* ones. Because row ``r``'s nodes and
    leaves all live in ``[r*W, (r+1)*W)``, the rewrite is a per-row offset
    subtraction: for a reference ``v`` in row ``r`` with ``off = r*W``,
    ``local = v - off`` when ``v >= 0`` (node id) and ``v + off`` when
    ``v < 0`` (leaf, since ``~(i - off) = ~i + off``). Row ``r`` of the
    result is bit-identical to ``forest_from_cdf(cdf_rows[r], m)`` — the
    spatial conformance suite pins this, fallback flags included.

    ``cdf_rows`` must be the exact ``(R, W+1)`` CDF stack the RowForest was
    built from: the batched kernel compares against the *unclamped* CDF
    (matching single builds), not the clamped flat ``data``."""
    R, W1 = cdf_rows.shape  # static, unlike the RowForest int leaves
    W = W1 - 1
    m = rows.table.shape[0] // R
    off = (jnp.arange(R, dtype=jnp.int32) * W)[:, None]

    def local(v):
        return jnp.where(v >= 0, v - off, v + off)

    cell_first = jnp.concatenate(
        [
            rows.cell_first[:-1].reshape(R, m) - off,
            jnp.full((R, 1), W - 1, jnp.int32),
        ],
        axis=1,
    )
    return BatchedForest(
        cdf=jnp.asarray(cdf_rows, jnp.float32),
        table=local(rows.table.reshape(R, m)),
        left=local(rows.left.reshape(R, W)),
        right=local(rows.right.reshape(R, W)),
        cell_first=cell_first,
        fallback=rows.fallback.reshape(R, m),
    )


def sample_forest_batched(
    forest: BatchedForest,
    dist_id: jax.Array,
    xi: jax.Array,
    use_pallas: bool | None = None,
) -> jax.Array:
    """Bulk mixed-batch sampling: draw ``q`` resolves uniform ``xi[q]`` in
    distribution ``dist_id[q]``'s tree — one launch for the whole batch.
    Thin re-export of :func:`repro.kernels.ops.forest_sample_batched` so
    pool callers never import the kernel layer directly."""
    from repro.kernels import ops

    return ops.forest_sample_batched(forest, dist_id, xi, use_pallas=use_pallas)


def build_alias_batched(weights: jax.Array,
                        use_pallas: bool | None = None) -> BatchedAlias:
    """The fused batched alias build: (B, n) weights -> B packed tables in
    one program (``kernels.alias_build``; the ref and kernel paths share
    the row core, so both are bit-identical). Rows with exact dyadic
    weights match ``core.alias.build_alias_parallel`` bit for bit."""
    from repro.kernels import ops

    return BatchedAlias(*ops.alias_build_batched(
        jnp.asarray(weights, jnp.float32), use_pallas=use_pallas
    ))


def sample_alias_batched(
    table: BatchedAlias,
    dist_id: jax.Array,
    xi: jax.Array,
    use_pallas: bool | None = None,
) -> jax.Array:
    """Bulk mixed-batch alias drain: draw ``q`` resolves uniform ``xi[q]``
    in distribution ``dist_id[q]``'s packed table — O(1) per lane, one
    launch for the whole batch. Thin re-export of
    :func:`repro.kernels.ops.alias_sample_batched`."""
    from repro.kernels import ops

    return ops.alias_sample_batched(table, dist_id, xi, use_pallas=use_pallas)
