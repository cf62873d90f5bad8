"""Batched alias-table sampling over B stacked tables: the pool's bulk
PRNG drain.

The O(1)-per-draw counterpart of ``forest_sample.forest_sample_batched``:
lane ``q`` resolves uniform ``xi[q]`` in distribution ``dist_id[q]``'s
packed ``(q, alias)`` row with exactly two flat row-offset gathers and one
comparison — no descent, no loop. This is the Lehmann et al. (2021) packed
layout applied to the mixed-batch drain, and the reason the pool carries a
per-tenant *method*: this path is ~100x the forest drain's throughput but
non-monotone (it destroys QMC stratification — see the fig-1 discrepancy
bench), so only PRNG tenants route here.

Same lane conventions as the forest kernels: ``dist_id < 0`` marks a
sentinel (padding) lane resolving to 0 without touching any row (a freed
row's cleared table must never be read as live), and ``coalesce=True``
runs the stable sort-by-row bucketing pre-pass (elementwise identical
either way). The within-cell fraction is clamped into [0, 1) with the same
constant as :func:`repro.core.alias.sample_alias`, so ``xi == 1.0`` (an
upcast float64 uniform) behaves as the limit draw instead of
unconditionally taking the last cell's alias.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.alias import ALIAS_FRAC_MAX

from .forest_sample import _bucket_order


def _alias_sample_kernel(q_ref, a_ref, did_ref, xi_ref, o_ref, *, n: int):
    did_raw = did_ref[...]
    valid = did_raw >= 0
    did = jnp.where(valid, did_raw, 0)
    scaled = xi_ref[...] * jnp.float32(n)
    cell = jnp.clip(scaled.astype(jnp.int32), 0, n - 1)
    frac = jnp.clip(
        scaled - cell.astype(jnp.float32), 0.0, jnp.float32(ALIAS_FRAC_MAX)
    )
    flat = did * n + cell
    qv = jnp.take(q_ref[...].reshape(-1), flat)
    av = jnp.take(a_ref[...].reshape(-1), flat)
    o_ref[...] = jnp.where(valid, jnp.where(frac < qv, cell, av), 0)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "coalesce"))
def alias_sample_batched(
    q: jax.Array,
    alias: jax.Array,
    dist_id: jax.Array,
    xi: jax.Array,
    block: int = 2048,
    *,
    interpret: bool,
    coalesce: bool = True,
) -> jax.Array:
    """Bulk sampling over B stacked alias tables: ``(dist_id, xi)`` pairs
    (Q,) -> row-local indices (Q,) int32, one launch for the mixed batch.

    ``q`` (B, n) f32 / ``alias`` (B, n) i32 are the stacked
    ``BatchedAlias`` arrays; the whole stack stays VMEM-resident (8 bytes
    per cell — half a forest row) while lanes stream through in tiles.
    Lanes with ``dist_id < 0`` are sentinels resolved to 0; block padding
    uses them too. Elementwise equal to the float32 numpy oracle
    ``core.alias.np_sample_alias_f32`` (identical IEEE arithmetic)."""
    (Q,) = xi.shape
    B, n = q.shape
    Qp = (Q + block - 1) // block * block
    xip = jnp.pad(xi, (0, Qp - Q))
    didp = jnp.pad(
        jnp.minimum(dist_id.astype(jnp.int32), B - 1), (0, Qp - Q),
        constant_values=-1,
    )
    if coalesce:
        order, inv = _bucket_order(didp)
        didp, xip = didp[order], xip[order]
    full2 = lambda r, c: pl.BlockSpec((r, c), lambda i: (0, 0))
    lane = pl.BlockSpec((block,), lambda i: (i,))
    out = pl.pallas_call(
        functools.partial(_alias_sample_kernel, n=n),
        grid=(Qp // block,),
        in_specs=[full2(B, n), full2(B, n), lane, lane],
        out_specs=lane,
        out_shape=jax.ShapeDtypeStruct((Qp,), jnp.int32),
        interpret=interpret,
    )(q, alias.astype(jnp.int32), didp, xip)
    if coalesce:
        out = out[inv]
    return out[:Q]
