"""Batched split-and-pack alias construction: B tables in one launch.

The paper notes that known alias-table builds are serial; the pool's
admission waves need thousands of small tables built concurrently. This
kernel vectorizes :func:`repro.core.alias.build_alias_parallel`'s geometric
formulation over a stacked ``(B, n)`` weight matrix — the construction twin
of ``pool/batched.py``'s fused forest build, feeding the packed
:class:`~repro.pool.batched.BatchedAlias` arenas that Lehmann et al. (2021)
show batched GPU sampling wants.

The formulation is **positional**, which is what makes it kernel-shaped:
instead of compacting lights/heavies onto separate tapes (a scatter), the
demand/supply prefixes are prefix sums of *masked* per-cell terms over the
original cell order, then pinned bit-flat between member cells by an
exactly-associative prefix max over member-only values (a reassociated
parallel scan can wobble by 1 ulp across a ``+0.0`` term). Because the
pinned tapes only increase at member cells, a search over them lands
directly on the ORIGINAL index of the covering heavy. The whole build is
then two prefix sums, two prefix maxes, one rotation sweep that does the
three searches, and elementwise selects: no scatter, no sort, no
data-dependent shapes.

Every step is something Mosaic lowers. Mosaic has no ``cumsum``/``cummax``
and gathers only within one vreg, so the prefix ops are the log-step
shifted combines of :mod:`repro.kernels.cdf_scan`, and each search is a
count: on a sorted tape, ``searchsorted(a, v)`` is the number of entries
``<= v`` (or ``< v``), accumulated while the tape rotates one lane per step
past every query. That sweep is O(n^2 / 1024) vector ops per 8-row block.

Boundary policy matches the fixed host build exactly: zero-surplus heavies
(``n*p == 1``) supply an empty interval, owe no debt (``surplus > 0``
gates it), and are skipped by the strictly-greater searches, so exact
dyadic weights — where supply ends coincide with demand boundaries — pack
without breaking the telescoping-mass invariant. The jnp reference
(:func:`repro.kernels.ref.ref_alias_build_batched`) calls the SAME row
core, so kernel/ref agreement is structural, and the dyadic differential
tests additionally pin both against ``build_alias_parallel`` row by row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .cdf_scan import lane_cummax, lane_cumsum


def _tape_searches(D: jax.Array, S: jax.Array, light_start: jax.Array):
    """The three tape searches of the build, by counting in one sweep.

    ``D``/``S`` (R, n) are the pinned (non-decreasing) demand/supply tapes.
    Returns, per cell, with ``x = S``:

    * ``p_light`` = ``searchsorted(S, light_start, side="right")``;
    * ``pj``      = ``searchsorted(D, x, side="left")``;
    * ``Dj``      = ``D[pj]`` where ``pj < n`` (the least tape value
      ``>= x``; ``+inf`` otherwise);
    * ``p_nxt``   = ``searchsorted(S, x, side="right")``.

    Both tapes rotate one lane per step, so after n steps every query has
    met every tape entry. Pure comparisons and mins: exact on any backend."""
    n = D.shape[-1]
    inf = jnp.float32(jnp.inf)
    zero = jnp.zeros(D.shape, jnp.int32)

    def body(_, state):
        d, s, p_light, pj, dj, p_nxt = state
        p_light = p_light + (s <= light_start).astype(jnp.int32)
        pj = pj + (d < S).astype(jnp.int32)
        dj = jnp.minimum(dj, jnp.where(d >= S, d, inf))
        p_nxt = p_nxt + (s <= S).astype(jnp.int32)
        d = jnp.roll(d, 1, axis=-1)
        s = jnp.roll(s, 1, axis=-1)
        return d, s, p_light, pj, dj, p_nxt

    init = (D, S, zero, zero, jnp.full(D.shape, inf), zero)
    _, _, p_light, pj, dj, p_nxt = jax.lax.fori_loop(0, n, body, init)
    return p_light, pj, dj, p_nxt


def alias_split_pack_rows(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The shared build core: (R, n) weights -> ``(q, alias)`` (R, n) rows.

    Both the Pallas kernel body and the jnp reference run THIS function, so
    their agreement is structural. Rows are independent; zero-weight cells
    (the pool's padding) become full-deficit lights with ``q == 0`` — no
    draw ever resolves own-side in one, and they are never heavy so never
    an alias target — so padded cells are unreachable, exactly like the
    forest arena's zero-width intervals."""
    R, n = w.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    wsum = jnp.sum(w, axis=-1, keepdims=True)
    npi = w / wsum * jnp.float32(n)
    light = npi < 1.0
    heavy = ~light
    dvals = jnp.where(light, 1.0 - npi, 0.0)   # per-cell demand (lights)
    svals = jnp.where(heavy, npi - 1.0, 0.0)   # per-cell surplus (heavies)
    D = lane_cumsum(dvals)                     # positional demand prefix
    S = lane_cumsum(svals)                     # positional supply prefix
    # Pin tape flatness between member cells: the prefix sum is a
    # reassociated parallel scan, so it can wobble by 1 ulp across a +0.0
    # term — enough for a strict search to land on a NON-member position (a
    # heavy's debt aliased to a light). max is exactly associative, so
    # propagating each member's own prefix with a prefix max makes flat
    # segments bit-flat by construction; member positions keep their own
    # prefix value.
    ninf = jnp.float32(-jnp.inf)
    D = lane_cummax(jnp.where(light, D, ninf), ninf)
    S = lane_cummax(jnp.where(heavy, S, ninf), ninf)
    total = jnp.minimum(D[:, -1:], S[:, -1:])
    has_both = jnp.any(light, axis=-1, keepdims=True) & jnp.any(
        heavy, axis=-1, keepdims=True
    )
    last_heavy = jnp.maximum(
        jnp.max(jnp.where(heavy, pos, -1), axis=-1, keepdims=True), 0
    )
    # lights: alias = the heavy whose supply interval contains the START of
    # the light's demand interval. The positional prefix only increases at
    # positive-surplus heavies, so the first strictly-greater position IS
    # that heavy's original index (zero-surplus heavies never cross).
    # heavies: where a heavy's own supply ends (x = S) inside a light's
    # demand interval it owes the remainder (debt) to the next supplying
    # heavy.
    x = S
    p_light, pj, Dj, p_nxt = _tape_searches(D, S, D - dvals)
    alias_light = jnp.where(p_light < n, jnp.minimum(p_light, n - 1), last_heavy)
    inside = (pj < n) & (x < total) & (svals > 0.0)
    debt = jnp.clip(jnp.where(inside, Dj - x, 0.0), 0.0, 1.0)
    nxt = jnp.where(p_nxt < n, jnp.minimum(p_nxt, n - 1), last_heavy)
    alias_heavy = jnp.where(debt > 0.0, nxt, pos)

    q = jnp.where(light, npi, 1.0 - debt)
    alias = jnp.where(light, alias_light, alias_heavy)
    # rows without both sides (exactly uniform, or single-sided rounding)
    # are already exact: the identity table
    q = jnp.where(has_both, q, jnp.ones_like(q))
    alias = jnp.where(has_both, alias, jnp.broadcast_to(pos, alias.shape))
    return q.astype(jnp.float32), alias.astype(jnp.int32)


def _alias_build_kernel(w_ref, q_ref, a_ref):
    q, a = alias_split_pack_rows(w_ref[...])
    q_ref[...] = q
    a_ref[...] = a


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def alias_build_batched(
    weights: jax.Array, block_b: int = 8, *, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    """(B, n) stacked weights -> packed ``(q, alias)`` (B, n) f32/i32 stacks.

    Grid over row blocks; each program instance packs ``block_b`` whole
    rows from VMEM (rows are independent, so blocking cannot change bits).
    The batch is padded with uniform rows to a ``block_b`` multiple and
    trimmed on the way out."""
    B, n = weights.shape
    Bp = (B + block_b - 1) // block_b * block_b
    wp = jnp.pad(
        jnp.asarray(weights, jnp.float32), ((0, Bp - B), (0, 0)),
        constant_values=1.0,  # padding rows: uniform => identity tables
    )
    q, a = pl.pallas_call(
        _alias_build_kernel,
        grid=(Bp // block_b,),
        in_specs=[pl.BlockSpec((block_b, n), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Bp, n), jnp.float32),
            jax.ShapeDtypeStruct((Bp, n), jnp.int32),
        ),
        interpret=interpret,
    )(wp)
    return q[:B], a[:B]
