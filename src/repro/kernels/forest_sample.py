"""Algorithm 2 as a Pallas kernel: shared-distribution batch sampling.

The paper's primary workload: ONE distribution (environment map row, data
mixture, expert gate prior), MILLIONS of uniforms. Guide table + node arrays
+ CDF stay VMEM-resident (O(n) each; n = 2^20 f32 -> 4 MB/table); uniforms
stream through in tiles. The traversal runs as a fixed-trip predicated loop:
every lane advances until *all* lanes in the tile hit a leaf — the hardware
analogue of the paper's warp-synchronized cost (``average_32``), which is
precisely the quantity radix forests minimize, so the algorithm/hardware fit
is tighter on TPU than on the paper's GPUs.

Gathers (``jnp.take`` from VMEM) are the honest cost: one per lane per level.
Depth is bounded (<= ~34 for distinct float32 keys; build flags tied chains
into fallback cells which ops.py pre-resolves), so `depth` is static.

:func:`forest_sample_batched` is the multi-distribution twin (the
``repro.pool`` serving workload): B stacked forests resident at once, each
lane routed into its own tree by a per-lane ``dist_id`` row offset. Two
serving-path refinements live here:

* **Coalesced bucketing pre-pass** (``coalesce=True``): lanes are stably
  sorted by owning tree inside the jitted program before the kernel runs, so
  each tile walks draws against one (or few) trees — Steele & Tristan's
  butterfly-partial-sum observation applied to the mixed-batch drain: the
  scattered-gather traffic of an unsorted drain is the memory bottleneck.
  Results are scattered back through the inverse permutation, so the output
  is elementwise identical to the unsorted descent (the per-lane walk is
  order-independent), and differential tests compare both.
* **Sentinel lanes**: ``dist_id < 0`` marks a padding lane. Sentinel lanes
  start at leaf ``~0`` and never descend, so block-size padding cannot walk
  a freed (stale) row's tree. The dispatchers pad with the sentinel.

:func:`forest_sample_batched_streams` is the stream-aware drain: instead of
host-computed uniforms it takes per-lane QMC counter values and
Cranley-Patterson offset bits, and computes the base-2 radical inverse and
rotation *in-kernel* (exact 24-bit integer pipeline, ``core.lds.qmc_bits24``)
— the pool's full drain then needs no host-side uniform generation at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.lds import qmc_point


def _forest_kernel(
    cdf_ref, table_ref, left_ref, right_ref, *rest, depth: int, m: int, fb: bool
):
    if fb:
        cf_ref, fb_ref, xi_ref, o_ref = rest
    else:
        xi_ref, o_ref = rest
    xi = xi_ref[...]
    n = left_ref.shape[0]
    g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    j = jnp.take(table_ref[...], g, axis=0)
    cdf = cdf_ref[...]
    left = left_ref[...]
    right = right_ref[...]

    if fb:
        # Pre-resolve lanes in degenerate cells by balanced index bisection
        # (the paper's logarithmic-worst-case guard) — without this, tied
        # zero-width chains exceed any fixed `depth` and the descent below
        # returns an unresolved internal node. The SAME _bisect as
        # core.sample.sample_forest, so elementwise agreement is structural.
        from repro.core.sample import _bisect

        flagged = (jnp.take(fb_ref[...], g, axis=0) > 0) & (j >= 0)
        cf = cf_ref[...]
        bal = _bisect(cdf, xi, jnp.take(cf, g, axis=0), jnp.take(cf, g + 1, axis=0), 32)
        j = jnp.where(flagged, ~bal, j)

    def body(_, j):
        jj = jnp.clip(j, 0, n - 1)
        go_left = xi < jnp.take(cdf, jj, axis=0)
        nxt = jnp.where(go_left, jnp.take(left, jj, axis=0), jnp.take(right, jj, axis=0))
        return jnp.where(j >= 0, nxt, j)

    j = jax.lax.fori_loop(0, depth, body, j)
    o_ref[...] = ~j


def _forest_batched_kernel(
    cdf_ref, table_ref, left_ref, right_ref, *rest,
    depth: int, m: int, n: int, fb: bool, stream: bool,
):
    """Mixed-batch descent: lane q walks distribution dist_id[q]'s tree.

    The stacked tables stay VMEM-resident as full (B, ...) blocks; each lane
    resolves its own row by flat row-offset gathers (``dist * stride + idx``)
    — the packed-table trick that makes batched GPU sampling fast (Lehmann
    et al. 2021), here with the row id varying per lane so ONE launch drains
    draws against every distribution in the batch.

    ``dist_id < 0`` marks a sentinel (padding) lane: it resolves to leaf
    ``~0`` immediately, without walking any row's tree (a freed row's stale
    arrays must never be descended — after an evict they can hold tied
    chains deeper than ``depth`` with their fallback flags cleared).

    With ``stream=True`` the lane inputs are per-lane QMC counter values and
    24-bit Cranley-Patterson offsets instead of uniforms; the base-2 radical
    inverse + rotation run in-kernel (exact integer ops) and the kernel also
    writes the points it drew, so the host oracle can be asserted bit-equal.
    """
    if stream:
        if fb:
            cf_ref, fb_ref, did_ref, ctr_ref, off_ref, o_ref, xi_ref = rest
        else:
            did_ref, ctr_ref, off_ref, o_ref, xi_ref = rest
        xi = qmc_point(ctr_ref[...], off_ref[...])
        xi_ref[...] = xi
    else:
        if fb:
            cf_ref, fb_ref, did_ref, xi_ref_in, o_ref = rest
        else:
            did_ref, xi_ref_in, o_ref = rest
        xi = xi_ref_in[...]
    did_raw = did_ref[...]
    valid = did_raw >= 0
    did = jnp.where(valid, did_raw, 0)
    g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    cdf = cdf_ref[...].reshape(-1)      # (B*(n+1),)
    left = left_ref[...].reshape(-1)    # (B*n,)
    right = right_ref[...].reshape(-1)
    cbase = did * (n + 1)               # per-lane row offsets
    nbase = did * n
    # sentinel lanes start AT a leaf (~0 == -1): the descent below is inert
    j = jnp.where(valid, jnp.take(table_ref[...].reshape(-1), did * m + g), -1)

    if fb:
        # Same degenerate-cell pre-resolution as the shared-distribution
        # kernel, bisecting each lane's own CDF row (row-local indices).
        flagged = (jnp.take(fb_ref[...].reshape(-1), did * m + g) > 0) & (j >= 0)
        cf = cf_ref[...].reshape(-1)    # (B*(m+1),)
        lo = jnp.take(cf, did * (m + 1) + g)
        hi = jnp.take(cf, did * (m + 1) + g + 1)

        def bisect_body(_, state):
            lo, hi = state
            mid = (lo + hi + 1) >> 1
            ge = xi >= jnp.take(cdf, cbase + mid)
            return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid - 1)

        lo, _ = jax.lax.fori_loop(0, 32, bisect_body, (lo, hi))
        j = jnp.where(flagged, ~lo, j)

    def body(_, j):
        jj = jnp.clip(j, 0, n - 1)
        go_left = xi < jnp.take(cdf, cbase + jj)
        nxt = jnp.where(
            go_left, jnp.take(left, nbase + jj), jnp.take(right, nbase + jj)
        )
        return jnp.where(j >= 0, nxt, j)

    j = jax.lax.fori_loop(0, depth, body, j)
    o_ref[...] = ~j


def _bucket_order(did: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The coalescing pre-pass: a stable sort by owning tree. Returns the
    gather permutation and its inverse scatter permutation. Stability keeps
    the within-tree draw order, so the tiles walk contiguous per-tree runs
    (sentinel lanes, ``did < 0``, group in front — they never descend)."""
    order = jnp.argsort(did, stable=True)
    inv = jnp.argsort(order, stable=True)
    return order, inv


@functools.partial(
    jax.jit, static_argnames=("depth", "block", "interpret", "coalesce")
)
def forest_sample_batched(
    cdf: jax.Array,
    table: jax.Array,
    left: jax.Array,
    right: jax.Array,
    dist_id: jax.Array,
    xi: jax.Array,
    cell_first: jax.Array | None = None,
    fallback: jax.Array | None = None,
    depth: int = 40,
    block: int = 2048,
    *,
    interpret: bool,
    coalesce: bool = True,
) -> jax.Array:
    """Bulk sampling over B stacked forests: ``(dist_id, xi)`` pairs (Q,) ->
    row-local interval indices (Q,) int32, one launch for the mixed batch.

    Inputs are the stacked ``BatchedForest`` arrays (``cdf`` (B, n+1),
    ``table`` (B, m), ``left``/``right`` (B, n), optionally ``cell_first``
    (B, m+1) / ``fallback`` (B, m) for degenerate-cell pre-resolution —
    required whenever any row flagged a cell). VMEM budget is the whole
    stack (~B * n * 16B), which is exactly the pool's size-class regime:
    many small distributions sharing one resident table.

    ``dist_id < 0`` lanes are sentinels: resolved to 0 without descending
    any tree (block padding uses them too). ``coalesce=True`` (default)
    runs the bucketing pre-pass — stable sort by tree, descend coalesced
    per-tree tiles, scatter back — elementwise identical to the scattered
    walk; ``coalesce=False`` keeps the scattered order (the bench contrast).
    """
    (Q,) = xi.shape
    B, m = table.shape
    n = left.shape[1]
    fb = cell_first is not None and fallback is not None
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
    in_specs = [full2(B, n + 1), full2(B, m), full2(B, n), full2(B, n)]
    operands = [cdf, table, left, right]
    if fb:
        in_specs += [full2(B, m + 1), full2(B, m)]
        operands += [cell_first, fallback.astype(jnp.int32)]
    in_specs += [
        pl.BlockSpec((block,), lambda i: (i,)),
        pl.BlockSpec((block,), lambda i: (i,)),
    ]
    operands += [didp, xip]
    out = pl.pallas_call(
        functools.partial(
            _forest_batched_kernel, depth=depth, m=m, n=n, fb=fb,
            stream=False,
        ),
        grid=(Qp // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Qp,), jnp.int32),
        interpret=interpret,
    )(*operands)
    if coalesce:
        out = out[inv]
    return out[:Q]


@functools.partial(
    jax.jit, static_argnames=("depth", "block", "interpret", "coalesce")
)
def forest_sample_batched_streams(
    cdf: jax.Array,
    table: jax.Array,
    left: jax.Array,
    right: jax.Array,
    dist_id: jax.Array,
    counter: jax.Array,
    offset_bits: jax.Array,
    cell_first: jax.Array | None = None,
    fallback: jax.Array | None = None,
    depth: int = 40,
    block: int = 2048,
    *,
    interpret: bool,
    coalesce: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """The stream-aware bulk drain: per-lane QMC state in, draws out.

    Like :func:`forest_sample_batched`, but the lane inputs are
    ``counter`` (Q,) uint32 — each lane's already-rank-adjusted stream
    counter — and ``offset_bits`` (Q,) uint32 — its slot's 24-bit
    Cranley-Patterson rotation. The base-2 radical inverse and rotation run
    *in-kernel* (exact integer pipeline), so no uniform ever materializes on
    the host. Returns ``(idx, xi)`` — the resolved row-local interval
    indices and the exact float32 stream points the kernel drew (bit-equal
    to the host ``QmcStreams`` oracle; the differential suite asserts it).
    Sentinel lanes (``dist_id < 0``) resolve to 0 and still report their
    (unused) point."""
    (Q,) = counter.shape
    B, m = table.shape
    n = left.shape[1]
    fb = cell_first is not None and fallback is not None
    Qp = (Q + block - 1) // block * block
    ctrp = jnp.pad(counter.astype(jnp.uint32), (0, Qp - Q))
    offp = jnp.pad(offset_bits.astype(jnp.uint32), (0, Qp - Q))
    didp = jnp.pad(
        jnp.minimum(dist_id.astype(jnp.int32), B - 1), (0, Qp - Q),
        constant_values=-1,
    )
    if coalesce:
        order, inv = _bucket_order(didp)
        didp, ctrp, offp = didp[order], ctrp[order], offp[order]
    full2 = lambda r, c: pl.BlockSpec((r, c), lambda i: (0, 0))
    in_specs = [full2(B, n + 1), full2(B, m), full2(B, n), full2(B, n)]
    operands = [cdf, table, left, right]
    if fb:
        in_specs += [full2(B, m + 1), full2(B, m)]
        operands += [cell_first, fallback.astype(jnp.int32)]
    lane = pl.BlockSpec((block,), lambda i: (i,))
    in_specs += [lane, lane, lane]
    operands += [didp, ctrp, offp]
    out, xi = pl.pallas_call(
        functools.partial(
            _forest_batched_kernel, depth=depth, m=m, n=n, fb=fb,
            stream=True,
        ),
        grid=(Qp // block,),
        in_specs=in_specs,
        out_specs=(lane, lane),
        out_shape=(
            jax.ShapeDtypeStruct((Qp,), jnp.int32),
            jax.ShapeDtypeStruct((Qp,), jnp.float32),
        ),
        interpret=interpret,
    )(*operands)
    if coalesce:
        out, xi = out[inv], xi[inv]
    return out[:Q], xi[:Q]


@functools.partial(jax.jit, static_argnames=("depth", "block", "interpret"))
def forest_sample(
    cdf: jax.Array,
    table: jax.Array,
    left: jax.Array,
    right: jax.Array,
    xi: jax.Array,
    cell_first: jax.Array | None = None,
    fallback: jax.Array | None = None,
    depth: int = 40,
    block: int = 2048,
    *,
    interpret: bool,
) -> jax.Array:
    """Batch Algorithm 2. xi (B,) -> interval indices (B,) int32.

    Passing ``cell_first``/``fallback`` (as built by ``build_forest``)
    enables the degenerate-cell pre-resolution; without them the fixed-trip
    descent can return garbage for flagged cells (tied-weight chains deeper
    than ``depth``)."""
    (B,) = xi.shape
    m = table.shape[0]
    n = left.shape[0]
    fb = cell_first is not None and fallback is not None
    Bp = (B + block - 1) // block * block
    xip = jnp.pad(xi, (0, Bp - B))
    full = lambda size: pl.BlockSpec((size,), lambda i: (0,))
    in_specs = [full(n + 1), full(m), full(n), full(n)]
    operands = [cdf, table, left, right]
    if fb:
        in_specs += [full(m + 1), full(m)]
        operands += [cell_first, fallback.astype(jnp.int32)]
    in_specs.append(pl.BlockSpec((block,), lambda i: (i,)))
    operands.append(xip)
    out = pl.pallas_call(
        functools.partial(_forest_kernel, depth=depth, m=m, fb=fb),
        grid=(Bp // block,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Bp,), jnp.int32),
        interpret=interpret,
    )(*operands)
    return out[:B]
