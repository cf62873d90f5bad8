"""Cell-partitioned sharded radix-tree forests (multi-device Sec. 3).

The paper's guide cells make every per-cell radix tree independent: a
separator that crosses a cell boundary is clamped to the sentinel distance,
so no tree edge ever crosses a cell. That is exactly a distribution
boundary — this module partitions the ``m`` guide cells *contiguously* over
the mesh data axis, and because shard boundaries are aligned to cell
boundaries, **no cross-device tree edges exist by construction**.

Windowed shard-local builds (the scaling contract; tests pin it):

* Leaves are sorted by value, and cells are contiguous in leaf space, so a
  contiguous cell range owns a **contiguous leaf range**. Each shard's build
  runs over only that range, padded to a static ``capacity`` (shapes must be
  static under ``shard_map``): per-device tree work is the O(C log C)
  nearest-greater descent over its C-sized window, **not** O(n log n) over
  the world — the per-device window provably shrinks with the shard count
  (``tests/test_dist_forest.py`` asserts this on window sizes, not clocks).
* The plan (cell bounds -> leaf windows -> capacity) is derived on host from
  the *device-computed* CDF, so window boundaries agree bit-for-bit with
  what every shard computes under ``shard_map``. A window may include a few
  unowned neighbor leaves (capacity padding / clamping); ownership masking
  in ``core.forest._build_cell_trees`` keeps their slots ``INVALID``.
* The cell partition may be **unequal** (``occupancy_partition``): contiguous
  and cell-aligned, but balanced by *leaf occupancy* so spiky distributions
  no longer pile onto one shard. Equal-width ``cell_partition`` stays the
  default (requires ``D | m``); ``rebalance=True`` opts into occupancy
  balancing; ``partition=`` pins explicit bounds.
* All stored references are *global*: child refs, leaf refs (``~i``), guide
  table entries, and ``cell_first`` use global leaf indices. A node slot is
  owned by the shard owning its leaf's cell; slot ownership is a disjoint
  partition, so scatter-maxing the per-shard windows (unowned slots
  ``INVALID`` = int32 min) reconstructs the exact single-device arrays —
  :func:`gather_forest` is **bit-identical** to ``repro.core.build_forest``.
* The CDF is produced by a **distributed scan** over the fixed
  ``core.cdf.SCAN_CHUNKS`` reassociation grid: each device scans its chunk
  rows locally (optionally through the ``kernels.cdf_scan`` Pallas kernel in
  raw mode), chunk totals are exchanged with an exact ``psum`` scatter-gather
  (disjoint one-hot support, so the reduction adds zeros — no rounding), and
  every device re-derives the serial carry chain identically. The carry is
  deliberately *not* a ``psum`` of totals: a tree reduction has
  order-dependent rounding, and tree topology depends on CDF *bit patterns*.
* Sampling is an **owner-routed bulk drain** (Hübschle-Schneider & Sanders:
  bulk queries are the natural parallel granularity). The batch is sharded
  over the mesh data axis; each shard buckets its ~B/D draws by owning
  shard (cell id against the replicated partition bounds, stable sort,
  host-planned static bucket capacity), exchanges buckets with one
  ``all_to_all``, runs the window-local Algorithm-2 descent on **only the
  ~B/D draws it owns** (the descent ``while_loop`` terminates on the local
  deepest lane, not the global one), and routes interval ids back through a
  second ``all_to_all`` plus the inverse sort permutation — elementwise
  identical to ``core.sample.sample_forest``, with per-shard work that
  *shrinks* as devices grow instead of staying O(B) per shard. The old
  replicated masked-psum merge (every shard descends the full batch, exact
  one-owner-per-lane ``psum``) is kept behind ``routed=False`` as the
  reference oracle; the conformance suite runs both.

Delta updates (:func:`update_forest_sharded`): a weight update patches the
CDF through the same fixed ``SCAN_CHUNKS`` grid (identical reassociation, so
the result is bit-identical to a from-scratch scan), recomputes the
Algorithm-1 per-element work through :mod:`repro.kernels.forest_delta`
(new separator distances + changed-leaf-bits mask), and rebuilds only
window-sized problems — the dirty-gated program runs the tree build on
**only the dirty shards** (clean shards pass their window and cell-table
rows through byte-for-byte, so a sparse update does strictly less device
work than a full rebuild), and a no-op delta returns without touching the
trees at all. The result is bit-identical to a from-scratch
sharded rebuild over the same partition (the delta differential tests gate
this).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.cdf import (
    SCAN_CHUNKS,
    chunk_bounds,
    finalize_cdf,
    lower_bounds,
    scan_chunk_rows,
)
from repro.core.forest import (
    INVALID,
    RadixForest,
    _build_cell_trees,
    _cells,
)
from repro.core.sample import bisect, descend
from repro.kernels import ops

# Window capacities are rounded up to this granule: coarse enough that small
# occupancy drift between delta updates reuses the compiled program, fine
# enough that the per-device window still shrinks ~linearly with the shard
# count (a pow2 round would flatten 5/8ths of the sweep).
_CAPACITY_GRANULE = 64
# Routed-drain bucket capacities round up to this granule: small owner-load
# drift between batches reuses the compiled drain program, and the padding
# overhead stays a few lanes per (source, owner) pair.
_BUCKET_GRANULE = 16


class ShardedForest(NamedTuple):
    """Guide table + forest, cell-partitioned over ``n_shards`` devices.

    ``left``/``right`` are (D, C) *windowed* partial node arrays: row ``d``
    holds the contiguous global slot range ``[window_start[d],
    window_start[d] + C)`` with unowned slots ``INVALID``; stored references
    are global. ``table``/``fallback``/``cell_first``/``cdf`` are replicated
    (combined across shards with exact disjoint-support psums at build
    time). ``cell_bounds`` is the contiguous cell partition (shard ``d``
    owns cells ``[cell_bounds[d], cell_bounds[d+1])``); ``window_count`` is
    the number of owned leaves per shard (``window_start`` may be clamped
    below the first owned leaf so the static window fits in ``[0, n)``)."""

    cdf: jax.Array           # (n+1,) f32, replicated
    table: jax.Array         # (m,)  i32, replicated
    left: jax.Array          # (D, C) i32 windowed partial child refs
    right: jax.Array         # (D, C) i32 windowed partial child refs
    cell_first: jax.Array    # (m+1,) i32, replicated
    fallback: jax.Array      # (m,)  bool, replicated
    cell_bounds: jax.Array   # (D+1,) i32 cell partition bounds
    window_start: jax.Array  # (D,)  i32 global leaf offset of each window
    window_count: jax.Array  # (D,)  i32 owned leaves per shard

    @property
    def n(self) -> int:
        return self.cdf.shape[0] - 1

    @property
    def m(self) -> int:
        return self.table.shape[0]

    @property
    def n_shards(self) -> int:
        return self.left.shape[0]

    @property
    def capacity(self) -> int:
        """Static per-shard leaf-window size (the local build problem)."""
        return self.left.shape[1]


def default_mesh(axis: str = "data") -> Mesh:
    """1-D mesh over every local device (8 fake CPU devices in tests)."""
    return Mesh(np.array(jax.devices()), (axis,))


def cell_partition(m: int, n_shards: int) -> np.ndarray:
    """Equal-width shard bounds in cell space: shard d owns [b[d], b[d+1])."""
    if m % n_shards:
        raise ValueError(f"m={m} must divide over {n_shards} shards")
    return np.arange(n_shards + 1, dtype=np.int64) * (m // n_shards)


def occupancy_partition(cell_counts, n_shards: int) -> np.ndarray:
    """Contiguous cell-aligned bounds minimizing the max per-shard leaf load.

    Classic painter's partition: binary-search the smallest capacity for
    which a greedy left-to-right fill needs at most ``n_shards`` segments,
    then emit the greedy cuts at that capacity. Deterministic in the input;
    trailing shards may own empty cell ranges. No absolute per-shard load
    bound is promised — one giant cell forces its whole load onto a single
    shard (cell alignment is the contract) — but the returned partition
    minimizes the max per-shard load over all contiguous cell-aligned
    partitions, which the property tests verify by brute force.
    """
    counts = np.asarray(cell_counts, np.int64)
    if counts.ndim != 1 or counts.size == 0:
        raise ValueError("cell_counts must be a non-empty 1-D array")
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    m = counts.shape[0]
    cum = np.concatenate([[0], np.cumsum(counts)])
    total = int(cum[-1])

    def cuts(cap: int) -> list[int]:
        """Greedy segment ends: each shard takes the longest prefix <= cap."""
        out, b = [], 0
        for _ in range(n_shards):
            if b < m:
                b = int(np.searchsorted(cum, cum[b] + cap, side="right")) - 1
            out.append(b)
        return out

    lo = max(int(counts.max(initial=0)), -(-total // n_shards), 1)
    hi = max(total, lo)
    while lo < hi:
        mid = (lo + hi) // 2
        if cuts(mid)[-1] >= m:
            hi = mid
        else:
            lo = mid + 1
    return np.asarray([0] + cuts(lo), np.int64)


def resolve_partition(
    m: int,
    n_shards: int,
    partition=None,
    rebalance: bool = False,
    cell_counts=None,
) -> np.ndarray:
    """Cell bounds for a build: explicit > occupancy-balanced > equal-width."""
    if partition is not None:
        b = np.asarray(partition, np.int64)
        if (
            b.shape != (n_shards + 1,)
            or b[0] != 0
            or b[-1] != m
            or np.any(np.diff(b) < 0)
        ):
            raise ValueError(
                f"partition must be a nondecreasing (n_shards+1,) bounds "
                f"array from 0 to m={m}, got {b!r}"
            )
        return b
    if rebalance:
        return occupancy_partition(cell_counts, n_shards)
    return cell_partition(m, n_shards)


def pallas_row_scan(rows: jax.Array) -> jax.Array:
    """Local chunk-row scan through the Pallas kernel (raw cumsum mode)."""
    return ops.row_cumsum(rows, use_pallas=True)


def _distributed_raw_scan(w_rows: jax.Array, axis: str, n: int, row_scan=None):
    """Inside ``shard_map``: (C/D, L) local rows -> (n,) full raw scan.

    Bit-identical to ``core.cdf.chunked_cumsum`` on the concatenated rows:
    same per-row scans, same serial carry chain (re-derived on every device
    from the exact psum-gathered totals), same final adds."""
    Cl, L = w_rows.shape
    idx = jax.lax.axis_index(axis)
    local = jnp.cumsum(w_rows, axis=-1) if row_scan is None else row_scan(w_rows)
    my = idx * Cl + jnp.arange(Cl, dtype=jnp.int32)
    # Exact all-gather of chunk totals: one-hot scatter + psum only ever adds
    # zeros to the single contributor.
    totals = jax.lax.psum(
        jnp.zeros((SCAN_CHUNKS,), local.dtype).at[my].set(local[:, -1]), axis
    )
    carry = jnp.concatenate(
        [jnp.zeros((1,), local.dtype), jnp.cumsum(totals)[:-1]]
    )
    out = local + carry[my, None]
    full = jax.lax.psum(
        jnp.zeros((SCAN_CHUNKS, L), local.dtype).at[my].set(out), axis
    )
    return full.reshape(-1)[:n]


def _shard_count(mesh: Mesh, axis: str) -> int:
    D = int(mesh.shape[axis])
    if SCAN_CHUNKS % D:
        raise ValueError(
            f"shard count {D} must divide SCAN_CHUNKS={SCAN_CHUNKS}"
        )
    if jax.config.jax_enable_x64:
        # build_cdf switches to float64 accumulation under x64; the chunked
        # float32 scan cannot reproduce that bit-for-bit, so fail loudly
        # instead of silently breaking the conformance contract.
        raise NotImplementedError(
            "repro.dist.forest requires the float32 chunked scan; "
            "disable jax_enable_x64"
        )
    return D


@functools.lru_cache(maxsize=128)
def _cdf_builder(mesh: Mesh, axis: str, n: int, row_scan):
    """Cached jitted distributed-CDF program (keyed by mesh/shape)."""

    def shard_fn(w_rows):
        return finalize_cdf(_distributed_raw_scan(w_rows, axis, n, row_scan))

    return jax.jit(shard_map(
        shard_fn, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False
    ))


def build_cdf_sharded(
    weights: jax.Array, mesh: Mesh | None = None, axis: str = "data",
    row_scan=None,
) -> jax.Array:
    """Distributed CDF build: local chunk scans + exact cross-device carry.

    Returns the replicated (n+1,) cdf, bit-identical to
    ``core.cdf.build_cdf(weights, row_scan=row_scan)``."""
    mesh = mesh if mesh is not None else default_mesh(axis)
    _shard_count(mesh, axis)
    w = jnp.asarray(weights, jnp.float32)
    return _cdf_builder(mesh, axis, int(w.shape[0]), row_scan)(scan_chunk_rows(w))


@functools.partial(jax.jit, static_argnames=("m",))
def _device_cells(cdf: jax.Array, m: int) -> jax.Array:
    """Guide cell of every leaf, with the device's own float ops (the plan
    must agree bit-for-bit with what shard_fn computes)."""
    return _cells(lower_bounds(cdf), m)


def _round_capacity(max_count: int, n: int) -> int:
    c = -(-max(int(max_count), 1) // _CAPACITY_GRANULE) * _CAPACITY_GRANULE
    return min(c, n)


def _plan_windows(cells_np: np.ndarray, bounds: np.ndarray, n: int):
    """Per-shard leaf windows for a cell partition.

    Returns ``(starts, counts, capacity)``: true first-owned-leaf indices,
    owned leaf counts, and the static window capacity. ``cells_np`` is
    nondecreasing (leaves sorted by value), so each shard's owned leaves are
    the contiguous range ``[starts[d], starts[d] + counts[d])``."""
    starts = np.searchsorted(cells_np, bounds[:-1], side="left").astype(np.int64)
    ends = np.searchsorted(cells_np, bounds[1:], side="left").astype(np.int64)
    counts = ends - starts
    return starts, counts, _round_capacity(counts.max(initial=1), n)


def _window_build_local(
    cdf, d_full, bounds, starts, idx, *, m: int, n: int, cap: int,
    m_cap: int, fallback_slack: int,
):
    """One shard's windowed tree build (inside ``shard_map``): slice the
    ``cap``-sized leaf window, build the owned cell range's trees. Shared by
    the full builder and the dirty-gated delta builder — both must run the
    byte-identical program or the delta bit-identity contract breaks."""
    data = lower_bounds(cdf)
    start = starts[idx]
    cell_lo, cell_hi = bounds[idx], bounds[idx + 1]
    wdata = jax.lax.dynamic_slice(data, (start,), (cap,))
    wcells = _cells(wdata, m)
    if cap > 1:
        wd = jax.lax.dynamic_slice(d_full, (start,), (cap - 1,))
    else:
        wd = jnp.zeros((0,), jnp.uint32)
    left, right, tbl, cf, fb = _build_cell_trees(
        wdata, wd, wcells, m=m, cell_lo=cell_lo, m_local=m_cap,
        m_owned=cell_hi - cell_lo, node_offset=start, n_total=n,
        fallback_slack=fallback_slack,
    )
    return tbl, left, right, cf, fb.astype(jnp.int32)


def _combine_cell_rows(tbl, cf, fb_i32, bounds, idx, *, m: int, m_cap: int, axis: str):
    """Combine owned per-cell rows into replicated (m,) tables: targets are
    disjoint across shards and slack rows route to m (dropped), so the psum
    only ever adds zeros to the single contributor."""
    cell_lo, cell_hi = bounds[idx], bounds[idx + 1]
    cids = cell_lo + jnp.arange(m_cap, dtype=jnp.int32)
    owned_c = jnp.arange(m_cap, dtype=jnp.int32) < (cell_hi - cell_lo)
    tgt = jnp.where(owned_c, cids, m)
    table_g = jax.lax.psum(
        jnp.zeros((m,), jnp.int32).at[tgt].set(tbl, mode="drop"), axis
    )
    cf_g = jax.lax.psum(
        jnp.zeros((m,), jnp.int32).at[tgt].set(cf, mode="drop"), axis
    )
    fb_g = jax.lax.psum(
        jnp.zeros((m,), jnp.int32).at[tgt].set(fb_i32, mode="drop"), axis
    )
    return table_g, cf_g, fb_g > 0


@functools.lru_cache(maxsize=128)
def _windowed_builder(
    mesh: Mesh, axis: str, m: int, n: int, cap: int, m_cap: int,
    fallback_slack: int,
):
    """Cached jitted windowed-build program.

    Inputs (all replicated): the cdf, the global separator distances, the
    cell partition bounds, and the clamped window starts. Each device slices
    its own ``cap``-sized leaf window and builds only the trees of its owned
    cell range; per-cell outputs combine into replicated global tables via
    exact disjoint-support psums."""

    def shard_fn(cdf, d_full, bounds, starts):
        idx = jax.lax.axis_index(axis)
        tbl, left, right, cf, fb = _window_build_local(
            cdf, d_full, bounds, starts, idx, m=m, n=n, cap=cap,
            m_cap=m_cap, fallback_slack=fallback_slack,
        )
        table_g, cf_g, fb_g = _combine_cell_rows(
            tbl, cf, fb, bounds, idx, m=m, m_cap=m_cap, axis=axis
        )
        return table_g, left[None], right[None], cf_g, fb_g

    return jax.jit(shard_map(
        shard_fn, mesh=mesh, in_specs=(P(), P(), P(), P()),
        out_specs=(P(), P(axis), P(axis), P(), P()),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=128)
def _windowed_delta_builder(
    mesh: Mesh, axis: str, m: int, n: int, cap: int, m_cap: int,
    fallback_slack: int,
):
    """Cached jitted **dirty-gated** windowed-build program (delta updates).

    Like :func:`_windowed_builder` plus the previous forest's per-shard
    windows, the replicated old cell tables, and a replicated (D,) dirty
    mask. Each shard runs the window build **only when its dirty flag is
    set** (``lax.cond`` executes one branch, so a sparse update really does
    strictly less device tree work than a full rebuild); clean shards
    contribute their old window rows and old cell-table rows byte-for-byte.
    That reuse is exact: a clean shard's owned leaf bits are unchanged and
    the window plan is unchanged, so every one of its outputs — child refs
    *and* its ``table``/``cell_first``/``fallback`` rows, all pure functions
    of the owned window data — would rebuild to the identical bits (the
    delta differential suite gates this)."""

    def shard_fn(cdf, d_full, bounds, starts, dirty,
                 old_left, old_right, old_table, old_cf, old_fb):
        idx = jax.lax.axis_index(axis)
        cell_lo = bounds[idx]

        def build(_):
            return _window_build_local(
                cdf, d_full, bounds, starts, idx, m=m, n=n, cap=cap,
                m_cap=m_cap, fallback_slack=fallback_slack,
            )

        def keep(_):
            safe = jnp.clip(cell_lo + jnp.arange(m_cap, dtype=jnp.int32),
                            0, m - 1)
            return (old_table[safe], old_left[0], old_right[0],
                    old_cf[safe], old_fb[safe].astype(jnp.int32))

        tbl, left, right, cf, fb = jax.lax.cond(
            dirty[idx] > 0, build, keep, operand=None
        )
        table_g, cf_g, fb_g = _combine_cell_rows(
            tbl, cf, fb, bounds, idx, m=m, m_cap=m_cap, axis=axis
        )
        return table_g, left[None], right[None], cf_g, fb_g

    return jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(axis), P(axis), P(), P(), P()),
        out_specs=(P(), P(axis), P(axis), P(), P()),
        check_vma=False,
    ))


def _separator_distances_for(cdf: jax.Array, m: int) -> jax.Array:
    """Global (n-1,) separator distances via the forest_delta kernel path
    (the Algorithm-1 per-element work; bit-identical to
    ``core.forest._separator_distances`` on the same lower bounds)."""
    return ops.forest_delta(lower_bounds(cdf), m)


def build_forest_from_cdf_sharded(
    cdf: jax.Array,
    m: int,
    mesh: Mesh | None = None,
    axis: str = "data",
    fallback_slack: int = 2,
    partition=None,
    rebalance: bool = False,
    d_full: jax.Array | None = None,
    cells_np: np.ndarray | None = None,
    capacity: int | None = None,
) -> ShardedForest:
    """Windowed shard-local forest build over a replicated CDF.

    Host-side planning (cell occupancy -> partition -> leaf windows ->
    static capacity) runs on the device-computed cell ids, then one
    ``shard_map`` builds every shard's window. Gathering the result
    (:func:`gather_forest`) is bit-identical to
    ``core.build_forest_from_cdf(cdf, m)``. ``d_full``/``cells_np`` let the
    delta-update path feed in the distances and cell ids it already
    computed (they must match the device's own — bit-identity rests on it).
    ``capacity`` pins the static window size instead of the planned one
    (must fit every shard's owned leaf count) — the hysteresis hook:
    :func:`update_forest_sharded` passes the previous forest's capacity so
    occupancy drift below the old window reuses the compiled program.
    """
    mesh = mesh if mesh is not None else default_mesh(axis)
    D = _shard_count(mesh, axis)
    cdf = jnp.asarray(cdf, jnp.float32)
    n = int(cdf.shape[0]) - 1
    if cells_np is None:
        cells_np = np.asarray(_device_cells(cdf, m))
    bounds = resolve_partition(
        m, D, partition=partition, rebalance=rebalance,
        cell_counts=(
            np.bincount(cells_np, minlength=m)
            if partition is None and rebalance else None
        ),
    )
    starts, counts, cap = _plan_windows(cells_np, bounds, n)
    if capacity is not None:
        if capacity < counts.max(initial=1):
            raise ValueError(
                f"capacity={capacity} below the plan's max owned leaf "
                f"count {int(counts.max(initial=1))}"
            )
        cap = min(int(capacity), n)
    w_starts = np.clip(starts, 0, n - cap)
    m_cap = _round_capacity(np.diff(bounds).max(initial=1), m)
    if d_full is None:
        d_full = _separator_distances_for(cdf, m)
    table, left, right, cf, fb = _windowed_builder(
        mesh, axis, m, n, cap, m_cap, fallback_slack
    )(
        cdf,
        d_full,
        jnp.asarray(bounds, jnp.int32),
        jnp.asarray(w_starts, jnp.int32),
    )
    return ShardedForest(
        cdf, table, left, right,
        jnp.concatenate([cf, jnp.asarray([n - 1], jnp.int32)]),
        fb,
        jnp.asarray(bounds, jnp.int32),
        jnp.asarray(w_starts, jnp.int32),
        jnp.asarray(counts, jnp.int32),
    )


def build_forest_sharded(
    weights: jax.Array,
    m: int,
    mesh: Mesh | None = None,
    axis: str = "data",
    fallback_slack: int = 2,
    row_scan=None,
    partition=None,
    rebalance: bool = False,
    capacity: int | None = None,
) -> ShardedForest:
    """Distributed scan -> windowed per-shard cell-range tree build.

    Each device derives the full CDF from the distributed chunked scan, then
    builds only the trees of its own cell range over a capacity-bounded
    local leaf window, with node ids in the global index space. Gathering
    the partials (:func:`gather_forest`) is bit-identical to
    ``core.build_forest``."""
    mesh = mesh if mesh is not None else default_mesh(axis)
    _shard_count(mesh, axis)
    w = jnp.asarray(weights, jnp.float32)
    cdf = _cdf_builder(mesh, axis, int(w.shape[0]), row_scan)(scan_chunk_rows(w))
    return build_forest_from_cdf_sharded(
        cdf, m, mesh=mesh, axis=axis, fallback_slack=fallback_slack,
        partition=partition, rebalance=rebalance, capacity=capacity,
    )


def build_forest_sharded_auto(
    weights: jax.Array,
    m: int,
    mesh: Mesh | None = None,
    axis: str = "data",
    fallback_slack: int = 2,
    rebalance: bool = False,
) -> tuple[ShardedForest, Mesh]:
    """Caller-friendly build: default mesh over all devices and ``m`` rounded
    up to the next shard multiple (the equal cell-aligned partition needs
    D | m; occupancy rebalancing has no such constraint but keeps the same
    guide resolution). The shared glue for opt-in call sites
    (``serve.sampler.ForestSampler``, ``data.mixture.MixtureSampler``);
    returns the forest and the mesh to sample with."""
    mesh = mesh if mesh is not None else default_mesh(axis)
    D = int(mesh.shape[axis])
    m = -(-m // D) * D
    return (
        build_forest_sharded(
            weights, m, mesh=mesh, axis=axis, fallback_slack=fallback_slack,
            rebalance=rebalance,
        ),
        mesh,
    )


def update_forest_sharded(
    forest: ShardedForest,
    weights: jax.Array | None = None,
    *,
    weights_delta=None,
    base_weights=None,
    mesh: Mesh | None = None,
    axis: str = "data",
    fallback_slack: int = 2,
    row_scan=None,
    with_stats: bool = False,
):
    """Delta update: rebuild only the shards whose owned windows changed.

    ``weights`` is the full new weight vector (or pass ``weights_delta`` +
    ``base_weights`` and the float32 sum is formed here). The CDF is patched
    through the fixed ``SCAN_CHUNKS`` reassociation grid (same row scans,
    same serial carry — bit-identical to a from-scratch distributed scan);
    the Algorithm-1 per-element re-work (new separator distances + the
    changed-leaf-bits mask) comes from :mod:`repro.kernels.forest_delta`.
    Shards whose leaf windows carry no changed bits keep their partial
    arrays byte-for-byte; a no-op delta skips the tree rebuild entirely.

    **Capacity hysteresis**: the fresh plan's capacity is only adopted when
    it *grows* past the current window — while the new plan still fits,
    the old (possibly larger) capacity is kept, so an adversarial weight
    stream oscillating across a 64-leaf granule boundary stops recompiling
    the windowed build/sampling programs on every update (the regression
    test drives exactly that stream). The result is **bit-identical** to
    ``build_forest_sharded(weights, m, partition=forest.cell_bounds,
    capacity=<the kept capacity>)``, and its gather stays bit-identical to
    the single-device build (window capacity never affects stored bits).

    With ``with_stats=True`` also returns a dict: ``dirty_shards`` /
    ``dirty_chunks`` (scan-grid rows re-spanned by changed CDF entries) /
    ``plan_changed`` (leaf windows moved -> full windowed rebuild) /
    ``rebuilt`` (the tree-build shard_map actually ran) /
    ``rebuilt_windows`` (window builds the devices actually executed: the
    dirty-gated program runs the tree build only on dirty shards, so a
    sparse update does strictly less device work than a full rebuild —
    the structural fact the delta benchmarks pin, never wall-clock) /
    ``capacity`` (the static window adopted) / ``capacity_kept``
    (hysteresis retained a window larger than the fresh plan's).
    """
    mesh = mesh if mesh is not None else default_mesh(axis)
    D = _shard_count(mesh, axis)
    if forest.n_shards != D:
        raise ValueError(
            f"forest has {forest.n_shards} shards but mesh axis has {D}"
        )
    if weights is None:
        if weights_delta is None or base_weights is None:
            raise ValueError(
                "pass weights, or both weights_delta and base_weights"
            )
        weights = (
            jnp.asarray(base_weights, jnp.float32)
            + jnp.asarray(weights_delta, jnp.float32)
        )
    w = jnp.asarray(weights, jnp.float32)
    n, m = forest.n, forest.m
    if int(w.shape[0]) != n:
        raise ValueError(
            f"delta update keeps n fixed: forest has {n} intervals, "
            f"got {int(w.shape[0])} weights"
        )
    new_cdf = _cdf_builder(mesh, axis, n, row_scan)(scan_chunk_rows(w))
    old_bits = np.asarray(forest.cdf).view(np.uint32)
    new_bits = np.asarray(new_cdf).view(np.uint32)
    cb = chunk_bounds(n)
    changed_cdf = np.flatnonzero(new_bits[1:] != old_bits[1:])
    dirty_chunks = int(
        np.unique(np.searchsorted(cb, changed_cdf, side="right") - 1).size
    )

    if changed_cdf.size == 0:
        stats = dict(
            dirty_shards=0, dirty_chunks=0, plan_changed=False, rebuilt=False,
            rebuilt_windows=0, capacity=forest.capacity, capacity_kept=False,
        )
        out = forest._replace(cdf=new_cdf)  # same bits; fresh buffer
        return (out, stats) if with_stats else out

    bounds = np.asarray(forest.cell_bounds, np.int64)
    # Algorithm-1 re-work for the changed weights, via the Pallas kernel
    # entry point: new distances feed the rebuild, the changed-bits mask
    # drives per-shard dirtiness.
    d_new, leaf_changed = ops.forest_delta_update(
        lower_bounds(forest.cdf), lower_bounds(new_cdf), m
    )
    cells_np = np.asarray(_device_cells(new_cdf, m))
    starts, counts, fresh_cap = _plan_windows(cells_np, bounds, n)
    # Hysteresis: keep the compiled program's window while the new plan
    # still fits; only a genuine overflow re-plans (and recompiles).
    cap = forest.capacity if fresh_cap <= forest.capacity else fresh_cap
    w_starts = np.clip(starts, 0, n - cap)
    plan_same = (
        cap == forest.capacity
        and np.array_equal(w_starts, np.asarray(forest.window_start))
        and np.array_equal(counts, np.asarray(forest.window_count))
    )
    lc = np.asarray(leaf_changed)
    dirty = np.array(
        [bool(lc[s : s + c].any()) for s, c in zip(starts, counts)]
    )
    if plan_same:
        # Dirty-gated rebuild: only the dirty shards run their window build
        # on device (lax.cond executes one branch); clean shards pass their
        # old window rows and old cell-table rows through byte-for-byte.
        m_cap = _round_capacity(np.diff(bounds).max(initial=1), m)
        table, left, right, cf, fb = _windowed_delta_builder(
            mesh, axis, m, n, cap, m_cap, fallback_slack
        )(
            new_cdf, d_new,
            jnp.asarray(bounds, jnp.int32),
            jnp.asarray(w_starts, jnp.int32),
            jnp.asarray(dirty, jnp.int32),
            forest.left, forest.right, forest.table,
            forest.cell_first[:m], forest.fallback,
        )
        out = ShardedForest(
            new_cdf, table, left, right,
            jnp.concatenate([cf, jnp.asarray([n - 1], jnp.int32)]),
            fb, forest.cell_bounds, forest.window_start, forest.window_count,
        )
    else:
        out = build_forest_from_cdf_sharded(
            new_cdf, m, mesh=mesh, axis=axis, fallback_slack=fallback_slack,
            partition=bounds, d_full=d_new, cells_np=cells_np, capacity=cap,
        )
    stats = dict(
        dirty_shards=int(dirty.sum()) if plan_same else D,
        dirty_chunks=dirty_chunks,
        plan_changed=not plan_same,
        rebuilt=True,
        rebuilt_windows=int(dirty.sum()) if plan_same else D,
        capacity=cap,
        capacity_kept=cap > fresh_cap,
    )
    return (out, stats) if with_stats else out


def _round_bucket(count: int, limit: int) -> int:
    """Static per-(source, owner) bucket capacity: the observed max count
    rounded up to the bucket granule (program reuse under owner-load drift),
    never above the per-shard lane count (can't send more than you hold)."""
    k = -(-max(int(count), 1) // _BUCKET_GRANULE) * _BUCKET_GRANULE
    return max(min(k, limit), 1)


@functools.partial(jax.jit, static_argnames=("m",))
def _draw_owners(xi: jax.Array, bounds: jax.Array, m: int) -> jax.Array:
    """Owning shard of each uniform: cell id against the partition bounds.

    The same float/int ops the drain program runs under ``shard_map`` — the
    host-side bucket plan and the device-side routing must agree draw for
    draw. Empty shards (repeated bounds) are skipped by the right-sided
    search, so every draw has exactly one owner."""
    g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    return jnp.clip(
        jnp.searchsorted(bounds, g, side="right").astype(jnp.int32) - 1,
        0, bounds.shape[0] - 2,
    )


def _drain_plan(forest: ShardedForest, xi: jax.Array, D: int):
    """Host-side routed-drain plan: pad the batch to D lanes-per-shard, count
    draws per (source shard, owning shard), round the max to the static
    bucket capacity. Returns ``(plan, xi_padded)``."""
    B = int(xi.shape[0])
    if B == 0:
        raise ValueError("cannot drain an empty batch")
    lanes = -(-B // D)
    b_pad = lanes * D
    xi_p = jnp.pad(
        jnp.asarray(xi, jnp.float32), (0, b_pad - B), constant_values=-1.0
    )
    owners = np.asarray(_draw_owners(xi_p, forest.cell_bounds, forest.m))
    counts = np.stack(
        [np.bincount(row, minlength=D) for row in owners.reshape(D, lanes)]
    )
    K = _round_bucket(counts.max(initial=1), lanes)
    plan = dict(
        batch=B, padded_batch=b_pad, lanes_per_shard=lanes,
        bucket_capacity=K, descent_lanes=D * K, send_counts=counts,
    )
    return plan, xi_p


def drain_plan(
    forest: ShardedForest, xi: jax.Array, mesh: Mesh | None = None,
    axis: str = "data",
) -> dict:
    """The routed drain's bucket plan for a batch (what the devices will do,
    structurally): ``lanes_per_shard`` (the batch shard each device holds),
    ``bucket_capacity`` (static per-(source, owner) bucket), and
    ``descent_lanes`` (lanes each shard's Algorithm-2 descent runs over —
    ~B/D for balanced owner loads, vs the full B every shard pays on the
    masked-psum oracle path). Tests assert scaling on these shapes, never
    on wall-clock."""
    mesh = mesh if mesh is not None else default_mesh(axis)
    plan, _ = _drain_plan(forest, xi, int(mesh.shape[axis]))
    return plan


def sample_sharded(
    forest: ShardedForest,
    xi: jax.Array,
    mesh: Mesh | None = None,
    axis: str = "data",
    use_fallback: bool = True,
    routed: bool = True,
    on_mismatch: str = "raise",
    with_stats: bool = False,
) -> jax.Array:
    """Algorithm 2 over the sharded forest: owner-routed bulk drain.

    ``routed=True`` (default): the batch is sharded over the mesh data axis,
    each shard stably sorts its ~B/D draws by owning shard into
    capacity-padded buckets (host-planned static shapes), one ``all_to_all``
    exchanges the buckets, the owner resolves **only its owned draws** over
    its local window (every edge of an owned cell's tree stays inside the
    window, and global node id minus window start is the local slot; the
    descent loop terminates on the *local* deepest lane), and a second
    ``all_to_all`` plus the inverse sort permutation routes interval ids
    back to the requesting lanes.

    ``routed=False`` keeps the replicated masked-psum merge as a reference
    oracle: every shard descends the full batch and the per-lane results
    combine with an exact one-owner-per-lane ``psum``.

    Both paths are elementwise identical to ``core.sample.sample_forest`` on
    the gathered forest. Returns global interval ids.

    ``on_mismatch`` picks the behavior when the forest's shard count does
    not match the mesh data axis (a restore onto a shrunk/grown mesh):
    ``"raise"`` (default, the strict contract) or ``"degrade"`` — gather
    the forest (:func:`gather_forest` is exact) and resolve the whole
    batch with the single-device descent, elementwise-identical to the
    sharded drain, flagged ``degraded=True`` in the stats dict that
    ``with_stats=True`` adds to the return."""
    if on_mismatch not in ("raise", "degrade"):
        raise ValueError(
            f"on_mismatch must be 'raise' or 'degrade', got {on_mismatch!r}"
        )
    mesh = mesh if mesh is not None else default_mesh(axis)
    D = int(mesh.shape[axis])
    stats = dict(degraded=False, n_shards=forest.n_shards, mesh_devices=D)
    if forest.n_shards != D:
        if on_mismatch == "raise":
            raise ValueError(
                f"forest has {forest.n_shards} shards but mesh axis has {D}"
            )
        from repro.core.sample import sample_forest

        out = sample_forest(
            gather_forest(forest), jnp.asarray(xi, jnp.float32),
            use_fallback=use_fallback,
        )
        stats["degraded"] = True
        return (out, stats) if with_stats else out
    if not routed:
        out = _sampler(
            mesh, axis, forest.m, forest.n, forest.capacity, use_fallback
        )(
            forest.table, forest.left, forest.right, forest.fallback,
            forest.cdf, forest.cell_first, forest.cell_bounds,
            forest.window_start, jnp.asarray(xi, jnp.float32),
        )
        return (out, stats) if with_stats else out
    plan, xi_p = _drain_plan(forest, xi, D)
    out = _routed_sampler(
        mesh, axis, forest.m, forest.n, forest.capacity, use_fallback,
        plan["lanes_per_shard"], plan["bucket_capacity"],
    )(
        forest.table, forest.left, forest.right, forest.fallback,
        forest.cdf, forest.cell_first, forest.cell_bounds,
        forest.window_start, xi_p,
    )
    out = out[: plan["batch"]]
    return (out, stats) if with_stats else out


def _window(n: int, cap: int, start: jax.Array):
    """A shard's node indices for ``core.sample.descend``: the CDF is
    indexed globally, the children by slot in the shard's window."""
    return lambda j: (jnp.clip(j, 0, n - 1), jnp.clip(j - start, 0, cap - 1))


@functools.lru_cache(maxsize=128)
def _routed_sampler(
    mesh: Mesh, axis: str, m: int, n: int, cap: int, use_fallback: bool,
    lanes: int, K: int,
):
    """Cached jitted owner-routed all-to-all drain program.

    Each shard holds ``lanes`` draws of the batch and a ``(D, K)`` bucket
    grid; the tiled ``all_to_all`` is a transpose of that grid across the
    mesh (and hence its own inverse — the identical collective routes the
    answers back). Bucket padding lanes carry the sentinel ``-1.0`` and are
    resolved to ``done`` before the descent starts, so they cost nothing."""
    D = int(mesh.shape[axis])

    def shard_fn(table, left_l, right_l, fb, cdf, cell_first, bounds, starts, xi_l):
        idx = jax.lax.axis_index(axis)
        left_l, right_l = left_l[0], right_l[0]
        start = starts[idx]

        # Bucket my batch shard by owning shard: stable sort keeps duplicate
        # uniforms and equal-owner draws in batch order, and the (owner,
        # within-bucket rank) pair is exactly the slot the owner will answer
        # at — the round trip needs no index payload at all.
        g = jnp.clip(jnp.floor(xi_l * jnp.float32(m)).astype(jnp.int32),
                     0, m - 1)
        owner = jnp.clip(
            jnp.searchsorted(bounds, g, side="right").astype(jnp.int32) - 1,
            0, D - 1,
        )
        order = jnp.argsort(owner)                       # stable
        so, sx = owner[order], xi_l[order]
        seg = jnp.searchsorted(so, jnp.arange(D, dtype=jnp.int32))
        rank = jnp.arange(lanes, dtype=jnp.int32) - seg[so].astype(jnp.int32)
        send = jnp.full((D, K), -1.0, jnp.float32).at[so, rank].set(
            sx, mode="drop"
        )
        recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=True)

        # Window-local Algorithm-2 descent over only my owned draws (~B/D
        # lanes): the while_loop ends on MY deepest lane, not the world's.
        rx = recv.reshape(-1)
        live = rx >= 0.0
        rg = jnp.clip(jnp.floor(rx * jnp.float32(m)).astype(jnp.int32),
                      0, m - 1)
        j = jnp.where(live, table[rg], jnp.int32(-1))
        if use_fallback:
            flagged = live & fb[rg] & (j >= 0)
            lo = cell_first[rg]
            hi = jnp.where(flagged, cell_first[rg + 1], lo)
            j = jnp.where(flagged, ~bisect(cdf, rx, lo, hi)[0], j)
        got, _ = descend(cdf, left_l, right_l, rx, j,
                         node=_window(n, cap, start))

        # Route interval ids back: the same all_to_all inverts the exchange,
        # then the inverse sort permutation restores batch order.
        back = jax.lax.all_to_all(got.reshape(D, K), axis, 0, 0, tiled=True)
        return jnp.zeros((lanes,), jnp.int32).at[order].set(back[so, rank])

    return jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(), P(), P(), P(), P(), P(axis)),
        out_specs=P(axis), check_vma=False,
    ))


@functools.lru_cache(maxsize=128)
def _sampler(mesh: Mesh, axis: str, m: int, n: int, cap: int, use_fallback: bool):
    """Cached jitted replicated masked-psum sampling program (the reference
    oracle the routed drain is verified against: every shard descends the
    full batch; exact merge because every lane has exactly one owner)."""

    def shard_fn(table, left_l, right_l, fb, cdf, cell_first, bounds, starts, xi):
        idx = jax.lax.axis_index(axis)
        left_l, right_l = left_l[0], right_l[0]
        start = starts[idx]
        g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
        owned = (g >= bounds[idx]) & (g < bounds[idx + 1])
        j = jnp.where(owned, table[g], jnp.int32(-1))

        if use_fallback:
            flagged = owned & fb[g] & (j >= 0)
            lo = cell_first[g]
            hi = jnp.where(flagged, cell_first[g + 1], lo)
            j = jnp.where(flagged, ~bisect(cdf, xi, lo, hi)[0], j)
        got, _ = descend(cdf, left_l, right_l, xi, j,
                         node=_window(n, cap, start))
        return jax.lax.psum(jnp.where(owned, got, 0), axis)

    return jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(), P(), P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    ))


def gather_forest(forest: ShardedForest) -> RadixForest:
    """Combine the per-shard windows into a single-device ``RadixForest``.

    Slot ownership is disjoint and ``INVALID`` is the int32 minimum, so
    scatter-maxing every shard's window at its global offset is the exact
    union of the writes (window padding/overlap only ever contributes
    ``INVALID``)."""
    D, cap = forest.left.shape
    n = forest.n
    idx = (
        forest.window_start[:, None].astype(jnp.int32)
        + jnp.arange(cap, dtype=jnp.int32)[None, :]
    ).reshape(-1)
    left = jnp.full((n,), INVALID, jnp.int32).at[idx].max(
        forest.left.reshape(-1), mode="drop"
    )
    right = jnp.full((n,), INVALID, jnp.int32).at[idx].max(
        forest.right.reshape(-1), mode="drop"
    )
    return RadixForest(
        forest.cdf,
        forest.table,
        left,
        right,
        forest.cell_first,
        forest.fallback,
    )
