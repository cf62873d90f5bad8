"""Radix tree forests over CDF intervals (Binder & Keller 2019, Sec. 3).

The structure: the unit interval is cut into ``m`` guide cells. A cell
overlapped by a single CDF interval stores ``~i`` (two's complement, MSB set)
directly in the guide table. A cell containing several interval lower bounds
stores the index of its *root slot* node; the per-cell radix tree over the
contained lower bounds hangs off that slot's right child, while the slot's
left child is manually set to the interval overlapping the cell from the left
(paper Fig. 11). Node index ``j`` doubles as CDF index: node ``j`` splits at
``cdf[j]`` (the Apetrei enumeration), so nodes store only two child refs.

Child references: ``>= 0`` → internal node id, ``< 0`` → leaf ``~i``.

Slot accounting (a property worth stating): with ``n`` intervals there are
exactly ``n`` node slots and all are used — ``n-1-#crossing`` internal
separators (separator ``k`` ↔ node ``k+1``) plus ``#crossing+1`` cell root
slots (the first leaf index of each non-empty cell; the crossing separator's
own node id *is* the next cell's root slot). Indices of nodes of small
subtrees are consecutive, which the paper exploits for cache locality.

Two builders produce bit-identical forests:

* :func:`build_forest` — TPU-native: the radix forest is the Cartesian
  (max-)tree over separator distances ``delta(k) = bits(data[k]) XOR
  bits(data[k+1])`` with cell-crossing separators clamped to the sentinel
  distance. Parents are found in closed form with an all-nearest-greater-
  values sparse-table descent: O(n log n) work, O(log n) depth, **no
  atomics**, perfectly load-balanced (identical instruction stream per lane).
* :func:`build_forest_apetrei` — a round-synchronous faithful emulation of
  the paper's Algorithm 1 (bottom-up merging with atomicExch emulation),
  kept as ground truth for tests and as executable documentation.

Tie-breaking matches Algorithm 1: a subtree whose left/right boundary
distances are equal merges left (becomes the *right* child of the node at its
low bound). In nearest-greater terms: L(k) uses strict ``>``, R(k) uses
``>=``, and the parent is L when ``delta[L] <= delta[R]``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.trace import scope, span

from .bits import DIST_SENTINEL, float_to_bits, np_xor_distance
from .cdf import build_cdf, lower_bounds, np_build_cdf

INVALID = np.int32(-(2**31))  # never a legal ref; only in untouched slots
# Radix-tree depth over *distinct* float32 keys is <= ~34 (one bit level per
# edge). Zero-width intervals (tied CDF values, delta == 0) chain arbitrarily
# deep; such cells are flagged for balanced fallback at build time, so 256 is
# a pure safety guard for fallback-disabled traversal.
MAX_DEPTH = 256
_DEPTH_ITERS = 48  # cap of the saturating depth walk; deeper is flagged anyway


class RadixForest(NamedTuple):
    """Guide table + radix tree forest (+ cutpoint/fallback side tables)."""

    cdf: jax.Array         # (n+1,) f32; interval i = [cdf[i], cdf[i+1])
    table: jax.Array       # (m,)  i32; >=0 node id, <0 ~interval
    left: jax.Array        # (n,)  i32 child refs
    right: jax.Array       # (n,)  i32 child refs
    cell_first: jax.Array  # (m+1,) i32 first interval overlapping each cell
    fallback: jax.Array    # (m,)  bool; degenerate cell -> balanced bisection

    @property
    def n(self) -> int:
        return self.left.shape[0]

    @property
    def m(self) -> int:
        return self.table.shape[0]


def _cells(data: jax.Array, m: int) -> jax.Array:
    """Guide cell of each lower bound; float32 math to match traversal."""
    c = jnp.floor(data * jnp.float32(m)).astype(jnp.int32)
    return jnp.clip(c, 0, m - 1)


def _block_max_table(d: jax.Array, levels: int) -> list[jax.Array]:
    """T[j][s] = max d[s : s+2^j] (out of range = 0, neutral for uint)."""
    tables = [d]
    cur = d
    for j in range(levels):
        shift = 1 << j
        shifted = jnp.concatenate(
            [cur[shift:], jnp.zeros((min(shift, cur.shape[0]),), cur.dtype)]
        )[: cur.shape[0]]
        cur = jnp.maximum(cur, shifted)
        tables.append(cur)
    return tables


def _nearest_greater(d: jax.Array):
    """For every separator k return (dL, L, dR, R):

    L(k): nearest l < k with d[l] >  d[k]  (virtual boundary -1, SENTINEL)
    R(k): nearest r > k with d[r] >= d[k]  (virtual boundary len, SENTINEL)
    """
    s = d.shape[0]
    levels = max(1, int(np.ceil(np.log2(max(s, 2)))))
    T = _block_max_table(d, levels)
    k = jnp.arange(s, dtype=jnp.int32)
    v = d

    # Left search: shrink exclusive upper bound p while block has no '> v'.
    p = k
    for j in range(levels, -1, -1):
        step = 1 << j
        idx = jnp.clip(p - step, 0, max(s - 1, 0))
        can = (p >= step) & (T[min(j, len(T) - 1)][idx] <= v)
        p = jnp.where(can, p - step, p)
    L = p - 1
    dL = jnp.where(L >= 0, d[jnp.clip(L, 0)], jnp.uint32(DIST_SENTINEL))

    # Right search: grow start q while block has no '>= v'.
    q = k + 1
    for j in range(levels, -1, -1):
        step = 1 << j
        idx = jnp.clip(q, 0, max(s - 1, 0))
        can = (q + step <= s) & (T[min(j, len(T) - 1)][idx] < v)
        q = jnp.where(can, q + step, q)
    R = q
    dR = jnp.where(R < s, d[jnp.clip(R, 0, max(s - 1, 0))], jnp.uint32(DIST_SENTINEL))
    return dL, L, dR, R


def _separator_distances(data: jax.Array, cells: jax.Array) -> jax.Array:
    """(n-1,) XOR separator distances; cell crossings clamp to the sentinel."""
    bits = float_to_bits(data)
    sep_raw = bits[:-1] ^ bits[1:]
    crossing = cells[:-1] != cells[1:]
    return jnp.where(crossing, jnp.uint32(DIST_SENTINEL), sep_raw)


def _chain_depth(leaf_parent: jax.Array, node_parent: jax.Array):
    """Saturating traversal depth of every leaf: ``min(chain, _DEPTH_ITERS)
    + 1``, where ``chain`` counts the live ancestors met from ``leaf_parent``
    up ``node_parent`` (``-1`` ends a chain), plus the leaf step itself.

    The walk stops when the last chain has ended, after at most
    ``_DEPTH_ITERS`` trips; a trip leaves an ended chain unchanged, so the
    depths are those of a fixed ``_DEPTH_ITERS``-trip walk. Under ``vmap``
    the loop runs until the last row is done, each row keeping its own
    state. Returns ``(depth, trips)``."""

    def cond(state):
        anc, _, it = state
        return jnp.any(anc >= 0) & (it < _DEPTH_ITERS)

    def body(state):
        anc, depth, it = state
        with scope("depth_guard.trip"):
            live = anc >= 0
            return (jnp.where(live, node_parent[jnp.clip(anc, 0)], anc),
                    depth + live.astype(jnp.int32), it + 1)

    _, depth, trips = jax.lax.while_loop(
        cond, body, (leaf_parent, jnp.zeros_like(leaf_parent), jnp.int32(0)))
    return depth + 1, trips


def _build_cell_trees(
    data: jax.Array,
    d: jax.Array,
    cells: jax.Array,
    *,
    m: int,
    cell_lo,
    m_local: int,
    m_owned=None,
    node_offset=0,
    n_total: int | None = None,
    fallback_slack: int = 2,
):
    """Per-cell radix trees for the guide-cell range [cell_lo, cell_lo+m_owned).

    The shared build core of the single-device path (``cell_lo=0,
    m_local=m``) and the cell-partitioned sharded path
    (:mod:`repro.dist.forest`). ``data``/``cells``/``d`` are a contiguous
    window of the global leaf arrays; window index ``w`` is global leaf
    ``w + node_offset``, and all *stored references* (node ids, leaf refs,
    ``table``/``cell_first`` entries) are global. ``cell_lo`` and
    ``node_offset`` may be traced (they come from per-shard plan arrays
    indexed by ``axis_index`` under ``shard_map``); ``m_local`` is static.

    ``m_owned`` (traced, default ``m_local``) is the number of *owned* cells
    at the front of the ``m_local``-sized cell window. Shard plans with
    unequal cell ranges pad every range to a static capacity ``m_local``;
    the ``[m_owned, m_local)`` slack carries no ownership, so its per-cell
    outputs (``table``/``cell_first``/``fallback`` rows) are garbage the
    caller must mask out.

    Every edge of a cell's tree stays inside that cell (crossing separators
    carry the sentinel distance), so a node slot is written only by the cell
    owning its leaf. Restricting writes to an ownership mask therefore makes
    partial results from a *disjoint* cell partition combine exactly by
    elementwise max (``INVALID`` is int32 min): the combination of the shards
    is bit-identical to the unpartitioned build.

    Returns ``(left, right, table, cell_first, fallback)``: window-sized
    ``left``/``right`` (unowned slots ``INVALID``) and ``(m_local,)`` per-cell
    arrays for the owned range.
    """
    n = data.shape[0]
    n_total = n if n_total is None else n_total
    sentinel = jnp.uint32(DIST_SENTINEL)
    cell_lo = jnp.int32(cell_lo)
    node_offset = jnp.int32(node_offset)
    m_owned = jnp.int32(m_local if m_owned is None else m_owned)

    with scope("forest.cell_trees"):
        # Ownership; out-of-range scatter indices route to m_local and drop
        # (negative indices would wrap, so they must be rewritten, not dropped).
        loc = cells - cell_lo
        owned_leaf = (loc >= 0) & (loc < m_owned)
        loc_safe = jnp.where(owned_leaf, loc, m_local)

        grid = (cell_lo + jnp.arange(m_local, dtype=jnp.int32)).astype(
            jnp.float32
        ) / jnp.float32(m)
        cell_first = (
            jnp.searchsorted(data, grid, side="right").astype(jnp.int32) - 1
        )
        cell_first = jnp.clip(cell_first + node_offset, 0, n_total - 1)

        counts = jnp.zeros((m_local,), jnp.int32).at[loc_safe].add(1, mode="drop")
        first_leaf = jnp.full((m_local,), n, jnp.int32).at[loc_safe].min(
            jnp.arange(n, dtype=jnp.int32), mode="drop"
        )
        f_safe = jnp.clip(first_leaf, 0, n - 1)       # window-relative
        left_overlap = data[f_safe] > grid
        overlap = jnp.where(counts > 0, counts + left_overlap.astype(jnp.int32), 1)

        left = jnp.full((n,), INVALID, jnp.int32)
        right = jnp.full((n,), INVALID, jnp.int32)
        leaf_parent = jnp.full((n,), -1, jnp.int32)   # window-relative node ids
        node_parent = jnp.full((n,), -1, jnp.int32)

        if n > 1:
            dL, _L, dR, _R = _nearest_greater(d)
            k = jnp.arange(n - 1, dtype=jnp.int32)
            in_cell = d != sentinel
            owned_k = owned_leaf[:-1]    # separator k lives in cell cells[k]
            is_root = in_cell & (dL == sentinel) & (dR == sentinel)
            par_is_L = dL <= dR
            parent_sep = jnp.where(par_is_L, _L, _R)
            parent_node = parent_sep + 1              # window-relative slot
            node_id = k + 1 + node_offset             # global reference value

            # Internal non-root separators -> child of parent separator's node.
            wr = owned_k & in_cell & ~is_root & par_is_L    # right child of L
            wl = owned_k & in_cell & ~is_root & ~par_is_L   # left child of R
            right = right.at[jnp.where(wr, parent_node, n)].set(node_id, mode="drop")
            left = left.at[jnp.where(wl, parent_node, n)].set(node_id, mode="drop")
            node_parent = node_parent.at[
                jnp.where(owned_k & in_cell & ~is_root, k + 1, n)
            ].set(parent_node, mode="drop")

            # Cell roots -> right child of the cell's root slot.
            root_slot = first_leaf[
                jnp.clip(loc[jnp.clip(k, 0, n - 1)], 0, m_local - 1)
            ]
            wroot = owned_k & is_root
            right = right.at[jnp.where(wroot, root_slot, n)].set(node_id, mode="drop")
            node_parent = node_parent.at[jnp.where(wroot, k + 1, n)].set(
                root_slot, mode="drop"
            )

        # Leaves.
        i = jnp.arange(n, dtype=jnp.int32)
        dl = jnp.where(i > 0, d[jnp.clip(i - 1, 0)], sentinel) if n > 1 else jnp.full(
            (n,), sentinel, jnp.uint32
        )
        dr = jnp.where(i < n - 1, d[jnp.clip(i, 0, max(n - 2, 0))], sentinel) if n > 1 else (
            jnp.full((n,), sentinel, jnp.uint32)
        )
        lone = (dl == sentinel) & (dr == sentinel)
        lpar_is_left = dl <= dr
        lparent = jnp.where(lpar_is_left, i, i + 1)   # node slot (sep i-1 -> node i)
        leaf_ref = ~(i + node_offset)
        wr = owned_leaf & ~lone & lpar_is_left
        wl = owned_leaf & ~lone & ~lpar_is_left
        right = right.at[jnp.where(wr, lparent, n)].set(leaf_ref, mode="drop")
        left = left.at[jnp.where(wl, lparent, n)].set(leaf_ref, mode="drop")
        # Lone leaf: it is its cell's entire tree -> right child of its root slot
        # (which is itself).
        right = right.at[jnp.where(owned_leaf & lone, i, n)].set(leaf_ref, mode="drop")
        leaf_parent = jnp.where(lone, i, lparent)

        # Manual left child of every root slot: the interval overlapping the cell
        # from the left (unreachable when the cell starts exactly at a bound).
        nonempty = counts > 0
        manual = ~jnp.maximum(f_safe + node_offset - 1, 0)
        left = left.at[jnp.where(nonempty, f_safe, n)].set(manual, mode="drop")

        # Guide table.
        table = jnp.where(
            counts == 0,
            ~cell_first,
            jnp.where(overlap == 1, ~(f_safe + node_offset), f_safe + node_offset),
        ).astype(jnp.int32)

    # Traversal depth per leaf -> per-cell fallback flags (paper's degenerate-
    # tree guard: rebuild-as-balanced becomes a per-cell bisection mode).
    with scope("forest.depth_guard"):
        depth, _ = _chain_depth(leaf_parent, node_parent)
        cell_depth = jnp.zeros((m_local,), jnp.int32).at[loc_safe].max(
            depth, mode="drop"
        )
        allowed = jnp.ceil(
            jnp.log2(jnp.maximum(overlap, 2).astype(jnp.float32)))
        fallback = (overlap > 1) & (
            cell_depth > allowed.astype(jnp.int32) + fallback_slack
        )
    return left, right, table, cell_first, fallback


def forest_from_cdf(
    cdf: jax.Array, m: int, fallback_slack: int = 2, d: jax.Array | None = None
) -> RadixForest:
    """Unjitted single-distribution build core — the vmap-safe entry.

    Every op here is batchable, so ``jax.vmap`` over a stacked ``(B, n+1)``
    CDF matrix produces exactly the arrays of B independent builds (the
    fused batched builder in :mod:`repro.pool.batched` rests on this; its
    differential tests pin the bit-identity). ``d`` optionally feeds
    precomputed separator distances (the :mod:`repro.kernels.forest_delta`
    route used by pool delta updates) — they must match
    :func:`_separator_distances` bitwise or the forest silently diverges.
    """
    cdf = jnp.asarray(cdf, jnp.float32)
    n = cdf.shape[0] - 1
    with scope("forest.separators"):
        data = lower_bounds(cdf)  # (n,)
        cells = _cells(data, m)
        if d is None:
            d = _separator_distances(data, cells)
    left, right, table, cf, fallback = _build_cell_trees(
        data, d, cells, m=m, cell_lo=0, m_local=m, fallback_slack=fallback_slack
    )
    cell_first = jnp.concatenate([cf, jnp.int32(n - 1)[None]])
    return RadixForest(cdf, table, left, right, cell_first, fallback)


@functools.partial(jax.jit, static_argnames=("m", "fallback_slack"))
def build_forest_from_cdf(
    cdf: jax.Array, m: int, fallback_slack: int = 2
) -> RadixForest:
    """TPU-native massively parallel forest construction (see module doc)."""
    return forest_from_cdf(cdf, m, fallback_slack)


def build_forest(weights: jax.Array, m: int, fallback_slack: int = 2) -> RadixForest:
    """Weights -> CDF (parallel scan) -> forest. The end-to-end build."""
    with span("repro.build_forest"):
        with span("repro.build_cdf"):
            cdf = build_cdf(weights)
        with span("repro.build_forest_from_cdf"):
            return build_forest_from_cdf(cdf, m, fallback_slack)


# ---------------------------------------------------------------------------
# Faithful Apetrei-style emulation of the paper's Algorithm 1 (ground truth).
# ---------------------------------------------------------------------------


def build_forest_apetrei(cdf: np.ndarray, m: int) -> dict:
    """Round-synchronous numpy emulation of Algorithm 1.

    One logical thread per leaf merges bottom-up; the GPU ``atomicExch`` on
    ``otherBounds[parent]`` is emulated by posting bounds and letting the
    *second* arrival continue (the result is order-independent: the winner
    takes over the identical merged range). Distances use the text's
    "maximum" semantics at cell boundaries (see bits.DIST_SENTINEL note).
    Returns dict(table, left, right) matching :func:`build_forest_from_cdf`.
    """
    cdf = np.asarray(cdf, np.float32)
    n = len(cdf) - 1
    data = np.minimum(cdf[:-1], np.float32(np.nextafter(np.float32(1), np.float32(0))))
    cells = np.clip(np.floor(data * np.float32(m)).astype(np.int64), 0, m - 1)

    def dist(a: int, b: int) -> int:
        """Distance between leaves a and b=a+1 (sentinel at boundaries)."""
        if a < 0 or b > n - 1 or cells[a] != cells[b]:
            return int(DIST_SENTINEL)
        return int(np_xor_distance(data[a : a + 1], data[b : b + 1])[0])

    left = np.full(n, INVALID, np.int64)
    right = np.full(n, INVALID, np.int64)
    other = np.full(n, -1, np.int64)   # otherBounds

    # Thread state: (nodeId, lo, hi); leaves encoded ~i.
    threads = [(~i, i, i) for i in range(n)]
    while threads:
        nxt = []
        for node_id, lo, hi in threads:
            dl, dr = dist(lo - 1, lo), dist(hi, hi + 1)
            if dl == dr == int(DIST_SENTINEL):
                # Cell root (incl. lone leaf): Algorithm 1's tie rule makes it
                # the right child of node range[0] == first leaf of the cell —
                # exactly the root-slot write. Thread terminates.
                right[lo] = node_id
                continue
            child = 0 if dl > dr else 1            # 0 = left child
            parent = hi + 1 if child == 0 else lo
            if child == 0:
                left[parent] = node_id
            else:
                right[parent] = node_id
            # atomicExch(otherBounds[parent], range[child])
            posted = lo if child == 0 else hi
            prev, other[parent] = other[parent], posted
            if prev == -1:
                continue  # first arrival dies; sibling will merge up
            # Second arrival: range[1-child] <- otherBound, continue as parent.
            nlo, nhi = (prev, hi) if child == 1 else (lo, prev)
            nxt.append((parent, nlo, nhi))
        threads = nxt

    # Manual left child per non-empty cell root slot + guide table.
    table = np.zeros(m, np.int64)
    grid = (np.arange(m, dtype=np.float32)) / np.float32(m)
    cf = np.clip(np.searchsorted(data, grid, side="right") - 1, 0, n - 1)
    for c in range(m):
        leaves = np.where(cells == c)[0]
        if len(leaves) == 0:
            table[c] = ~cf[c]
            continue
        f = int(leaves[0])
        overlap = len(leaves) + (1 if data[f] > grid[c] else 0)
        if overlap == 1:
            table[c] = ~f
        else:
            table[c] = f
        left[f] = ~max(f - 1, 0)
    return {
        "table": table.astype(np.int32),
        "left": left.astype(np.int32),
        "right": right.astype(np.int32),
    }


# ---------------------------------------------------------------------------
# Validation / analysis helpers (numpy; used by tests and benchmarks).
# ---------------------------------------------------------------------------


def forest_to_numpy(f: RadixForest) -> dict:
    return {k: np.asarray(v) for k, v in f._asdict().items()}


def validate_forest(f: RadixForest) -> None:
    """Structural invariants; raises AssertionError on violation."""
    fn = forest_to_numpy(f)
    cdf, table, left, right = fn["cdf"], fn["table"], fn["left"], fn["right"]
    n, m = len(left), len(table)
    data = cdf[:-1]
    cells = np.clip(np.floor(data * np.float32(m)).astype(np.int64), 0, m - 1)

    for c in range(m):
        ref = int(table[c])
        leaves = np.where(cells == c)[0]
        if ref < 0:
            i = ~ref
            assert 0 <= i < n
            # the single overlapping interval must cover the cell start
            assert data[i] <= (c / m) + 1e-7 or (len(leaves) == 1 and leaves[0] == i)
            continue
        # In-order traversal of the cell tree must enumerate the cell's
        # leaves in increasing order (plus the manual left-overlap leaf).
        got: list[int] = []
        depth_guard = 0

        def walk(j: int) -> None:
            nonlocal depth_guard
            depth_guard += 1
            assert depth_guard < 10_000
            if j < 0:
                got.append(~j)
                return
            assert 0 <= j < n
            walk(int(left[j]))
            walk(int(right[j]))

        walk(ref)
        f0 = int(leaves[0])
        expect = [max(f0 - 1, 0)] + list(leaves)
        assert got == expect, (c, got, expect)


def depth_stats(f: RadixForest) -> dict:
    """Per-cell traversal depth statistics (node visits to reach a leaf)."""
    fn = forest_to_numpy(f)
    table, left, right = fn["table"], fn["left"], fn["right"]
    n, m = len(left), len(table)
    depths = np.zeros(n, np.int64)

    for c in range(m):
        ref = int(table[c])
        if ref < 0:
            continue
        stack = [(ref, 1)]
        while stack:
            j, dep = stack.pop()
            if j < 0:
                depths[~j] = max(depths[~j], dep)
                continue
            stack.append((int(left[j]), dep + 1))
            stack.append((int(right[j]), dep + 1))
    return {
        "max_depth": int(depths.max(initial=0)),
        "mean_depth": float(depths.mean()) if n else 0.0,
        "depths": depths,
    }
