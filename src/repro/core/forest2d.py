"""Simultaneous multi-row forest construction (paper Sec. 5).

"Building multiple tables and trees simultaneously, e.g. for two-dimensional
distributions, is as simple as adding yet another criterion to the extended
check in Algorithm 1: if the index of the left or right neighbor goes beyond
the *index boundary* of a row, it is a leftmost or a rightmost node."

Here the criterion is folded into the cell id: with per-row guide tables of
m cells, a flat entry (row r, interval j) lives in cell ``r*m +
floor(cdf_r[j]*m)`` — row boundaries change the cell id, which already
clamps the separator distance to the sentinel. ONE data-parallel pass builds
every row tree of a 2-D distribution (H rows x W columns => H*W leaves, H*m
guide cells), with the same perfect load balancing as the 1-D case. This
replaces the per-row Python build loop in the env-map workload (paper's
target application: HDR environment maps, one CDF per image row).

Every per-row quantity is a pure function of that row's data (crossing
separators carry the sentinel distance, so the nearest-greater searches
never escape a row), which buys two properties the 2-D serving layer
(:mod:`repro.spatial`) builds on:

* **Per-row bit-identity.** Row ``r`` of the flat build carries exactly the
  arrays of an independent ``core.build_forest`` over that row's CDF —
  including the per-(row, cell) degenerate-cell ``fallback`` flags computed
  here with the same saturating parent-chase as the 1-D builder.
  :func:`repro.pool.batched.batched_from_row_forest` rewrites the flat
  global references into row-local ones and the result is bit-equal to B
  stacked single builds (the spatial conformance suite pins this), so the
  one-pass builder can feed the fixed-trip batched descent kernel
  (:func:`repro.kernels.forest_sample.forest_sample_batched`).
* **Row-sparse rebuilds.** Because rows never interact, rebuilding a dirty
  subset of rows and scattering the rows into a stacked forest is bit-equal
  to a from-scratch build of the whole stack — the ``update_map`` delta
  path of :class:`repro.spatial.Map2DSampler` rests on exactly this.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .bits import DIST_SENTINEL
from .cdf import lower_bounds
from .forest import INVALID, _chain_depth, _nearest_greater
from .sample import bisect, descend
from .bits import float_to_bits
from ..trace import scope


class RowForest(NamedTuple):
    data: jax.Array        # (R*W,) f32 flat lower bounds (per-row CDFs)
    table: jax.Array       # (R*m,) i32
    left: jax.Array        # (R*W,) i32
    right: jax.Array       # (R*W,) i32
    cell_first: jax.Array  # (R*m + 1,) i32 flat first-overlap per cell
    rows: int
    width: int
    m: int
    fallback: jax.Array    # (R*m,) bool degenerate (row, cell)


@functools.partial(jax.jit, static_argnames=("m", "fallback_slack"))
def build_forest_rows(
    cdf_rows: jax.Array, m: int, fallback_slack: int = 2
) -> RowForest:
    """cdf_rows (R, W+1) per-row CDFs -> all R forests in one pass."""
    R, W1 = cdf_rows.shape
    W = W1 - 1
    n = R * W
    n_cells = R * m
    sentinel = jnp.uint32(DIST_SENTINEL)
    with scope("forest2d.separators"):
        data = lower_bounds(cdf_rows).reshape(n)            # (R*W,) in [0,1)
        local = jnp.clip(
            jnp.floor(data * jnp.float32(m)).astype(jnp.int32), 0, m - 1
        )
        rows = jnp.repeat(jnp.arange(R, dtype=jnp.int32), W)
        cells = rows * m + local                            # (R*W,) flat cells

        bits = float_to_bits(data)
        sep_raw = bits[:-1] ^ bits[1:]
        crossing = cells[:-1] != cells[1:]                  # includes row bounds
        d = jnp.where(crossing, sentinel, sep_raw)

    with scope("forest2d.cell_trees"):
        # first interval overlapping each (row, cell): per-row searchsorted
        grid = jnp.arange(m, dtype=jnp.float32) / jnp.float32(m)
        cf_local = jax.vmap(
            lambda row: jnp.searchsorted(row, grid, side="right").astype(jnp.int32) - 1
        )(data.reshape(R, W))
        cf = jnp.clip(cf_local, 0, W - 1) + (jnp.arange(R, dtype=jnp.int32) * W)[:, None]
        cell_first = jnp.concatenate([cf.reshape(-1), jnp.int32(n - 1)[None]])

        counts = jnp.zeros((n_cells,), jnp.int32).at[cells].add(1)
        first_leaf = jnp.full((n_cells,), n, jnp.int32).at[cells].min(
            jnp.arange(n, dtype=jnp.int32)
        )
        f_safe = jnp.clip(first_leaf, 0, n - 1)
        cell_start = (jnp.arange(n_cells, dtype=jnp.int32) % m).astype(jnp.float32) / m
        left_overlap = data[f_safe] > cell_start
        overlap = jnp.where(counts > 0, counts + left_overlap.astype(jnp.int32), 1)

        left = jnp.full((n,), INVALID, jnp.int32)
        right = jnp.full((n,), INVALID, jnp.int32)
        leaf_parent = jnp.full((n,), -1, jnp.int32)
        node_parent = jnp.full((n,), -1, jnp.int32)

        if n > 1:
            dL, _L, dR, _R = _nearest_greater(d)
            k = jnp.arange(n - 1, dtype=jnp.int32)
            in_cell = ~crossing
            is_root = in_cell & (dL == sentinel) & (dR == sentinel)
            par_is_L = dL <= dR
            parent_node = jnp.where(par_is_L, _L, _R) + 1
            node_id = k + 1
            wr = in_cell & ~is_root & par_is_L
            wl = in_cell & ~is_root & ~par_is_L
            right = right.at[jnp.where(wr, parent_node, n)].set(node_id, mode="drop")
            left = left.at[jnp.where(wl, parent_node, n)].set(node_id, mode="drop")
            node_parent = node_parent.at[
                jnp.where(in_cell & ~is_root, k + 1, n)
            ].set(parent_node, mode="drop")
            root_slot = first_leaf[cells[jnp.clip(k, 0, n - 1)]]
            right = right.at[jnp.where(is_root, root_slot, n)].set(node_id, mode="drop")
            node_parent = node_parent.at[jnp.where(is_root, k + 1, n)].set(
                root_slot, mode="drop"
            )

        i = jnp.arange(n, dtype=jnp.int32)
        if n > 1:
            dl = jnp.where(i > 0, d[jnp.clip(i - 1, 0)], sentinel)
            dr = jnp.where(i < n - 1, d[jnp.clip(i, 0, max(n - 2, 0))], sentinel)
        else:
            dl = jnp.full((n,), sentinel, jnp.uint32)
            dr = jnp.full((n,), sentinel, jnp.uint32)
        lone = (dl == sentinel) & (dr == sentinel)
        lpar_left = dl <= dr
        lparent = jnp.where(lpar_left, i, i + 1)
        right = right.at[jnp.where(~lone & lpar_left, lparent, n)].set(~i, mode="drop")
        left = left.at[jnp.where(~lone & ~lpar_left, lparent, n)].set(~i, mode="drop")
        right = right.at[jnp.where(lone, i, n)].set(~i, mode="drop")
        leaf_parent = jnp.where(lone, i, lparent)

        # manual left child: previous interval IN THE SAME ROW (clamp at row start)
        nonempty = counts > 0
        row_of_f = f_safe // W
        prev_in_row = jnp.maximum(f_safe - 1, row_of_f * W)
        left = left.at[jnp.where(nonempty, f_safe, n)].set(~prev_in_row, mode="drop")

        table = jnp.where(
            counts == 0, ~cell_first[:-1], jnp.where(overlap == 1, ~f_safe, f_safe)
        ).astype(jnp.int32)

    # Traversal depth per leaf -> per-(row, cell) fallback flags: the 1-D
    # builder's walk (core.forest._chain_depth), so the flags are
    # bit-identical per row — chases never cross a row because every parent
    # edge stays inside its cell.
    with scope("forest2d.depth_guard"):
        depth, _ = _chain_depth(leaf_parent, node_parent)
        cell_depth = jnp.zeros((n_cells,), jnp.int32).at[cells].max(depth)
        allowed = jnp.ceil(jnp.log2(jnp.maximum(overlap, 2).astype(jnp.float32)))
        fallback = (overlap > 1) & (
            cell_depth > allowed.astype(jnp.int32) + fallback_slack
        )
    return RowForest(data, table, left, right, cell_first, R, W, m, fallback)


@functools.partial(jax.jit, static_argnames=())
def sample_forest_rows(f: RowForest, row: jax.Array, xi: jax.Array) -> jax.Array:
    """Sample column index within each lane's row: (rows (B,), xi (B,)) ->
    column ids (B,). Batched Algorithm 2 over the flat forest, with the 1-D
    sampler's degenerate-cell bisection, kept inside the lane's row."""
    m, W = f.m, f.width
    g = row * m + jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    j = f.table[g]
    flagged = f.fallback[g] & (j >= 0)
    lo = f.cell_first[g]
    hi = jnp.where(flagged, jnp.minimum(f.cell_first[g + 1], row * W + W - 1), lo)
    j = jnp.where(flagged, ~bisect(f.data, xi, lo, hi)[0], j)
    flat, _ = descend(f.data, f.left, f.right, xi, j)
    return flat - row * W   # column within the row


def validate_forest_rows(f: RowForest) -> None:
    """Structural invariants of the flat multi-row forest; AssertionError on
    violation. The 2-D twin of ``core.forest.validate_forest``: for every
    (row, cell) the guide entry must resolve within the row, and in-order
    traversal of a cell tree must enumerate the cell's leaves in increasing
    order prefixed by the row-clamped left-overlap leaf."""
    data = np.asarray(f.data)
    table = np.asarray(f.table)
    left = np.asarray(f.left)
    right = np.asarray(f.right)
    R, W, m = f.rows, f.width, f.m
    n = R * W
    local = np.clip(np.floor(data * np.float32(m)).astype(np.int64), 0, m - 1)
    cells = np.repeat(np.arange(R), W) * m + local

    for c in range(R * m):
        r = c // m
        ref = int(table[c])
        leaves = np.where(cells == c)[0]
        if ref < 0:
            i = ~ref
            assert r * W <= i < (r + 1) * W, (c, i)  # never leaves the row
            cell_start = (c % m) / m
            assert data[i] <= cell_start + 1e-7 or (
                len(leaves) == 1 and leaves[0] == i
            ), (c, i)
            continue
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
        expect = [max(f0 - 1, r * W)] + list(leaves)
        assert got == expect, (c, got, expect)
        assert all(r * W <= i < (r + 1) * W for i in got), (c, got)


def np_reference_rows(cdf_rows: np.ndarray, row: np.ndarray, xi: np.ndarray):
    """searchsorted oracle per lane."""
    out = np.empty(len(xi), np.int64)
    for i, (r, u) in enumerate(zip(row, xi)):
        out[i] = np.clip(
            np.searchsorted(cdf_rows[r][1:], u, side="right"),
            0, cdf_rows.shape[1] - 2,
        )
    return out
