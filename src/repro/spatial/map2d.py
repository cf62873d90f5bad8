"""Sharded piecewise-constant 2-D serving: environment/density maps as a
row-marginal forest plus pow2-size-class conditional row stacks.

The paper's headline application (Sec. 5 / Fig. 8) samples a 2-D piecewise
constant distribution — an HDR environment map — as a product: a *marginal*
over rows (one CDF of per-row masses) and one *conditional* per row (that
row's texels). :class:`Map2DSampler` serves exactly that decomposition at
bulk granularity:

* **Marginal** — one :class:`~repro.core.forest.RadixForest` over the H row
  masses. With ``sharded=True`` it is built and drained through
  :mod:`repro.dist.forest` instead (cell-partitioned windowed build,
  owner-routed bulk drain) — the marginal is the map's single large
  distribution, so it is the one worth sharding.
* **Conditionals** — all H row distributions, packed the way
  :class:`repro.pool.ForestPool` packs tenants: rows grouped into
  power-of-two width classes (texel weights zero-padded to the class
  width), each class built by ONE :func:`repro.core.forest2d.build_forest_rows`
  launch (the paper's Sec. 5 simultaneous multi-row pass) and rewrapped by
  :func:`repro.pool.batched.batched_from_row_forest` into the stacked
  :class:`~repro.pool.batched.BatchedForest` layout the batched descent
  kernel wants. H per-row Python builds collapse into one launch per class.

:meth:`Map2DSampler.sample_map` resolves a bulk batch of 2-D points: the
marginal descends on ``u``, then every conditional draw resolves in ONE
:func:`repro.kernels.ops.forest_sample_batched` launch per *touched size
class* with ``dist_id = row`` (coalescing pre-pass included) — never one
launch per distinct sampled row. Single-class unsharded maps take a fully
fused jitted pipeline (marginal descent + conditional descent in one
program). Semantics are exact: class rows behave exactly like
``core.build_forest`` over the zero-padded row (the conformance suite pins
elementwise identity against the per-row reference), and **zero-mass rows
are never selected** — their marginal intervals have zero width, which no
uniform in [0, 1) can hit, so no epsilon fudge is needed (or tolerated:
an epsilon would give empty rows real probability).

:meth:`Map2DSampler.update_map` re-targets a sparse set of rows in O(dirty
rows): per touched class, rows whose new padded CDF bits are unchanged skip
(the same raw-bits skip key as the pool), the truly dirty rows rebuild in
one ``build_forest_rows`` launch and scatter into the class stack — bit-
identical to a from-scratch build because rows of the flat builder never
interact. The marginal re-targets through
:func:`repro.kernels.ops.forest_delta_update` (or
:func:`repro.dist.forest.update_forest_sharded` when sharded), with the
CDF-bits skip deciding whether any rebuild runs at all.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cdf import build_cdf, lower_bounds, normalize_weights
from repro.core.forest import RadixForest, forest_from_cdf
from repro.core.forest2d import build_forest_rows
from repro.kernels import ops
from repro.pool.arena import _pow2_at_least
from repro.robust.validate import check_policy, sanitize_weights
from repro.pool.batched import BatchedForest, batched_from_row_forest


class _CondClass:
    """One conditional size class: every map row of padded width ``width``
    stacked into a single :class:`BatchedForest` (slot ``s`` holds map row
    ``row_ids[s]``), plus the exact CDF stack the forests were built from
    (the update skip is keyed on its raw bits) and the host-tracked
    degenerate flag that spares drains a device sync."""

    def __init__(self, width: int, row_ids: list[int],
                 forest: BatchedForest, cdf_rows: jax.Array,
                 degenerate: bool):
        self.width = width           # padded texel count = per-row guide m
        self.row_ids = row_ids       # slot -> map row
        self.forest = forest
        self.cdf_rows = cdf_rows     # (B, width+1) f32 — the skip key
        self.degenerate = degenerate
        self.rebuilds = 0            # update_map: rows actually rebuilt
        self.skips = 0               # update_map: bit-unchanged rows


@functools.partial(
    jax.jit,
    static_argnames=("use_pallas", "marg_degenerate", "cond_degenerate",
                     "coalesce"),
)
def _fused_sample(marg: RadixForest, cond: BatchedForest, slot_of, widths,
                  u, v, *, use_pallas: bool | None, marg_degenerate: bool,
                  cond_degenerate: bool, coalesce: bool):
    """The single-class pipeline as ONE program: marginal descent on ``u``,
    slot lookup, batched conditional descent on ``v``, true-width clip."""
    row = ops.forest_sample(marg, u, use_pallas=use_pallas,
                            degenerate=marg_degenerate)
    col = ops.forest_sample_batched(
        cond, slot_of[row], v, use_pallas=use_pallas,
        degenerate=cond_degenerate, coalesce=coalesce,
    )
    return row, jnp.minimum(col, widths[row] - 1)


@jax.jit
def _cdf_stack(weights: jax.Array) -> jax.Array:
    """(B, W) padded weight rows -> (B, W+1) CDF rows. vmap of the scalar
    ``build_cdf`` — the scan grid is per-row, so every row's bits equal an
    independent ``build_cdf`` call (the class-row semantics contract)."""
    return jax.vmap(build_cdf)(weights)


@functools.partial(jax.jit, static_argnames=("m",))
def _rebuild_marginal(cdf: jax.Array, d: jax.Array, m: int) -> RadixForest:
    """Jitted marginal rebuild from a patched CDF + delta-kernel distances."""
    return forest_from_cdf(cdf, m, d=d)


class Map2DSampler:
    """Bulk 2-D piecewise-constant sampling over an environment/density map.

    ``img`` is a 2-D array (H, W) or a ragged list of per-row weight arrays
    (rows may differ in width; each lands in its power-of-two size class,
    floored at ``min_class``). Weights must be non-negative with positive
    total mass; individual rows may be all-zero and are then *exactly*
    unselectable. ``m_marginal`` sets the marginal guide density (default:
    one cell per row). ``sharded=True`` routes the marginal through
    :mod:`repro.dist.forest` (optional ``mesh``/``rebalance``/``routed``
    mirror that module); conditionals stay in stacked class arenas either
    way — they are many *small* trees, exactly the shape the batched kernel
    serves best. ``use_pallas`` defaults to the repo-wide dispatch policy.

    ``policy`` is the per-map weight-admission policy (``reject`` |
    ``clamp`` | ``quarantine`` | ``off``, see :mod:`repro.robust`): each
    row classifies against the structured taxonomy — non-finite or
    negative entries raise under ``reject`` (NaN rows previously slipped
    through to opaque downstream errors) and are repaired / replaced by
    the uniform placeholder under ``clamp``/``quarantine``. All-zero rows
    are NOT violations here: a zero-mass row is exactly unselectable by
    the marginal, the map's long-standing semantics.
    """

    def __init__(self, img, *, m_marginal: int | None = None,
                 min_class: int = 8, sharded: bool = False, mesh=None,
                 rebalance: bool = False, routed: bool = True,
                 use_pallas: bool | None = None, coalesce: bool = True,
                 fallback_slack: int = 2, policy: str = "reject"):
        if min_class < 1 or (min_class & (min_class - 1)):
            raise ValueError("min_class must be a positive power of two")
        self.policy = check_policy(policy)
        rows = [np.asarray(r, np.float64) for r in img]
        if not rows:
            raise ValueError("map must have at least one row")
        rows = [
            sanitize_weights(w, policy, allow_zero_total=True)[0]
            for w in rows
        ]
        self.rows_raw = rows
        self.H = len(rows)
        self.widths = np.asarray([len(w) for w in rows], np.int64)
        self.row_offsets = np.concatenate(
            [[0], np.cumsum(self.widths)]
        ).astype(np.int64)
        self.row_mass = np.asarray([w.sum() for w in rows], np.float64)
        self.min_class = min_class
        self.fallback_slack = fallback_slack
        self.coalesce = coalesce
        self.use_pallas = use_pallas
        self.sharded = sharded
        self.routed = routed
        self.last_drain: dict | None = None

        # ---- marginal over row masses (zero-mass rows: zero-width interval)
        self.m_marginal = int(m_marginal) if m_marginal else self.H
        marg_w = normalize_weights(self.row_mass)  # raises on zero total
        if sharded:
            from repro.dist import forest as DF

            self._DF = DF
            self._marginal, self._mesh = DF.build_forest_sharded_auto(
                marg_w, self.m_marginal, mesh=mesh,
                fallback_slack=fallback_slack, rebalance=rebalance,
            )
            self.m_marginal = self._marginal.m  # rounded to a shard multiple
            self._marg_degenerate = False       # sharded drain self-handles
        else:
            cdf = build_cdf(jnp.asarray(marg_w))
            self._marginal = forest_from_cdf(
                cdf, self.m_marginal, fallback_slack=fallback_slack
            )
            self._marg_degenerate = bool(
                jax.device_get(self._marginal.fallback.any())
            )

        # ---- conditionals: one RowForest launch per pow2 width class
        self.classes: dict[int, _CondClass] = {}
        self._class_of = np.empty(self.H, np.int64)  # row -> class width
        self._slot_of = np.empty(self.H, np.int64)   # row -> slot in class
        by_class: dict[int, list[int]] = {}
        for r in range(self.H):
            wc = _pow2_at_least(int(self.widths[r]), min_class)
            by_class.setdefault(wc, []).append(r)
        for wc, rids in sorted(by_class.items()):
            stack = np.stack([self._padded_cond(r, wc) for r in rids])
            cdf_rows = _cdf_stack(jnp.asarray(stack))
            rf = build_forest_rows(cdf_rows, m=wc,
                                   fallback_slack=fallback_slack)
            bf = batched_from_row_forest(rf, cdf_rows)
            degenerate = bool(jax.device_get(bf.fallback.any()))
            self.classes[wc] = _CondClass(wc, rids, bf, cdf_rows, degenerate)
            for slot, r in enumerate(rids):
                self._class_of[r] = wc
                self._slot_of[r] = slot
        self._slot_j = jnp.asarray(self._slot_of, jnp.int32)
        self._widths_j = jnp.asarray(self.widths, jnp.int32)
        # fused single-program pipeline: one class, unsharded marginal
        self._fused = (not sharded) and len(self.classes) == 1

    # ------------------------------------------------------------- plumbing

    def _padded_cond(self, r: int, wc: int) -> np.ndarray:
        """Row ``r``'s conditional weights, normalized and zero-padded to the
        class width. Zero-mass rows get a uniform placeholder: the marginal
        can never select them (zero-width interval), but the class stack
        needs a valid distribution in the slot."""
        w = self.rows_raw[r]
        if self.row_mass[r] <= 0:
            w = np.ones(len(w), np.float64)
        w32 = normalize_weights(w)
        return np.pad(w32, (0, wc - len(w32)))

    def flat_index(self, rows, cols) -> np.ndarray:
        """(row, col) pairs -> flat texel ids over the ragged map layout."""
        return self.row_offsets[np.asarray(rows)] + np.asarray(cols)

    def marginal_weights(self) -> np.ndarray:
        """Normalized float32 row-marginal currently served."""
        return normalize_weights(self.row_mass)

    # ------------------------------------------------------------- sampling

    def _sample_marginal(self, u: jax.Array) -> jax.Array:
        if self.sharded:
            return self._DF.sample_sharded(
                self._marginal, u, mesh=self._mesh, routed=self.routed
            )
        return ops.forest_sample(
            self._marginal, u, use_pallas=self.use_pallas,
            degenerate=self._marg_degenerate,
        )

    def sample_map(self, points2d):
        """Bulk 2-D drain: ``points2d`` (B, 2) uniforms (or a ``(u, v)``
        pair) -> ``(row, col, xi_u, xi_v)`` int32/int32/f32/f32 arrays.

        ``u`` descends the row marginal, ``v`` the selected rows'
        conditionals — ONE batched launch per touched size class with
        ``dist_id`` = the row's class slot (the launch count lands in
        ``self.last_drain``, the structural fact the benchmarks pin).
        Elementwise identical to the per-row ``build_forest`` +
        ``sample_forest`` reference over the padded rows."""
        if isinstance(points2d, tuple):
            u, v = points2d
            u = np.asarray(u, np.float32)
            v = np.asarray(v, np.float32)
        else:
            pts = np.asarray(points2d, np.float32)
            if pts.ndim != 2 or pts.shape[1] != 2:
                raise ValueError("points2d must have shape (B, 2)")
            u, v = pts[:, 0], pts[:, 1]
        if self._fused:
            cls = next(iter(self.classes.values()))
            row, col = _fused_sample(
                self._marginal, cls.forest, self._slot_j, self._widths_j,
                jnp.asarray(u), jnp.asarray(v),
                use_pallas=self.use_pallas,
                marg_degenerate=self._marg_degenerate,
                cond_degenerate=cls.degenerate,
                coalesce=self.coalesce,
            )
            self.last_drain = dict(
                launches=1, fused=True, classes=[cls.width],
                marginal="fused",
            )
            return (np.asarray(row, np.int32), np.asarray(col, np.int32),
                    u, v)

        rows = np.asarray(self._sample_marginal(jnp.asarray(u)), np.int64)
        cols = np.empty(len(rows), np.int32)
        touched = []
        for wc in np.unique(self._class_of[rows]):
            cls = self.classes[int(wc)]
            qs = np.flatnonzero(self._class_of[rows] == wc)
            qpad = _pow2_at_least(len(qs), 64)
            didp = np.full(qpad, -1, np.int32)
            didp[: len(qs)] = self._slot_of[rows[qs]]
            vp = np.pad(v[qs], (0, qpad - len(qs)))
            idx = ops.forest_sample_batched(
                cls.forest, jnp.asarray(didp), jnp.asarray(vp),
                use_pallas=self.use_pallas, degenerate=cls.degenerate,
                coalesce=self.coalesce,
            )
            hi = (self.widths[rows[qs]] - 1).astype(np.int64)
            cols[qs] = np.minimum(
                np.asarray(idx)[: len(qs)], hi
            ).astype(np.int32)
            touched.append(int(wc))
        self.last_drain = dict(
            launches=len(touched), fused=False, classes=touched,
            marginal="sharded" if self.sharded else "direct",
        )
        return rows.astype(np.int32), cols, u, v

    # -------------------------------------------------------------- updates

    def update_map(self, delta_rows: dict, *, delta: bool = False) -> dict:
        """Re-target a sparse set of rows: ``delta_rows`` maps row -> new
        raw weights (or an additive delta with ``delta=True``); widths stay
        fixed. Per touched class, rows whose new padded CDF bits are
        unchanged skip; the truly dirty rows rebuild in ONE
        ``build_forest_rows`` launch and scatter into the class stack —
        bit-identical to a from-scratch :class:`Map2DSampler` over the new
        map (rows of the flat builder never interact). The marginal patches
        through the delta kernel (sharded: ``update_forest_sharded``), with
        its own CDF-bits skip. Returns stats: ``rebuilt_rows`` /
        ``skipped_rows`` (the O(dirty rows) structural witness),
        ``cond_launches``, ``marginal_rebuilt``."""
        by_class: dict[int, list[int]] = {}
        for r, w in delta_rows.items():
            r = int(r)
            if not 0 <= r < self.H:
                raise ValueError(f"row {r} out of range")
            w = np.asarray(w, np.float64)
            if w.shape != (int(self.widths[r]),):
                raise ValueError(
                    f"update keeps widths fixed: row {r} has width "
                    f"{int(self.widths[r])}, got shape {w.shape}"
                )
            raw = self.rows_raw[r] + w if delta else w
            # same admission policy as construction (reject raises the
            # structured class before any map state moves)
            raw = sanitize_weights(raw, self.policy, allow_zero_total=True)[0]
            self.rows_raw[r] = raw
            self.row_mass[r] = raw.sum()
            by_class.setdefault(int(self._class_of[r]), []).append(r)

        stats = dict(rebuilt_rows=0, skipped_rows=0, cond_launches=0,
                     marginal_rebuilt=False)
        for wc, rids in sorted(by_class.items()):
            cls = self.classes[wc]
            slots = np.asarray([self._slot_of[r] for r in rids], np.int64)
            stack = np.stack([self._padded_cond(r, wc) for r in rids])
            new_cdf = _cdf_stack(jnp.asarray(stack))
            old_bits = np.asarray(cls.cdf_rows)[slots].view(np.uint32)
            new_bits = np.asarray(new_cdf).view(np.uint32)
            dirty = np.flatnonzero((old_bits != new_bits).any(axis=1))
            stats["skipped_rows"] += len(rids) - len(dirty)
            cls.skips += len(rids) - len(dirty)
            if len(dirty) == 0:
                continue
            # one multi-row launch for the class's dirty rows, padded to a
            # pow2 batch (repeat row 0) so update sizes share programs
            dpad = _pow2_at_least(len(dirty), 8)
            sel = np.concatenate(
                [dirty, np.zeros(dpad - len(dirty), np.int64)]
            )
            cdf_dirty = new_cdf[jnp.asarray(sel)]
            rf = build_forest_rows(cdf_dirty, m=wc,
                                   fallback_slack=self.fallback_slack)
            built = batched_from_row_forest(rf, cdf_dirty)
            idx = jnp.asarray(slots[dirty], jnp.int32)
            cls.forest = BatchedForest(
                *(a.at[idx].set(b[: len(dirty)])
                  for a, b in zip(cls.forest, built))
            )
            cls.cdf_rows = cls.cdf_rows.at[idx].set(
                new_cdf[jnp.asarray(dirty)]
            )
            cls.degenerate = bool(jax.device_get(cls.forest.fallback.any()))
            cls.rebuilds += len(dirty)
            stats["rebuilt_rows"] += len(dirty)
            stats["cond_launches"] += 1

        # ---- marginal delta (row masses may have moved)
        marg_w = normalize_weights(self.row_mass)
        if self.sharded:
            self._marginal, mst = self._DF.update_forest_sharded(
                self._marginal, marg_w, mesh=self._mesh,
                fallback_slack=self.fallback_slack, with_stats=True,
            )
            stats["marginal_rebuilt"] = bool(mst["rebuilt"])
            stats["marginal_shards"] = mst
        else:
            new_cdf = build_cdf(jnp.asarray(marg_w))
            old_cdf = self._marginal.cdf
            if np.array_equal(
                np.asarray(old_cdf).view(np.uint32),
                np.asarray(new_cdf).view(np.uint32),
            ):
                return stats
            d_new, _ = ops.forest_delta_update(
                lower_bounds(old_cdf), lower_bounds(new_cdf),
                self.m_marginal, use_pallas=self.use_pallas,
            )
            self._marginal = _rebuild_marginal(
                new_cdf, d_new, self.m_marginal
            )
            self._marg_degenerate = bool(
                jax.device_get(self._marginal.fallback.any())
            )
            stats["marginal_rebuilt"] = True
        return stats

    # ---------------------------------------------------------- inspection

    def stats(self) -> dict:
        """Per-class shape/update counters + marginal coordinates."""
        return dict(
            H=self.H,
            m_marginal=self.m_marginal,
            sharded=self.sharded,
            policy=self.policy,
            classes={
                wc: dict(rows=len(c.row_ids), rebuilds=c.rebuilds,
                         skips=c.skips, degenerate=c.degenerate)
                for wc, c in sorted(self.classes.items())
            },
        )
