"""Size-class arenas with per-tenant sampling *method*: many variable-n
tenants, few compiled programs, two drain paths.

The multi-tenant serving problem: thousands of clients each own a *small*
categorical of a *different* size, churning (insert / re-weight / evict) at
request rate. Naively that is one compiled build + one compiled sampler per
distinct ``n`` — a recompile storm. :class:`ForestPool` packs tenants into
**power-of-two size classes** (weights zero-padded to the class size), so
every tenant in a class shares the same stacked arrays and the same handful
of compiled programs — regardless of how many tenants come and go.

Each tenant now also declares HOW it is sampled (the paper's central
tradeoff, made a per-slot attribute):

* ``method="forest"`` — the monotone radix-forest map
  (:class:`~repro.pool.batched.BatchedForest` stacks, ``_SizeClass``
  arenas). Preserves QMC stratification; this is the path for
  stream-sensitive tenants (best-of-n decode, stratified sweeps) and the
  default.
* ``method="alias"`` — packed Walker/Vose tables
  (:class:`~repro.pool.batched.BatchedAlias` stacks, :class:`AliasArena`
  arenas) built by the fused split-and-pack kernel. O(1) per draw, ~100x
  the forest drain's bulk throughput, but a **non-monotone** map that
  destroys low-discrepancy structure — for bulk PRNG tenants only.

Both arena kinds share one slot-lifecycle machine (:class:`_Arena`):
:meth:`ForestPool.insert` hands out a stable :class:`Handle` (size class,
row, true ``n``, version, method). Rows are recycled through a **free
list**; every recycle bumps the row's **version counter**, so a stale
handle (evicted tenant, reused slot) raises instead of silently sampling
someone else's distribution. :meth:`ForestPool.update_weights` re-targets
a tenant in place — forest rows route the Algorithm-1 re-work through
:mod:`repro.kernels.forest_delta` (a bit-identical CDF skips the rebuild),
alias rows re-pack (a bit-identical padded weight row skips). Eviction
clears the freed row's arena state (fallback flags on the forest side, the
packed table row on the alias side).

Zero-padding is sound on both paths: padded forest intervals have zero
width, and padded alias cells are full-deficit lights with ``q == 0`` that
are never an alias target — no uniform in [0, 1) ever resolves to either.

**Admission policy** (the :mod:`repro.robust` boundary): every weight row
entering the pool (``insert`` / ``insert_many`` / ``update_weights``) is
classified against the invariants a monotone CDF needs — finite entries,
no negatives, a positive total that survives the f64 normalize — with a
structured taxonomy (``non_finite`` / ``negative`` / ``zero_total`` /
``overflow_on_pad``, each a ``ValueError`` subclass in
:mod:`repro.robust.errors`). The per-pool ``policy`` decides what a
violation does: ``reject`` (default) raises before anything touches an
arena row; ``clamp`` repairs (NaN->0, +Inf->f32max, negatives->0, then a
uniform placeholder if the total is zero) and admits the repaired row;
``quarantine`` admits a uniform placeholder and flags the handle
(``is_quarantined`` / ``stats()['quarantined']``; ``weights()`` refuses;
a later clean ``update_weights`` clears the flag) — co-tenants in the
same packed batch are untouched in every case. ``off`` skips validation
(benchmark witness only). Stale handles raise
:class:`~repro.robust.errors.StaleHandleError`.

Draining groups draws by ``(method, size class)`` and issues ONE batched
kernel launch per touched group — ``forest_sample_batched`` /
``alias_sample_batched``, or their stream-aware forms under
:meth:`ForestPool.sample_streams`, where per-slot QMC stream state lives
on device (``DeviceQmcStreams`` protocol: ``draw(slots) -> (ctr,
offset_bits, xi)``); forest groups recompute the stream points in-kernel,
alias groups consume the pre-pass points (QMC tenants should stay on the
forest path — serving's ``auto`` method does exactly that). All lanes pad
to power-of-two buckets with **sentinel** dist ids (``-1``): a sentinel
lane resolves to a no-op instead of reading a freed row's stale arrays.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cdf import build_cdf, lower_bounds, normalize_weights
from repro.core.alias import AliasTable
from repro.core.forest import RadixForest, forest_from_cdf
from repro.kernels import ops
from repro.robust.errors import QuarantinedError, StaleHandleError
from repro.robust.validate import check_policy, sanitize_weights

from .batched import BatchedAlias, BatchedForest, build_forest_batched

METHODS = ("forest", "alias")


class Handle(NamedTuple):
    """Stable tenant reference: which class/row, how big, which lifetime,
    and which sampling method its row lives under (``method`` keys the
    arena kind — a forest handle can never resolve against an alias row)."""

    size_class: int  # padded n (power of two) — the class key
    row: int         # row in the class's stacked arrays
    n: int           # true (unpadded) distribution size
    version: int     # row lifetime counter; mismatch => stale handle
    method: str = "forest"  # "forest" (monotone) | "alias" (O(1), PRNG-only)


def _pow2_at_least(x: int, floor: int) -> int:
    p = max(int(floor), 1)
    while p < x:
        p <<= 1
    return p


class _Arena:
    """The shared size-class slot machine: pow2-padded rows, free-list
    recycling, per-row version counters, raw-weight shadow copies. Payload
    storage (forest stacks vs packed alias stacks) is the subclass's
    business via :meth:`_grow_payload`."""

    def __init__(self, size: int, init_rows: int):
        self.size = size
        self.rows = init_rows
        self.n_true = np.zeros(init_rows, np.int64)
        self.versions = np.zeros(init_rows, np.int64)
        self.free: list[int] = list(range(init_rows - 1, -1, -1))
        self.raw: dict[int, np.ndarray] = {}  # row -> float64 raw weights
        self.builds = 0
        self.grows = 0

    @property
    def occupied(self) -> int:
        return self.rows - len(self.free)

    def _grow_payload(self, extra: int) -> None:
        raise NotImplementedError

    def grow(self) -> None:
        extra = self.rows
        self.free.extend(range(self.rows + extra - 1, self.rows - 1, -1))
        self._grow_payload(extra)
        self.n_true = np.concatenate([self.n_true, np.zeros(extra, np.int64)])
        self.versions = np.concatenate([self.versions, np.zeros(extra, np.int64)])
        self.rows += extra
        self.grows += 1

    def take_row(self) -> int:
        if not self.free:
            self.grow()
        return self.free.pop()


class _SizeClass(_Arena):
    """One stacked forest arena: all tenants padded to ``size`` leaves."""

    def __init__(self, size: int, m: int, init_rows: int):
        super().__init__(size, init_rows)
        self.m = m
        self.forest: BatchedForest | None = None  # allocated on first build
        self.degenerate_rows: set[int] = set()  # rows with flagged cells
        self.delta_rebuilds = 0
        self.delta_skips = 0

    def _grow_payload(self, extra: int) -> None:
        if self.forest is not None:
            pad = _zeros_forest(extra, self.size, self.m)
            self.forest = BatchedForest(
                *(jnp.concatenate([a, b]) for a, b in zip(self.forest, pad))
            )


class AliasArena(_Arena):
    """One stacked packed-alias arena: the PRNG fast path's payload.

    Same lifecycle as the forest classes (free list, versions, raw
    shadows); the payload is a :class:`~repro.pool.batched.BatchedAlias`
    stack written by the fused split-and-pack build. ``rebuilds``/``skips``
    count :meth:`ForestPool.update_weights` work (a bit-unchanged padded
    weight row skips the re-pack)."""

    def __init__(self, size: int, init_rows: int):
        super().__init__(size, init_rows)
        self.table: BatchedAlias | None = None  # allocated on first build
        self.rebuilds = 0
        self.skips = 0

    def _grow_payload(self, extra: int) -> None:
        if self.table is not None:
            pad = _zeros_alias(extra, self.size)
            self.table = BatchedAlias(
                *(jnp.concatenate([a, b]) for a, b in zip(self.table, pad))
            )


def _zeros_forest(rows: int, n: int, m: int) -> BatchedForest:
    """Placeholder stack for never-occupied rows (no draw ever routes to a
    row without a live handle, so content only needs valid shapes/dtypes)."""
    return BatchedForest(
        cdf=jnp.zeros((rows, n + 1), jnp.float32),
        table=jnp.zeros((rows, m), jnp.int32),
        left=jnp.zeros((rows, n), jnp.int32),
        right=jnp.zeros((rows, n), jnp.int32),
        cell_first=jnp.zeros((rows, m + 1), jnp.int32),
        fallback=jnp.zeros((rows, m), jnp.bool_),
    )


def _zeros_alias(rows: int, n: int) -> BatchedAlias:
    """Placeholder/cleared alias rows: ``q == 0`` with self-aliases — inert
    even if read (every draw resolves to cell 0's alias 0)."""
    return BatchedAlias(
        q=jnp.zeros((rows, n), jnp.float32),
        alias=jnp.zeros((rows, n), jnp.int32),
    )


class ForestPool:
    """A batched two-method sampling pool over power-of-two size-class
    arenas: radix forests for stream-sensitive (QMC) tenants, packed alias
    tables for bulk PRNG tenants, selected per slot at admission.

    Parameters: ``min_class`` floors the smallest padded size (tiny tenants
    share one class instead of one class per n); ``m`` pins one guide
    resolution for every forest class (default: each class uses
    ``m = size``, the repo-wide guide density); ``init_rows`` is the
    starting arena height, doubled on demand. Forest and alias arenas are
    disjoint per size (``classes`` / ``alias_classes``); a handle's
    ``method`` routes every pool call to the right one. ``policy`` sets the
    weight-admission behavior (``reject`` | ``clamp`` | ``quarantine`` |
    ``off`` — see the module docstring for the taxonomy).
    """

    def __init__(self, min_class: int = 8, m: int | None = None,
                 init_rows: int = 4, policy: str = "reject"):
        if min_class < 1 or (min_class & (min_class - 1)):
            raise ValueError("min_class must be a positive power of two")
        self.min_class = min_class
        self._m = m
        self.init_rows = max(int(init_rows), 1)
        self.policy = check_policy(policy)
        self.classes: dict[int, _SizeClass] = {}
        self.alias_classes: dict[int, AliasArena] = {}
        # (method, size_class, row, version) of handles admitted under the
        # quarantine policy: serving a uniform placeholder, flag queryable.
        self.quarantined: set[tuple[str, int, int, int]] = set()

    # ------------------------------------------------------------- plumbing

    def _class_for(self, n: int, method: str = "forest") -> _Arena:
        if method not in METHODS:
            raise ValueError(f"unknown sampling method {method!r}; "
                             f"expected one of {METHODS}")
        size = _pow2_at_least(n, self.min_class)
        if method == "alias":
            ar = self.alias_classes.get(size)
            if ar is None:
                ar = AliasArena(size, self.init_rows)
                self.alias_classes[size] = ar
            return ar
        sc = self.classes.get(size)
        if sc is None:
            sc = _SizeClass(size, self._m or size, self.init_rows)
            self.classes[size] = sc
        return sc

    def _check(self, h: Handle) -> _Arena:
        # O(1): ``raw`` holds exactly the occupied rows (insert sets, evict
        # pops), and evict bumps the version BEFORE freeing, so a recycled
        # row can never satisfy a stale handle's version. The method field
        # picks the arena table, so a forest handle can never validate
        # against an alias row of the same (size, row) coordinates.
        table = self.alias_classes if h.method == "alias" else self.classes
        sc = table.get(h.size_class)
        if (
            sc is None
            or h.row not in sc.raw
            or sc.versions[h.row] != h.version
        ):
            raise StaleHandleError(f"stale or evicted handle: {h}")
        return sc

    @staticmethod
    def _qkey(h: Handle) -> tuple[str, int, int, int]:
        return (h.method, h.size_class, h.row, h.version)

    def is_quarantined(self, handle: Handle) -> bool:
        """True if the (live) handle was admitted under ``quarantine`` and
        has not since been cleared by a clean ``update_weights``."""
        self._check(handle)
        return self._qkey(handle) in self.quarantined

    def _pad(self, w: np.ndarray, size: int) -> np.ndarray:
        return np.pad(w.astype(np.float32), (0, size - len(w)))

    def _write_rows(self, sc: _SizeClass, rows: list[int],
                    built: BatchedForest) -> None:
        if sc.forest is None:
            sc.forest = _zeros_forest(sc.rows, sc.size, sc.m)
        idx = jnp.asarray(rows, jnp.int32)
        sc.forest = BatchedForest(
            *(a.at[idx].set(b) for a, b in zip(sc.forest, built))
        )

    def _write_alias_rows(self, ar: AliasArena, rows: list[int],
                          built: BatchedAlias) -> None:
        if ar.table is None:
            ar.table = _zeros_alias(ar.rows, ar.size)
        idx = jnp.asarray(rows, jnp.int32)
        ar.table = BatchedAlias(
            *(a.at[idx].set(b) for a, b in zip(ar.table, built))
        )

    # ------------------------------------------------------------ lifecycle

    def insert(self, weights, method: str = "forest") -> Handle:
        """Admit one tenant; see :meth:`insert_many` for the fused path."""
        return self.insert_many([weights], method=method)[0]

    def insert_many(self, weights_list, method="forest") -> list[Handle]:
        """Admit a group of tenants, fusing each (method, size class)
        group's builds into ONE batched launch (``build_forest_batched`` /
        the split-and-pack alias kernel over the stacked padded rows) — the
        build-B-at-once path the pool exists for. ``method`` is a single
        method for the whole wave or a per-tenant sequence
        (``"forest"``/``"alias"``). The group is padded to a power-of-two
        batch so heterogeneous admission waves reuse a logarithmic number
        of compiled build programs.

        Every row passes the pool's admission policy first: under
        ``reject`` a bad row raises (taxonomy class per violation) before
        any arena row is taken; under ``clamp``/``quarantine`` the
        repaired/placeholder row is what gets built, so a poisoned
        submission can never corrupt the packed batch it shares with
        co-tenants."""
        sanitized = [sanitize_weights(w, self.policy) for w in weights_list]
        raws = [r for r, _ in sanitized]
        if isinstance(method, str):
            methods = [method] * len(raws)
        else:
            methods = list(method)
        if len(methods) != len(raws):
            raise ValueError("method list must align with weights_list")
        norms = [normalize_weights(r) for r in raws]
        handles: list[Handle | None] = [None] * len(raws)
        by_group: dict[tuple[str, int], list[int]] = {}
        for i, w in enumerate(norms):
            ar = self._class_for(len(w), methods[i])
            by_group.setdefault((methods[i], ar.size), []).append(i)
        for (meth, size), idxs in by_group.items():
            ar = self._class_for(size, meth)
            rows = [ar.take_row() for _ in idxs]
            stack = np.stack([self._pad(norms[i], size) for i in idxs])
            bpad = _pow2_at_least(len(idxs), 1)
            if bpad != len(idxs):  # dummy rows keep the program count low
                fill = np.full((bpad - len(idxs), size), 1.0, np.float32)
                stack = np.concatenate([stack, fill])
            if meth == "alias":
                q, a = ops.alias_build_batched(jnp.asarray(stack))
                self._write_alias_rows(
                    ar, rows, BatchedAlias(q[: len(idxs)], a[: len(idxs)])
                )
                ar.builds += len(idxs)
                for i, row in zip(idxs, rows):
                    ar.n_true[row] = len(norms[i])
                    ar.raw[row] = raws[i]
                    handles[i] = Handle(
                        size, row, len(norms[i]), int(ar.versions[row]), "alias"
                    )
                    if sanitized[i][1]:
                        self.quarantined.add(self._qkey(handles[i]))
                continue
            built = build_forest_batched(jnp.asarray(stack), ar.m)
            built = BatchedForest(*(x[: len(idxs)] for x in built))
            self._write_rows(ar, rows, built)
            ar.builds += len(idxs)
            # one sync per admission wave keeps the drain path sync-free
            flagged = np.asarray(built.fallback.any(axis=1))
            for (i, row), flag in zip(zip(idxs, rows), flagged):
                ar.n_true[row] = len(norms[i])
                ar.raw[row] = raws[i]
                if flag:
                    ar.degenerate_rows.add(row)
                handles[i] = Handle(size, row, len(norms[i]),
                                    int(ar.versions[row]))
                if sanitized[i][1]:
                    self.quarantined.add(self._qkey(handles[i]))
        return handles  # type: ignore[return-value]

    def update_weights(self, handle: Handle, weights=None, *, delta=None) -> None:
        """In-place re-target of one tenant (full weights or a delta on the
        raw weights). Forest rows route the Algorithm-1 re-work through
        :func:`repro.kernels.ops.forest_delta_update`: bit-unchanged CDFs
        skip the rebuild; otherwise the returned separator distances feed a
        single-row rebuild. Alias rows re-run the split-and-pack on the one
        padded row, with the skip keyed on the padded float32 weight bits.
        The handle stays valid (versions track slot reuse, not content).

        The resulting raw row passes the pool's admission policy: a retune
        that goes bad (all-zero total, a delta driving entries negative,
        NaN poisoning) raises the taxonomy class under ``reject``, is
        repaired under ``clamp``, or swaps the row to the uniform
        placeholder and flags the handle under ``quarantine`` — and a
        clean update clears a standing quarantine flag."""
        sc = self._check(handle)
        if (weights is None) == (delta is None):
            raise ValueError("pass exactly one of weights or delta")
        for name, arr in (("weights", weights), ("delta", delta)):
            if arr is not None and np.asarray(arr).shape != (handle.n,):
                raise ValueError(
                    f"update keeps n fixed: handle has n={handle.n}, got "
                    f"{name} of shape {np.asarray(arr).shape} (scalars and "
                    f"padded-size arrays would silently broadcast)"
                )
        old_raw = sc.raw[handle.row]
        if weights is None:
            proposed = np.asarray(old_raw, np.float64) + np.asarray(delta, np.float64)
        else:
            proposed = np.asarray(weights, np.float64)
        # reject raises here, BEFORE the shadow copy or any arena row moves
        raw, quarantine = sanitize_weights(proposed, self.policy)
        w = normalize_weights(raw)
        if quarantine:
            self.quarantined.add(self._qkey(handle))
        else:
            self.quarantined.discard(self._qkey(handle))
        sc.raw[handle.row] = raw
        if handle.method == "alias":
            new_row = self._pad(w, sc.size)
            old_row = self._pad(normalize_weights(old_raw), sc.size)
            # skip keyed on the exact bits the table is a function of
            if np.array_equal(new_row.view(np.uint32), old_row.view(np.uint32)):
                sc.skips += 1
                return
            q, a = ops.alias_build_batched(jnp.asarray(new_row[None]))
            self._write_alias_rows(sc, [handle.row], BatchedAlias(q, a))
            sc.rebuilds += 1
            return
        new_cdf = build_cdf(jnp.asarray(self._pad(w, sc.size)))
        old_cdf = sc.forest.cdf[handle.row]
        # Skip keyed on raw CDF bits (the dist-layer policy): the clamped
        # lower bounds alone could hide a cdf move inside the last-ulp-
        # below-1 region and leave a stale row serving.
        if np.array_equal(
            np.asarray(old_cdf).view(np.uint32),
            np.asarray(new_cdf).view(np.uint32),
        ):
            sc.delta_skips += 1
            return
        d_new, _ = ops.forest_delta_update(
            lower_bounds(old_cdf), lower_bounds(new_cdf), sc.m
        )
        built = _rebuild_row(new_cdf, d_new, sc.m)
        self._write_rows(sc, [handle.row], BatchedForest(
            *(a[None] for a in built)
        ))
        if bool(jax.device_get(built.fallback.any())):
            sc.degenerate_rows.add(handle.row)
        else:
            sc.degenerate_rows.discard(handle.row)
        sc.delta_rebuilds += 1

    def evict(self, handle: Handle) -> None:
        """Release the tenant's row back to its arena's free list. The
        version bump invalidates every outstanding handle to the row, and
        the freed row's arena state is cleared: forest rows drop their
        fallback bits (a dead degenerate tenant must not force the
        side-table pre-resolution path on the whole class's future drains),
        alias rows zero their packed table (a cleared row is inert even if
        a bug ever routed a lane into it)."""
        sc = self._check(handle)
        self.quarantined.discard(self._qkey(handle))
        sc.versions[handle.row] += 1
        sc.n_true[handle.row] = 0
        sc.raw.pop(handle.row, None)
        sc.free.append(handle.row)
        if handle.method == "alias":
            if sc.table is not None:
                sc.table = BatchedAlias(
                    q=sc.table.q.at[handle.row].set(0.0),
                    alias=sc.table.alias.at[handle.row].set(0),
                )
            return
        if handle.row in sc.degenerate_rows:
            sc.degenerate_rows.discard(handle.row)
            sc.forest = sc.forest._replace(
                fallback=sc.forest.fallback.at[handle.row].set(False)
            )

    # ---------------------------------------------------------- persistence

    def snapshot(self) -> dict:
        """Full serving-state snapshot: every arena payload, free list,
        version counter, raw-weight shadow, and quarantine flag — the
        nested-dict form :func:`repro.ckpt.save_state` commits atomically.
        A pool restored from it (:meth:`restore`) validates every
        outstanding :class:`Handle` and produces bit-identical drains."""

        def common(ar: _Arena) -> dict:
            return dict(
                size=ar.size, rows=ar.rows,
                n_true=ar.n_true.copy(), versions=ar.versions.copy(),
                free=list(ar.free),
                raw={int(r): np.asarray(v) for r, v in ar.raw.items()},
                builds=ar.builds, grows=ar.grows,
            )

        classes = {}
        for size, sc in self.classes.items():
            d = common(sc)
            d.update(
                m=sc.m,
                degenerate_rows=set(sc.degenerate_rows),
                delta_rebuilds=sc.delta_rebuilds,
                delta_skips=sc.delta_skips,
                forest=None if sc.forest is None
                else [np.asarray(x) for x in sc.forest],
            )
            classes[int(size)] = d
        alias_classes = {}
        for size, ar in self.alias_classes.items():
            d = common(ar)
            d.update(
                rebuilds=ar.rebuilds, skips=ar.skips,
                table=None if ar.table is None
                else [np.asarray(x) for x in ar.table],
            )
            alias_classes[int(size)] = d
        return dict(
            kind="forest_pool",
            policy=self.policy, min_class=self.min_class, m=self._m,
            init_rows=self.init_rows,
            quarantined=set(self.quarantined),
            classes=classes, alias_classes=alias_classes,
        )

    @classmethod
    def restore(cls, state: dict) -> "ForestPool":
        """Rebuild a pool from :meth:`snapshot` output (live or round-
        tripped through :func:`repro.ckpt.load_state`). Handles issued by
        the snapshotted pool stay valid — versions are part of the state —
        and subsequent drains are bit-identical."""
        if state.get("kind") != "forest_pool":
            raise ValueError(f"not a ForestPool snapshot: {state.get('kind')!r}")
        pool = cls(min_class=state["min_class"], m=state["m"],
                   init_rows=state["init_rows"], policy=state["policy"])
        pool.quarantined = {tuple(k) for k in state["quarantined"]}

        def load_common(ar: _Arena, d: dict) -> None:
            ar.rows = int(d["rows"])
            ar.n_true = np.asarray(d["n_true"], np.int64).copy()
            ar.versions = np.asarray(d["versions"], np.int64).copy()
            ar.free = [int(r) for r in d["free"]]
            ar.raw = {int(r): np.asarray(v, np.float64)
                      for r, v in d["raw"].items()}
            ar.builds, ar.grows = int(d["builds"]), int(d["grows"])

        for size, d in state["classes"].items():
            sc = _SizeClass(int(d["size"]), int(d["m"]), 1)
            load_common(sc, d)
            sc.degenerate_rows = {int(r) for r in d["degenerate_rows"]}
            sc.delta_rebuilds = int(d["delta_rebuilds"])
            sc.delta_skips = int(d["delta_skips"])
            sc.forest = (None if d["forest"] is None else
                         BatchedForest(*(jnp.asarray(x) for x in d["forest"])))
            pool.classes[int(size)] = sc
        for size, d in state["alias_classes"].items():
            ar = AliasArena(int(d["size"]), 1)
            load_common(ar, d)
            ar.rebuilds, ar.skips = int(d["rebuilds"]), int(d["skips"])
            ar.table = (None if d["table"] is None else
                        BatchedAlias(*(jnp.asarray(x) for x in d["table"])))
            pool.alias_classes[int(size)] = ar
        return pool

    # ------------------------------------------------------------- sampling

    def _drain_plan(self, handles) -> dict[tuple[str, int], list[int]]:
        """Validate handles and group draw indices by (method, size class)
        — each group is one batched kernel launch."""
        for h in set(handles):  # validate each distinct handle once
            self._check(h)
        by_group: dict[tuple[str, int], list[int]] = {}
        for q, h in enumerate(handles):
            by_group.setdefault((h.method, h.size_class), []).append(q)
        return by_group

    def _class_lanes(self, handles, qs) -> tuple[np.ndarray, int]:
        """Per-group lane rows, sentinel-padded (-1) to a pow2 bucket: the
        padding must never route into row 0 — after an evict that row holds
        a freed tenant's stale arrays (forest: fallback-cleared tied chains
        deeper than the kernel's fixed trip count; alias: zeroed table)."""
        qpad = _pow2_at_least(len(qs), 64)  # bucket the drain size too
        didp = np.full(qpad, -1, np.int32)
        didp[: len(qs)] = [handles[q].row for q in qs]
        return didp, qpad

    def _guard_group(self, meth: str, size: int, rows) -> None:
        """Drain-time invariant screen (``guard=True``): before launching a
        group's kernel, vectorized-check the rows it will touch — forest
        rows must hold a finite monotone [0, 1] CDF, alias rows a valid
        split/target table. Catches payload corruption that slipped past
        admission (e.g. a bug writing through a freed row) at the cost the
        ``pool_sampling,guard=on`` bench row witnesses."""
        ridx = np.unique(np.asarray(rows, np.int64))
        ridx = ridx[ridx >= 0]
        if ridx.size == 0:
            return
        if meth == "alias":
            ar = self.alias_classes[size]
            q = np.asarray(ar.table.q)[ridx]
            a = np.asarray(ar.table.alias)[ridx]
            ok = (np.isfinite(q).all() and (q >= 0.0).all()
                  and (q <= 1.0).all() and (a >= 0).all()
                  and (a < ar.size).all())
            if not ok:
                raise ValueError(
                    f"guard: corrupted alias row(s) in size class {size}"
                )
        else:
            sc = self.classes[size]
            cdf = np.asarray(sc.forest.cdf)[ridx]
            ok = (np.isfinite(cdf).all()
                  and (np.diff(cdf, axis=1) >= 0.0).all()
                  and (cdf[:, 0] == 0.0).all() and (cdf[:, -1] == 1.0).all())
            if not ok:
                raise ValueError(
                    f"guard: corrupted forest row(s) in size class {size}"
                )

    def _clip_out(self, out, handles, qs, idx) -> None:
        hi = np.asarray([handles[q].n - 1 for q in qs], np.int64)
        out[qs] = np.minimum(np.asarray(idx)[: len(qs)], hi).astype(np.int32)

    def sample(self, handles, xi, use_pallas: bool | None = None,
               coalesce: bool = True, guard: bool = False) -> np.ndarray:
        """Bulk mixed-batch drain from host uniforms: draw q resolves
        ``xi[q]`` in ``handles[q]``'s distribution. One batched kernel
        launch per touched (method, size class) group — forest groups
        descend ``forest_sample_batched``, alias groups take the O(1)
        ``alias_sample_batched`` path (the whole point: a thousand tenants
        over 3 classes is 3 launches, not 1000). Results are clipped to
        each tenant's true range (zero-width padded intervals / q==0
        padded cells are unreachable). Returns (Q,) int32 row-local
        indices. QMC serving should prefer :meth:`sample_streams`; this is
        the oracle/compat path and the natural PRNG entry point."""
        xi = np.asarray(xi, np.float32)
        if len(handles) != len(xi):
            raise ValueError("handles and xi must align elementwise")
        out = np.empty(len(xi), np.int32)
        for (meth, size), qs in self._drain_plan(handles).items():
            didp, qpad = self._class_lanes(handles, qs)
            if guard:
                self._guard_group(meth, size, didp)
            up = np.pad(xi[qs], (0, qpad - len(qs)))
            if meth == "alias":
                ar = self.alias_classes[size]
                idx = ops.alias_sample_batched(
                    ar.table, jnp.asarray(didp), jnp.asarray(up),
                    use_pallas=use_pallas, coalesce=coalesce,
                )
            else:
                sc = self.classes[size]
                idx = ops.forest_sample_batched(
                    sc.forest, jnp.asarray(didp), jnp.asarray(up),
                    use_pallas=use_pallas, coalesce=coalesce,
                    # host-side flag bookkeeping spares the drain a device sync
                    degenerate=bool(sc.degenerate_rows),
                )
            self._clip_out(out, handles, qs, idx)
        return out

    def sample_streams(self, handles, slots, streams,
                       use_pallas: bool | None = None, coalesce: bool = True,
                       return_xi: bool = False,
                       guard: bool = False) -> np.ndarray:
        """The stream-aware bulk drain: draw q resolves ``slots[q]``'s next
        QMC stream point in ``handles[q]``'s distribution, with the whole
        stream side on device. ``streams`` follows the ``DeviceQmcStreams``
        protocol: ``draw(slots)`` ranks duplicate slots, advances the
        per-slot counters (functionally, device-side), and hands back the
        per-lane rank-adjusted counters + offset bits; each touched forest
        group then runs ONE ``forest_sample_batched_streams`` launch that
        recomputes the points in-kernel and walks coalesced per-tree tiles.
        Alias groups (legal, but they forfeit the stratification the
        streams exist for — serving's ``auto`` method keeps QMC tenants on
        the forest path) consume the pre-pass points through ONE
        ``alias_sample_batched`` launch. Zero host-side counter mutation
        anywhere on this path. With ``return_xi`` also returns the (Q,)
        float32 points that were drawn (bit-equal to the host
        ``QmcStreams`` oracle — differential tests)."""
        slots = np.asarray(slots)
        if len(handles) != len(slots):
            raise ValueError("handles and slots must align elementwise")
        ctr, off, xi = streams.draw(slots)
        out = np.empty(len(slots), np.int32)
        for (meth, size), qs in self._drain_plan(handles).items():
            didp, qpad = self._class_lanes(handles, qs)
            if guard:
                self._guard_group(meth, size, didp)
            sel = jnp.asarray(qs, jnp.int32)
            pad = qpad - len(qs)
            if meth == "alias":
                ar = self.alias_classes[size]
                up = jnp.pad(jnp.asarray(xi)[sel], (0, pad))
                idx = ops.alias_sample_batched(
                    ar.table, jnp.asarray(didp), up,
                    use_pallas=use_pallas, coalesce=coalesce,
                )
                self._clip_out(out, handles, qs, idx)
                continue
            sc = self.classes[size]
            ctrp = jnp.pad(ctr[sel], (0, pad))
            offp = jnp.pad(off[sel], (0, pad))
            idx, _ = ops.forest_sample_batched_streams(
                sc.forest, jnp.asarray(didp), ctrp, offp,
                use_pallas=use_pallas, coalesce=coalesce,
                degenerate=bool(sc.degenerate_rows),
            )
            self._clip_out(out, handles, qs, idx)
        if return_xi:
            return out, np.asarray(xi)
        return out

    # ---------------------------------------------------------- inspection

    def forest_row(self, handle: Handle) -> RadixForest:
        """The tenant's padded forest as a single-distribution view
        (differential tests; serving should drain through :meth:`sample`)."""
        if handle.method != "forest":
            raise ValueError(
                f"handle method is {handle.method!r}; use alias_row"
            )
        sc = self._check(handle)
        return sc.forest.row(handle.row)

    def alias_row(self, handle: Handle) -> AliasTable:
        """The tenant's padded packed alias table as a single-distribution
        view (differential tests; serving drains through :meth:`sample`)."""
        if handle.method != "alias":
            raise ValueError(
                f"handle method is {handle.method!r}; use forest_row"
            )
        ar = self._check(handle)
        return ar.table.row(handle.row)

    def weights(self, handle: Handle) -> np.ndarray:
        """Normalized float32 weights currently served for the tenant.
        Quarantined handles refuse (:class:`QuarantinedError`) — the row
        serves a uniform placeholder, not the tenant's submission, and
        reading it back as if it were theirs would hide the quarantine."""
        sc = self._check(handle)
        if self._qkey(handle) in self.quarantined:
            raise QuarantinedError(
                f"handle is quarantined (serving uniform placeholder): {handle}"
            )
        return normalize_weights(sc.raw[handle.row])

    def stats(self) -> dict:
        """Per-class occupancy/build counters + pool-level tenant count
        (both methods; ``classes`` is the forest side, ``alias_classes``
        the packed-alias side)."""
        per = {
            size: dict(
                m=sc.m, rows=sc.rows, occupied=sc.occupied,
                free=len(sc.free), builds=sc.builds,
                delta_rebuilds=sc.delta_rebuilds,
                delta_skips=sc.delta_skips, grows=sc.grows,
            )
            for size, sc in sorted(self.classes.items())
        }
        aper = {
            size: dict(
                rows=ar.rows, occupied=ar.occupied, free=len(ar.free),
                builds=ar.builds, rebuilds=ar.rebuilds, skips=ar.skips,
                grows=ar.grows,
            )
            for size, ar in sorted(self.alias_classes.items())
        }
        return dict(
            classes=per,
            alias_classes=aper,
            tenants=sum(sc.occupied for sc in self.classes.values())
            + sum(ar.occupied for ar in self.alias_classes.values()),
            policy=self.policy,
            quarantined=len(self.quarantined),
        )


@functools.partial(jax.jit, static_argnames=("m",))
def _rebuild_row(cdf: jax.Array, d: jax.Array, m: int) -> RadixForest:
    """Jitted single-row rebuild from a CDF + precomputed distances (one
    compiled program per size class, shared by every tenant update)."""
    return forest_from_cdf(cdf, m, d=d)
