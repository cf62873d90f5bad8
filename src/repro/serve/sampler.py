"""Token samplers for serving: the paper's monotone inversion vs the Alias
Method, with per-slot QMC uniform streams.

Modes:
  * ``inverse_qmc``  — fused softmax->CDF + tiled inverse (kernels), uniforms
    from per-slot scrambled van-der-Corput streams. Monotone warp => the
    stream's stratification survives (paper Sec. 3); best-of-n decode from
    one distribution provably covers the distribution better (benchmark
    ``benchmarks/serving_diversity.py``).
  * ``inverse_rng``  — same mapping, PRNG uniforms (the MC baseline).
  * ``alias``        — Walker/Vose per-row alias tables (serial build, non-
    monotone mapping; the paper's antagonist, kept for comparison).

QMC streams come in a host/device pair sharing ONE exact 24-bit fixed-point
pipeline (``core.lds.qmc_bits24*``): per-slot counters, Cranley-Patterson
offsets quantized to the 2^-24 grid, base-2 radical inverse by bit reversal,
rotation as integer add mod 2^24. :class:`QmcStreams` is the numpy oracle;
:class:`DeviceQmcStreams` keeps the same state as jax arrays and advances it
inside one jitted program per drain, so the serving hot path
(:class:`PooledForestSampler` -> ``ForestPool.sample_streams`` -> the
stream-aware ``forest_sample_batched_streams`` kernel) mutates no host-side
bookkeeping at all — and the differential suite asserts the two are
bit-equal, counters and points, including duplicate slots in one drain.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import build_forest, sample_forest
from repro.core.alias import build_alias, sample_alias
from repro.core.cdf import normalize_weights, updated_weights
from repro.core.lds import (
    QMC_SCALE,
    qmc2_point,
    qmc2_point_np,
    qmc_bits24_np,
    qmc_offset_bits_np,
    qmc_point,
)
from repro.kernels import ops


class QmcStreams:
    """Per-slot low-discrepancy uniform streams with Cranley-Patterson
    rotations (slot-hash offsets keep slots decorrelated but stratified).

    The host-side oracle of the stream pair: pure numpy, one counter per
    slot, points drawn through the exact fixed-point pipeline shared with
    :class:`DeviceQmcStreams` (same seed => bit-equal points and counters).
    Serving hot paths should prefer the device twin; this class remains the
    reference for differential tests and host-only callers."""

    def __init__(self, n_slots: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.offset_bits = qmc_offset_bits_np(rng.random(n_slots))
        self.offsets = self.offset_bits.astype(np.float32) * QMC_SCALE
        self.counters = np.zeros(n_slots, np.uint32)

    def next(self, slots: np.ndarray | None = None) -> np.ndarray:
        """One stream point per requested slot occurrence. A slot repeated k
        times in one drain draws its next k *distinct* stream points (the
        j-th occurrence, in call order, advances to counter+j) and its
        counter advances by k — fancy-index ``counters[slots] += 1`` would
        collapse duplicate increments and hand every occurrence the same
        point (identical best-of-n candidates)."""
        if slots is None:
            slots = np.arange(len(self.offset_bits))
        slots = np.asarray(slots)
        rank = _occurrence_rank_np(slots)
        xi = qmc_bits24_np(
            self.counters[slots] + rank, self.offset_bits[slots]
        ).astype(np.float32) * QMC_SCALE
        np.add.at(self.counters, slots, 1)
        return xi

    def snapshot(self) -> dict:
        """Exact stream state (offset bits + counters): restoring it makes
        every subsequent draw bit-identical to an uninterrupted stream."""
        return dict(kind="qmc_streams",
                    offset_bits=self.offset_bits.copy(),
                    counters=self.counters.copy())

    @classmethod
    def restore(cls, state: dict) -> "QmcStreams":
        s = cls.__new__(cls)
        s.offset_bits = np.asarray(state["offset_bits"], np.uint32).copy()
        s.offsets = s.offset_bits.astype(np.float32) * QMC_SCALE
        s.counters = np.asarray(state["counters"], np.uint32).copy()
        return s


def _occurrence_rank_np(slots: np.ndarray) -> np.ndarray:
    """Per-occurrence rank of each slot within one drain (call order): the
    j-th occurrence of a slot gets rank j. Stable sort + searchsorted."""
    order = np.argsort(slots, kind="stable")
    sorted_slots = slots[order]
    first = np.searchsorted(sorted_slots, sorted_slots, side="left")
    rank = np.empty(len(slots), np.uint32)
    rank[order] = (np.arange(len(slots)) - first).astype(np.uint32)
    return rank


def _pow2_at_least(x: int, floor: int) -> int:
    p = max(int(floor), 1)
    while p < x:
        p <<= 1
    return p


@jax.jit
def _stream_prepass(counters: jax.Array, offset_bits: jax.Array,
                    slots: jax.Array):
    """Device twin of one ``QmcStreams.next`` drain, as a single program:
    per-occurrence rank (stable sort — identical to the host rank), per-lane
    rank-adjusted counters + offsets, the drawn points, and the advanced
    per-slot counters. Sentinel lanes (``slots < 0``, padding so drain
    shapes bucket to a few compiled programs) draw a dead point and advance
    nothing."""
    S = counters.shape[0]
    valid = slots >= 0
    # sentinels sort AFTER every real slot so they never perturb real ranks
    key = jnp.where(valid, slots, S)
    order = jnp.argsort(key, stable=True)
    sk = key[order]
    first = jnp.searchsorted(sk, sk, side="left")
    rank = jnp.zeros(slots.shape[0], jnp.uint32).at[order].set(
        (jnp.arange(slots.shape[0]) - first).astype(jnp.uint32)
    )
    sl = jnp.where(valid, slots, 0)
    ctr = jnp.where(valid, counters[sl] + rank, 0).astype(jnp.uint32)
    off = jnp.where(valid, offset_bits[sl], 0).astype(jnp.uint32)
    new_counters = counters.at[sl].add(valid.astype(jnp.uint32))
    return ctr, off, qmc_point(ctr, off), new_counters


class DeviceQmcStreams:
    """Device-side twin of :class:`QmcStreams`: the per-slot counters and
    Cranley-Patterson offset bits live as jax arrays, and a drain advances
    them inside :func:`_stream_prepass` — zero host-side counter mutation.
    Same seed as the host class => bit-equal offsets, counters, and points
    (both run the exact ``core.lds`` fixed-point pipeline).

    ``draw`` is the pool-facing protocol: it returns the per-lane
    rank-adjusted ``(counter, offset_bits, xi)`` arrays that thread into the
    stream-aware drain kernel (which recomputes the very same ``xi``
    in-kernel). ``next`` matches the host API for standalone callers."""

    def __init__(self, n_slots: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.offset_bits = jnp.asarray(qmc_offset_bits_np(rng.random(n_slots)))
        self.counters = jnp.zeros(n_slots, jnp.uint32)

    @property
    def n_slots(self) -> int:
        return int(self.offset_bits.shape[0])

    @property
    def offsets(self) -> np.ndarray:
        return np.asarray(self.offset_bits).astype(np.float32) * QMC_SCALE

    def draw(self, slots) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Advance every requested slot occurrence and return the per-lane
        stream state ``(counter, offset_bits, xi)``, each (Q,) on device.
        Drain lengths are padded (power-of-two, floor 64, sentinel slots) so
        churning batch sizes reuse a logarithmic number of programs."""
        slots = np.asarray(slots)
        Q = len(slots)
        qpad = _pow2_at_least(Q, 64)
        padded = np.full(qpad, -1, np.int32)
        padded[:Q] = slots.astype(np.int32)
        ctr, off, xi, self.counters = _stream_prepass(
            self.counters, self.offset_bits, jnp.asarray(padded)
        )
        return ctr[:Q], off[:Q], xi[:Q]

    def next(self, slots: np.ndarray | None = None) -> np.ndarray:
        """Host-API-compatible drain (returns the points as numpy)."""
        if slots is None:
            slots = np.arange(self.n_slots)
        return np.asarray(self.draw(slots)[2])

    def snapshot(self) -> dict:
        return dict(kind="device_qmc_streams",
                    offset_bits=np.asarray(self.offset_bits),
                    counters=np.asarray(self.counters))

    @classmethod
    def restore(cls, state: dict) -> "DeviceQmcStreams":
        s = cls.__new__(cls)
        s.offset_bits = jnp.asarray(np.asarray(state["offset_bits"], np.uint32))
        s.counters = jnp.asarray(np.asarray(state["counters"], np.uint32))
        return s


class Qmc2Streams:
    """Per-slot 2-D low-discrepancy streams: the host oracle of the 2-D
    stream pair. Dimension u is the base-2 radical inverse (Sobol' dim 0),
    dimension v is Sobol' dim 1 — the exact 24-bit integer pipeline of
    ``core.lds.qmc2_*`` — with independent per-slot Cranley-Patterson
    rotations per dimension. One counter per slot drives both dimensions
    (a 2-D stream point is ONE sequence element; advancing dimensions
    separately would desynchronize the pair and destroy the 2-D
    stratification). Same seed as :class:`DeviceQmc2Streams` => bit-equal
    offsets, counters, and points."""

    def __init__(self, n_slots: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.offset_u = qmc_offset_bits_np(rng.random(n_slots))
        self.offset_v = qmc_offset_bits_np(rng.random(n_slots))
        self.counters = np.zeros(n_slots, np.uint32)

    def next(self, slots: np.ndarray | None = None):
        """One 2-D stream point per requested slot occurrence (duplicate
        slots get distinct consecutive points — same rank protocol as
        :class:`QmcStreams`). Returns ``(u, v)`` float32 arrays."""
        if slots is None:
            slots = np.arange(len(self.offset_u))
        slots = np.asarray(slots)
        rank = _occurrence_rank_np(slots)
        ctr = self.counters[slots] + rank
        u, v = qmc2_point_np(ctr, self.offset_u[slots], self.offset_v[slots])
        np.add.at(self.counters, slots, 1)
        return u, v

    def snapshot(self) -> dict:
        return dict(kind="qmc2_streams",
                    offset_u=self.offset_u.copy(),
                    offset_v=self.offset_v.copy(),
                    counters=self.counters.copy())

    @classmethod
    def restore(cls, state: dict) -> "Qmc2Streams":
        s = cls.__new__(cls)
        s.offset_u = np.asarray(state["offset_u"], np.uint32).copy()
        s.offset_v = np.asarray(state["offset_v"], np.uint32).copy()
        s.counters = np.asarray(state["counters"], np.uint32).copy()
        return s


@jax.jit
def _stream_prepass2(counters: jax.Array, offset_u: jax.Array,
                     offset_v: jax.Array, slots: jax.Array):
    """Device twin of one ``Qmc2Streams.next`` drain as a single program —
    the 2-D sibling of :func:`_stream_prepass` (same sentinel-slot padding
    and duplicate-rank protocol, two rotated dimensions out)."""
    S = counters.shape[0]
    valid = slots >= 0
    key = jnp.where(valid, slots, S)
    order = jnp.argsort(key, stable=True)
    sk = key[order]
    first = jnp.searchsorted(sk, sk, side="left")
    rank = jnp.zeros(slots.shape[0], jnp.uint32).at[order].set(
        (jnp.arange(slots.shape[0]) - first).astype(jnp.uint32)
    )
    sl = jnp.where(valid, slots, 0)
    ctr = jnp.where(valid, counters[sl] + rank, 0).astype(jnp.uint32)
    ou = jnp.where(valid, offset_u[sl], 0).astype(jnp.uint32)
    ov = jnp.where(valid, offset_v[sl], 0).astype(jnp.uint32)
    u, v = qmc2_point(ctr, ou, ov)
    new_counters = counters.at[sl].add(valid.astype(jnp.uint32))
    return u, v, new_counters


class DeviceQmc2Streams:
    """Device-side twin of :class:`Qmc2Streams`: counters and both offset
    vectors live as jax arrays; a drain advances them inside
    :func:`_stream_prepass2` with zero host-side counter mutation. Same
    seed as the host class => bit-equal points and counters (the spatial
    differential suite pins this, duplicate slots included)."""

    def __init__(self, n_slots: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.offset_u = jnp.asarray(qmc_offset_bits_np(rng.random(n_slots)))
        self.offset_v = jnp.asarray(qmc_offset_bits_np(rng.random(n_slots)))
        self.counters = jnp.zeros(n_slots, jnp.uint32)

    @property
    def n_slots(self) -> int:
        return int(self.offset_u.shape[0])

    def draw(self, slots) -> tuple[jax.Array, jax.Array]:
        """Advance every requested slot occurrence; returns the ``(u, v)``
        point pair, each (Q,) float32 on device. Drain lengths pad to
        pow2 (floor 64, sentinel slots) exactly like the 1-D streams."""
        slots = np.asarray(slots)
        Q = len(slots)
        qpad = _pow2_at_least(Q, 64)
        padded = np.full(qpad, -1, np.int32)
        padded[:Q] = slots.astype(np.int32)
        u, v, self.counters = _stream_prepass2(
            self.counters, self.offset_u, self.offset_v, jnp.asarray(padded)
        )
        return u[:Q], v[:Q]

    def next(self, slots: np.ndarray | None = None):
        """Host-API-compatible drain (returns ``(u, v)`` as numpy)."""
        if slots is None:
            slots = np.arange(self.n_slots)
        u, v = self.draw(slots)
        return np.asarray(u), np.asarray(v)

    def snapshot(self) -> dict:
        return dict(kind="device_qmc2_streams",
                    offset_u=np.asarray(self.offset_u),
                    offset_v=np.asarray(self.offset_v),
                    counters=np.asarray(self.counters))

    @classmethod
    def restore(cls, state: dict) -> "DeviceQmc2Streams":
        s = cls.__new__(cls)
        s.offset_u = jnp.asarray(np.asarray(state["offset_u"], np.uint32))
        s.offset_v = jnp.asarray(np.asarray(state["offset_v"], np.uint32))
        s.counters = jnp.asarray(np.asarray(state["counters"], np.uint32))
        return s


_STREAM_KINDS = {
    "qmc_streams": "QmcStreams",
    "device_qmc_streams": "DeviceQmcStreams",
    "qmc2_streams": "Qmc2Streams",
    "device_qmc2_streams": "DeviceQmc2Streams",
}


def restore_streams(state: dict):
    """Dispatch a stream snapshot back to its class by ``kind``."""
    if state is None:
        return None
    cls = globals()[_STREAM_KINDS[state["kind"]]]
    return cls.restore(state)


def _rng_state(rng):
    return None if rng is None else rng.bit_generator.state


def _rng_restore(state):
    if state is None:
        return None
    rng = np.random.default_rng()
    rng.bit_generator.state = state
    return rng


class SpatialSampler:
    """2-D serving sampler: ONE shared environment/density map
    (:class:`repro.spatial.Map2DSampler`) drained by per-slot 2-D QMC
    streams — the paper's env-map application behind the serving API.

    Each ``sample`` call draws one 2-D stream point per slot occurrence
    (``streams="qmc"``: the exact 24-bit Sobol' pair with device-side
    counters; ``streams="prng"``: a seeded PRNG baseline) and resolves the
    whole batch through :meth:`~repro.spatial.Map2DSampler.sample_map` —
    marginal descent on u, one batched conditional launch per touched size
    class on v. Both warps are monotone, so the 2-D stratification of the
    streams survives into texel space. :meth:`update` re-targets dirty map
    rows in place; slot streams keep their counters, exactly as the 1-D
    samplers do across distribution swaps."""

    def __init__(self, img, n_slots: int = 64, seed: int = 0,
                 streams: str = "qmc", device_streams: bool = True,
                 use_pallas: bool | None = None, **map_kwargs):
        from repro.spatial import Map2DSampler  # lazy: serve stays importable

        if streams not in ("qmc", "prng"):
            raise ValueError(f"streams must be 'qmc' or 'prng', got {streams!r}")
        self.map = Map2DSampler(img, use_pallas=use_pallas, **map_kwargs)
        self.stream_kind = streams
        self.device_streams = device_streams and streams == "qmc"
        if streams == "qmc":
            self.streams = (
                DeviceQmc2Streams(n_slots, seed) if device_streams
                else Qmc2Streams(n_slots, seed)
            )
            self.rng = None
        else:
            self.streams = None
            self.rng = np.random.default_rng(seed)

    def _points(self, slots: np.ndarray):
        if self.stream_kind == "prng":
            pts = self.rng.random((len(slots), 2)).astype(np.float32)
            return pts[:, 0], pts[:, 1]
        u, v = self.streams.next(np.asarray(slots)) if not self.device_streams \
            else self.streams.draw(np.asarray(slots))
        return np.asarray(u), np.asarray(v)

    def sample(self, slots: np.ndarray):
        """One (row, col) texel per slot occurrence."""
        u, v = self._points(np.asarray(slots))
        r, c, _, _ = self.map.sample_map((u, v))
        return r, c

    def sample_flat(self, slots: np.ndarray) -> np.ndarray:
        """One flat texel id per slot occurrence (the engine's token form)."""
        r, c = self.sample(slots)
        return self.map.flat_index(r, c)

    def update(self, delta_rows: dict, *, delta: bool = False) -> dict:
        """Patch dirty map rows in place (O(dirty rows); see
        :meth:`repro.spatial.Map2DSampler.update_map`)."""
        return self.map.update_map(delta_rows, delta=delta)

    def snapshot(self) -> dict:
        """Map rows + build config + exact stream state. Restore rebuilds
        the map deterministically (bit-identical arrays) and resumes the
        streams where they stopped; sharded maps restore single-device
        (the dist conformance suite pins build bit-identity across that)."""
        m = self.map
        return dict(
            kind="spatial_sampler",
            rows=[np.asarray(r, np.float64) for r in m.rows_raw],
            map_kwargs=dict(
                m_marginal=m.m_marginal, min_class=m.min_class,
                fallback_slack=m.fallback_slack, coalesce=m.coalesce,
                use_pallas=m.use_pallas, policy=m.policy,
            ),
            stream_kind=self.stream_kind,
            device_streams=self.device_streams,
            streams=None if self.streams is None else self.streams.snapshot(),
            rng=_rng_state(self.rng),
        )

    @classmethod
    def restore(cls, state: dict) -> "SpatialSampler":
        if state.get("kind") != "spatial_sampler":
            raise ValueError(f"not a SpatialSampler snapshot: {state.get('kind')!r}")
        img = np.stack([np.asarray(r, np.float64) for r in state["rows"]])
        s = cls(img, n_slots=1, streams=state["stream_kind"],
                device_streams=state["device_streams"], **state["map_kwargs"])
        s.streams = restore_streams(state["streams"])
        s.rng = _rng_restore(state["rng"])
        return s


class ForestSampler:
    """Shared-distribution serving sampler: ONE static distribution (draft
    prior, data mixture, env-map row), many draws per step — the paper's
    amortized workload behind a serving-shaped API.

    Builds the radix forest once at construction; every ``sample`` call
    inverts the CDF at the slots' QMC streams (monotone warp, so the
    stratification survives). ``sharded=True`` opts into the cell-partitioned
    :mod:`repro.dist.forest` path: guide cells are partitioned over the mesh
    data axis (``rebalance=True`` balances the partition by leaf occupancy
    for spiky priors) and each draw is resolved by its owning shard
    (bit-identical to the single-device path — the dist conformance suite
    gates that). :meth:`update_weights` swaps the distribution in place —
    the sharded path rebuilds only the shards whose windows changed, and the
    per-slot QMC streams continue uninterrupted."""

    def __init__(self, weights, m: int | None = None, sharded: bool = False,
                 mesh=None, n_slots: int = 64, seed: int = 0,
                 rebalance: bool = False, routed: bool = True):
        self._raw = np.asarray(weights, np.float64)
        w = normalize_weights(self._raw)
        m = m or max(len(w), 16)
        self.sharded = sharded
        # Owner-routed all-to-all bulk drain (default) vs the replicated
        # masked-psum oracle — identical draws; routed is the scaling path.
        self.routed = routed
        self.streams = QmcStreams(n_slots, seed)
        if sharded:
            from repro.dist import forest as DF  # lazy: serve stays importable

            self.forest, self.mesh = DF.build_forest_sharded_auto(
                jnp.asarray(w), m, mesh=mesh, rebalance=rebalance
            )
        else:
            self.mesh = None
            self.forest = build_forest(jnp.asarray(w), m)

    def update_weights(self, weights=None, *, delta=None) -> None:
        """In-place distribution update (new full weights, or a delta added
        to the current raw weights). Slot streams keep their counters, so a
        long-lived serving loop re-targets without a stratification reset."""
        self._raw, w = updated_weights(self._raw, weights, delta=delta)
        if self.sharded:
            from repro.dist import forest as DF

            self.forest = DF.update_forest_sharded(
                self.forest, jnp.asarray(w), mesh=self.mesh
            )
        else:
            self.forest = build_forest(jnp.asarray(w), self.forest.m)

    def sample(self, slots: np.ndarray) -> np.ndarray:
        xi = jnp.asarray(self.streams.next(slots))
        if self.sharded:
            from repro.dist import forest as DF

            return np.asarray(DF.sample_sharded(
                self.forest, xi, mesh=self.mesh, routed=self.routed
            ))
        return np.asarray(sample_forest(self.forest, xi))


class PooledForestSampler:
    """Multi-tenant serving sampler: thousands of per-request categoricals
    (draft priors, per-client mixtures, per-cell densities) in ONE
    :class:`repro.pool.ForestPool`, drained in bulk.

    The serving-shaped complement of :class:`ForestSampler` (one shared
    distribution, many draws): here every request owns its *own* small
    distribution. ``add`` admits a tenant and returns its stable pool
    :class:`~repro.pool.Handle`; ``sample`` resolves one QMC draw per slot
    against that slot's distribution through the **stream-aware drain**: the
    slot streams live device-side (:class:`DeviceQmcStreams`), one jitted
    pre-pass ranks duplicate slots and advances every counter, and each
    touched size class resolves its lanes with a single coalesced
    ``forest_sample_batched_streams`` launch that computes the QMC points
    in-kernel — no host-side uniform generation or counter bookkeeping on
    the hot path. ``device_streams=False`` falls back to the host
    :class:`QmcStreams` oracle path (bit-equal draws; the differential
    suite pins it). ``update``/``remove`` re-target and retire tenants in
    place; slot QMC streams keep their counters across tenant churn, so
    stratification survives distribution swaps exactly as in
    :class:`ForestSampler`.

    **Stream kind and per-tenant method.** ``streams="qmc"`` (default)
    drives the per-slot low-discrepancy streams above; ``streams="prng"``
    replaces them with one seeded PRNG (the MC baseline — no
    stratification to protect). Tenants declare stream sensitivity at
    admission: ``method="forest"`` (monotone map, QMC-safe),
    ``method="alias"`` (packed O(1) tables — the bulk fast path), or
    ``method="auto"`` (default), which picks **alias under PRNG streams
    and forest under QMC streams** — exactly the paper's tradeoff: spend
    the descent only where a stratified stream would be destroyed by the
    non-monotone alias map."""

    def __init__(self, n_slots: int = 64, seed: int = 0, min_class: int = 8,
                 m: int | None = None, use_pallas: bool | None = None,
                 device_streams: bool = True, streams: str = "qmc",
                 policy: str = "reject"):
        from repro.pool import ForestPool  # lazy: serve stays importable

        if streams not in ("qmc", "prng"):
            raise ValueError(f"streams must be 'qmc' or 'prng', got {streams!r}")
        self.pool = ForestPool(min_class=min_class, m=m, policy=policy)
        self.stream_kind = streams
        self.device_streams = device_streams and streams == "qmc"
        if streams == "qmc":
            self.streams = (
                DeviceQmcStreams(n_slots, seed) if device_streams
                else QmcStreams(n_slots, seed)
            )
            self.rng = None
        else:
            self.streams = None
            self.rng = np.random.default_rng(seed)
        self.use_pallas = use_pallas

    def _resolve(self, method: str) -> str:
        """``auto`` -> alias for PRNG streams (nothing to protect, take the
        O(1) path), forest for QMC streams (the monotone map keeps the
        stratification the streams exist for)."""
        if method == "auto":
            return "alias" if self.stream_kind == "prng" else "forest"
        return method

    def add(self, weights, method: str = "auto"):
        """Admit one tenant; returns its pool handle. ``method`` is
        ``"forest"``/``"alias"``/``"auto"`` (see the class docstring)."""
        return self.pool.insert(weights, method=self._resolve(method))

    def add_many(self, weights_list, method="auto"):
        """Admit an admission wave through the fused batched builders.
        ``method`` is one choice for the wave or a per-tenant sequence."""
        if isinstance(method, str):
            methods = [self._resolve(method)] * len(weights_list)
        else:
            methods = [self._resolve(m) for m in method]
        return self.pool.insert_many(weights_list, method=methods)

    def update(self, handle, weights=None, *, delta=None) -> None:
        self.pool.update_weights(handle, weights, delta=delta)

    def remove(self, handle) -> None:
        self.pool.evict(handle)

    def sample(self, handles, slots: np.ndarray) -> np.ndarray:
        """One draw per slot from that slot's tenant distribution — the
        batched drain. ``handles[i]`` pairs with ``slots[i]``'s stream.
        Under QMC streams this is one pool call regardless of tenant
        methods (forest groups walk the stream-aware descent, alias groups
        consume the same pre-pass points); under PRNG streams the uniforms
        are one seeded vector draw."""
        if self.stream_kind == "prng":
            xi = self.rng.random(len(slots)).astype(np.float32)
            return self.pool.sample(handles, xi, use_pallas=self.use_pallas)
        if self.device_streams:
            return self.pool.sample_streams(
                handles, np.asarray(slots), self.streams,
                use_pallas=self.use_pallas,
            )
        xi = self.streams.next(np.asarray(slots))
        return self.pool.sample(handles, xi, use_pallas=self.use_pallas)

    def snapshot(self) -> dict:
        """Pool arenas + exact stream/PRNG state — everything a resumed
        process needs for bit-identical subsequent drains."""
        return dict(
            kind="pooled_forest_sampler",
            pool=self.pool.snapshot(),
            stream_kind=self.stream_kind,
            device_streams=self.device_streams,
            streams=None if self.streams is None else self.streams.snapshot(),
            rng=_rng_state(self.rng),
            use_pallas=self.use_pallas,
        )

    @classmethod
    def restore(cls, state: dict) -> "PooledForestSampler":
        from repro.pool import ForestPool  # lazy: serve stays importable

        if state.get("kind") != "pooled_forest_sampler":
            raise ValueError(
                f"not a PooledForestSampler snapshot: {state.get('kind')!r}"
            )
        s = cls(n_slots=1, streams=state["stream_kind"],
                device_streams=state["device_streams"],
                use_pallas=state["use_pallas"])
        s.pool = ForestPool.restore(state["pool"])
        s.streams = restore_streams(state["streams"])
        s.rng = _rng_restore(state["rng"])
        return s


class TokenSampler:
    def __init__(self, mode: str = "inverse_qmc", n_slots: int = 64,
                 temperature: float = 1.0, seed: int = 0,
                 use_pallas: bool | None = None):
        # use_pallas: None takes the kernels.ops policy (the compiled
        # fused-CDF and sampling kernels on a TPU, their XLA references
        # elsewhere); True/False ask for the kernel or the reference.
        assert mode in ("inverse_qmc", "inverse_rng", "alias")
        self.mode = mode
        self.temperature = temperature
        self.streams = QmcStreams(n_slots, seed)
        self.rng = np.random.default_rng(seed)
        self.use_pallas = use_pallas

    def uniforms(self, slots: np.ndarray) -> np.ndarray:
        if self.mode == "inverse_qmc":
            return self.streams.next(slots)
        return self.rng.random(len(slots)).astype(np.float32)

    def sample(self, logits: jax.Array, slots: np.ndarray) -> np.ndarray:
        """logits (B, V) -> token ids (B,)."""
        if self.mode == "alias":
            p = np.asarray(jax.nn.softmax(logits / self.temperature, axis=-1))
            # every mode consumes the SAME per-slot draw protocol: mode
            # comparisons (inverse_rng vs alias) then contrast mappings,
            # not randomness, and the serving-diversity bench is honest
            xi = self.uniforms(slots)
            out = np.empty(len(slots), np.int64)
            for i in range(len(slots)):  # serial build per row — the point
                t = build_alias(p[i])
                out[i] = int(np.asarray(sample_alias(t, jnp.float32(xi[i]))))
            return out.astype(np.int32)
        xi = self.uniforms(slots)
        cdf = ops.fused_cdf(
            logits / self.temperature, softmax=True, use_pallas=self.use_pallas
        )
        idx = ops.sample_rows(cdf, jnp.asarray(xi)[:, None], use_pallas=self.use_pallas)
        return np.asarray(idx)[:, 0]

    def snapshot(self) -> dict:
        return dict(
            kind="token_sampler", mode=self.mode,
            temperature=self.temperature, use_pallas=self.use_pallas,
            streams=self.streams.snapshot(), rng=_rng_state(self.rng),
        )

    @classmethod
    def restore(cls, state: dict) -> "TokenSampler":
        if state.get("kind") != "token_sampler":
            raise ValueError(f"not a TokenSampler snapshot: {state.get('kind')!r}")
        s = cls(mode=state["mode"], n_slots=1,
                temperature=state["temperature"],
                use_pallas=state["use_pallas"])
        s.streams = restore_streams(state["streams"])
        s.rng = _rng_restore(state["rng"])
        return s
