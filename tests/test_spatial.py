"""Differential conformance suite for ``repro.spatial``.

The contract under test (module docstring of ``repro.spatial.map2d``): bulk
``sample_map`` is **elementwise identical** to the per-row row-then-column
reference — ``build_forest`` over the normalized row masses for the
marginal, one ``build_forest`` over each selected row's zero-padded
conditional at its class width for the columns — across map families (HDR
env map, one-hot texels, constant, Zipf rows) and ragged widths spanning
several size classes; **zero-mass rows are exactly unselectable** (no
epsilon) and single-texel rows resolve without special-casing;
``update_map`` is **bit-identical** to a from-scratch :class:`Map2DSampler`
over the new map while rebuilding only the dirty rows (the structural
``rebuilt_rows`` / ``skipped_rows`` witness); the 2-D QMC serving streams
are host/device **bit-equal**; and the sharded marginal agrees elementwise
with the single-device build (8-fake-device subprocess lane).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs.paper_workloads import env_map_2d
from repro.core import build_forest, sample_forest
from repro.core.cdf import normalize_weights
from repro.core.metrics import chi2_statistic
from repro.serve import (
    DeviceQmc2Streams,
    Qmc2Streams,
    Request,
    ServeEngine,
    SpatialSampler,
)
from repro.spatial import Map2DSampler


def _run(script: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ, PYTHONPATH="src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.getcwd(), timeout=timeout,
    )


# --------------------------------------------------------------- map families


def _family(name: str):
    """Map families from the issue: each is a list of 1-D weight rows."""
    rng = np.random.default_rng(hash(name) % (2**31))
    if name == "env":
        return list(env_map_2d(12, 24))
    if name == "onehot":
        rows = []
        for r in range(9):
            w = np.zeros(17)
            w[(r * 5) % 17] = 1.0 + r
            rows.append(w)
        return rows
    if name == "constant":
        return list(np.ones((7, 33)))
    if name == "zipf":
        return [
            rng.permutation(1.0 / np.arange(1, 41) ** 1.2) for _ in range(11)
        ]
    if name == "ragged":
        # widths span classes 8/16/32/64 + zero-mass + one-hot + width-1 rows
        rows = [rng.random(w) ** 3 for w in (5, 17, 33, 8, 64, 9, 2)]
        rows.append(np.zeros(12))        # zero-mass: must never be selected
        one = np.zeros(30)
        one[13] = 2.5
        rows.append(one)                 # one-hot: always texel 13
        rows.append(np.array([4.0]))     # single-texel row (width 1)
        return rows
    raise AssertionError(name)


def _reference(rows_raw, sampler: Map2DSampler, u, v):
    """The per-row oracle: marginal ``build_forest`` over row masses, then
    one ``build_forest`` per selected row at its padded class width (class
    rows behave exactly like ``build_forest`` over the zero-padded row),
    columns clipped to the true width."""
    mass = np.asarray([r.sum() for r in rows_raw], np.float64)
    f_marg = build_forest(
        jnp.asarray(normalize_weights(mass)), sampler.m_marginal
    )
    rows = np.asarray(
        sample_forest(f_marg, jnp.asarray(u, jnp.float32)), np.int64
    )
    cols = np.empty(len(rows), np.int64)
    for r in np.unique(rows):
        mask = rows == r
        w = rows_raw[r]
        wc = int(sampler._class_of[r])
        wpad = np.pad(normalize_weights(w), (0, wc - len(w)))
        f = build_forest(jnp.asarray(wpad), wc)
        cols[mask] = np.minimum(
            np.asarray(sample_forest(f, jnp.asarray(v[mask], jnp.float32))),
            len(w) - 1,
        )
    return rows, cols


FAMILIES = ("env", "onehot", "constant", "zipf", "ragged")


@pytest.mark.parametrize("family", FAMILIES)
def test_sample_map_matches_per_row_reference(family):
    rows_raw = _family(family)
    sampler = Map2DSampler(rows_raw)
    rng = np.random.default_rng(7)
    pts = rng.random((4096, 2)).astype(np.float32)
    ri, ci, u, v = sampler.sample_map(pts)
    rr, cr = _reference(rows_raw, sampler, pts[:, 0], pts[:, 1])
    assert np.array_equal(rr, ri), f"{family}: marginal diverged"
    assert np.array_equal(cr, ci), f"{family}: conditional diverged"
    # launch-count witness: one launch per touched class, never per row
    n_classes = len({int(sampler._class_of[r]) for r in np.unique(ri)})
    assert sampler.last_drain["launches"] == (
        1 if sampler.last_drain["fused"] else n_classes
    )


def test_zero_mass_and_single_texel_rows():
    """Exact zero-mass semantics (no ``+ 1e-18``): an all-zero row's
    marginal interval has zero width, so it is NEVER selected — and one-hot
    / single-texel rows resolve to their only live texel."""
    rows_raw = _family("ragged")
    sampler = Map2DSampler(rows_raw)
    rng = np.random.default_rng(3)
    pts = rng.random((1 << 14, 2)).astype(np.float32)
    # include the adversarial corners of the unit square
    pts[:4] = [[0.0, 0.0], [0.0, 1.0 - 2**-24], [1.0 - 2**-24, 0.0],
               [1.0 - 2**-24, 1.0 - 2**-24]]
    ri, ci, _, _ = sampler.sample_map(pts)
    assert not (ri == 7).any(), "zero-mass row was selected"
    assert (ci[ri == 8] == 13).all(), "one-hot row missed its live texel"
    assert (ci[ri == 9] == 0).all(), "single-texel row returned col != 0"
    assert (ci >= 0).all()
    assert (ci < sampler.widths[ri]).all(), "col escaped its row width"


def test_single_cell_map_min_class_one():
    """Degenerate 1x1 map at min_class=1: the flat builder's n == 1 path
    (all-sentinel separators) must still resolve every point to (0, 0)."""
    sampler = Map2DSampler([np.array([3.0])], min_class=1)
    pts = np.random.default_rng(0).random((256, 2)).astype(np.float32)
    ri, ci, _, _ = sampler.sample_map(pts)
    assert (ri == 0).all() and (ci == 0).all()


def test_all_zero_map_rejected():
    with pytest.raises(ValueError):
        Map2DSampler(np.zeros((4, 8)))
    with pytest.raises(ValueError):
        Map2DSampler([np.array([1.0, -2.0])])


# -------------------------------------------------------------------- updates


def _assert_bit_identical(a: Map2DSampler, b: Map2DSampler):
    assert sorted(a.classes) == sorted(b.classes)
    for wc in a.classes:
        ca, cb = a.classes[wc], b.classes[wc]
        assert ca.row_ids == cb.row_ids
        for fa, fb in zip(ca.forest, cb.forest):
            assert np.array_equal(np.asarray(fa), np.asarray(fb)), wc
        assert np.array_equal(
            np.asarray(ca.cdf_rows).view(np.uint32),
            np.asarray(cb.cdf_rows).view(np.uint32),
        )
        assert ca.degenerate == cb.degenerate
    for k in ("cdf", "table", "left", "right", "cell_first", "fallback"):
        assert np.array_equal(
            np.asarray(getattr(a._marginal, k)),
            np.asarray(getattr(b._marginal, k)),
        ), k


def test_update_map_bit_identical_to_from_scratch():
    """Sparse ``update_map`` == from-scratch :class:`Map2DSampler` over the
    new map, bitwise over every class-forest array, the CDF skip keys, and
    the marginal — while the stats witness O(dirty rows): the unchanged
    resubmitted row skips, only the truly dirty rows rebuild."""
    rows_raw = _family("ragged")
    sampler = Map2DSampler(rows_raw)
    rng = np.random.default_rng(11)
    delta = {
        0: rng.random(5) ** 2,                # dirty (class 8)
        3: np.asarray(rows_raw[3]),           # resubmitted unchanged: skip
        4: rng.random(64) ** 2,               # dirty (class 64)
        7: rng.random(12) + 0.1,              # zero-mass row comes alive
    }
    stats = sampler.update_map(delta)
    assert stats["rebuilt_rows"] == 3
    assert stats["skipped_rows"] == 1
    # one launch per touched class (8, 16, 64) — never one per row
    assert stats["cond_launches"] == 3
    assert stats["marginal_rebuilt"] is True

    new_rows = list(rows_raw)
    for r, w in delta.items():
        new_rows[r] = np.asarray(w, np.float64)
    fresh = Map2DSampler(new_rows)
    _assert_bit_identical(sampler, fresh)

    pts = rng.random((4096, 2)).astype(np.float32)
    r1, c1, _, _ = sampler.sample_map(pts)
    r2, c2, _, _ = fresh.sample_map(pts)
    assert np.array_equal(r1, r2) and np.array_equal(c1, c2)
    assert (r1 == 7).any(), "revived row never selected after update"


def test_update_reviving_zero_row_to_uniform_skips_conditional():
    """A zero-mass row's placeholder conditional IS the uniform distribution
    — reviving it with uniform weights only moves the marginal, and the
    CDF-bits skip proves the conditional stack untouched. Still bit-identical
    to from-scratch (the placeholder normalizes to the same CDF)."""
    rows_raw = _family("ragged")
    sampler = Map2DSampler(rows_raw)
    stats = sampler.update_map({7: np.full(12, 0.25)})
    assert stats == dict(rebuilt_rows=0, skipped_rows=1, cond_launches=0,
                         marginal_rebuilt=True)
    new_rows = list(rows_raw)
    new_rows[7] = np.full(12, 0.25)
    _assert_bit_identical(sampler, Map2DSampler(new_rows))


def test_update_map_noop_and_delta_form():
    rows_raw = _family("zipf")
    sampler = Map2DSampler(rows_raw)
    stats = sampler.update_map({2: np.asarray(rows_raw[2])})
    assert stats == dict(rebuilt_rows=0, skipped_rows=1, cond_launches=0,
                         marginal_rebuilt=False)
    # additive form: img[r] += delta
    bump = np.zeros(40)
    bump[5] = 1.0
    stats = sampler.update_map({2: bump}, delta=True)
    assert stats["rebuilt_rows"] == 1 and stats["marginal_rebuilt"] is True
    fresh_rows = list(rows_raw)
    fresh_rows[2] = rows_raw[2] + bump
    _assert_bit_identical(sampler, Map2DSampler(fresh_rows))
    with pytest.raises(ValueError):
        sampler.update_map({2: np.ones(7)})  # widths are fixed
    with pytest.raises(ValueError):
        sampler.update_map({99: np.ones(40)})


def _sun_band(base, row0, rows, col, amp=1e3, sigma=1.5):
    """``rows`` rows of ``base`` from ``row0`` with a Gaussian sun at
    (``row0 + rows // 2``, ``col``), its azimuth wrapped at the seam."""
    H, W = base.shape
    yy = np.arange(row0, row0 + rows, dtype=np.float64)[:, None]
    dx = (np.arange(W)[None, :] - col + W / 2) % W - W / 2
    sun = amp * np.exp(-((yy - row0 - rows // 2) ** 2 + dx ** 2)
                       / (2 * sigma * sigma))
    return base[row0:row0 + rows] + sun


def test_moving_sun_updates_match_a_fresh_build_every_frame():
    """A sun moves along a row every frame; its 8-row band is handed to
    ``update_map`` and a frame is drawn. After each update the sampler is
    bit-identical to a from-scratch build of that frame's map, its answers
    equal the fresh sampler's and the per-row reference's, every band row
    rebuilds, and the host reads O(touched rows), not O(map rows)."""
    from repro import trace

    H, W, row0, band = 64, 128, 16, 8
    base = np.asarray(env_map_2d(H, W), np.float64)
    img = base.copy()
    img[row0:row0 + band] = _sun_band(base, row0, band, col=120)
    sampler = Map2DSampler(img)
    rng = np.random.default_rng(23)
    for frame in range(6):
        col = (120 + 13 * (frame + 1)) % W      # crosses the seam
        img[row0:row0 + band] = _sun_band(base, row0, band, col)
        trace.reset_counters()
        stats = sampler.update_map(
            {row0 + i: img[row0 + i] for i in range(band)})
        pulled = trace.counters()["host.bytes_out"]
        assert stats["rebuilt_rows"] == band and stats["skipped_rows"] == 0
        # a flag per touched row, the marginal's two CDFs, two degenerate
        # flags; the class stack alone would be H * (W + 1) * 4 bytes
        assert pulled <= band + 2 * (H + 1) * 4 + 2, pulled
        fresh = Map2DSampler(img)
        _assert_bit_identical(sampler, fresh)
        pts = rng.random((2048, 2)).astype(np.float32)
        r1, c1, u, v = sampler.sample_map(pts)
        r2, c2, _, _ = fresh.sample_map(pts)
        assert np.array_equal(r1, r2) and np.array_equal(c1, c2)
        rr, cr = _reference(list(img), sampler, u, v)
        assert np.array_equal(r1, rr) and np.array_equal(c1, cr)
        assert (r1 == row0 + band // 2).any()


# --------------------------------------------------------------- distribution


def test_map_distribution_preserved_chi2():
    """Per-texel chi-square GOF: the bulk pipeline must reproduce the full
    2-D distribution (marginal x conditional = flat texel mass)."""
    rng = np.random.default_rng(5)
    H, W = 8, 32
    img = rng.random((H, W)) ** 2 + 0.05   # bounded below: chi2 approx valid
    sampler = Map2DSampler(img)
    pts = rng.random((1 << 15, 2)).astype(np.float32)
    ri, ci, _, _ = sampler.sample_map(pts)
    counts = np.bincount(sampler.flat_index(ri, ci), minlength=H * W)
    chi2 = chi2_statistic(counts, (img / img.sum()).ravel())
    # dof = 255: mean 255, sd ~22.6; 500 is a ~10-sigma guard
    assert chi2 < 500, chi2


# ------------------------------------------------------------- serving layers


def test_qmc2_streams_host_device_bit_equal():
    """The serving contract from the 1-D streams, in 2-D: device prepass
    counters and points must be BIT-equal to the host oracle, including
    duplicate slots in one drain (occurrence-rank offsets)."""
    host = Qmc2Streams(8, seed=42)
    dev = DeviceQmc2Streams(8, seed=42)
    for slots in ([0, 3, 3, 5, 3, 0], [7, 7, 7, 7], [1]):
        s = np.asarray(slots)
        hu, hv = host.next(s)
        du, dv = dev.draw(s)
        assert np.array_equal(hu.view(np.uint32),
                              np.asarray(du).view(np.uint32))
        assert np.array_equal(hv.view(np.uint32),
                              np.asarray(dv).view(np.uint32))
    assert np.array_equal(host.counters, np.asarray(dev.counters))


def test_spatial_sampler_streams_and_update():
    img = env_map_2d(10, 20)
    a = SpatialSampler(img, n_slots=4, seed=9, device_streams=True)
    b = SpatialSampler(img, n_slots=4, seed=9, device_streams=False)
    slots = np.array([0, 2, 2, 3])
    for _ in range(3):
        assert np.array_equal(a.sample_flat(slots), b.sample_flat(slots))
    stats = a.update({1: np.full(20, 0.5)})
    assert stats["rebuilt_rows"] == 1
    flat = a.sample_flat(slots)
    assert ((0 <= flat) & (flat < img.size)).all()


def test_engine_serves_prior2d_requests():
    """Pure 2-D traffic through the engine (params=None): every emitted
    token is a valid flat texel id, zero-mass rows never appear, slots
    recycle, and a mismatched map is rejected (the map is shared)."""
    img = np.asarray(env_map_2d(9, 16))
    img[4] = 0.0                      # a dead row mid-map
    eng = ServeEngine(None, None, n_slots=4)
    reqs = [
        Request(rid=i, prompt=np.zeros(0, np.int32), max_new=5,
                prior2d=img)
        for i in range(6)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=50)
    dead_lo, dead_hi = 4 * 16, 5 * 16
    for r in reqs:
        assert r.done and len(r.out) == 5
        out = np.asarray(r.out)
        assert ((0 <= out) & (out < img.size)).all()
        assert not ((dead_lo <= out) & (out < dead_hi)).any()
    assert not eng.spatial_slots  # all retired

    other = img.copy()
    other[0, 0] += 1.0
    eng2 = ServeEngine(None, None, n_slots=2)
    eng2.submit(Request(rid=0, prompt=np.zeros(0, np.int32), prior2d=img))
    eng2.submit(Request(rid=1, prompt=np.zeros(0, np.int32), prior2d=other))
    with pytest.raises(ValueError):
        eng2.run(max_steps=5)
    with pytest.raises(ValueError):
        eng2.submit(Request(rid=2, prompt=np.zeros(0, np.int32),
                            prior=np.ones(8), prior2d=img))


# ------------------------------------------------------- sharded marginal lane


@pytest.mark.slow
def test_sharded_marginal_8_devices_subprocess():
    """The sharded marginal at 8 fake devices: ``sample_map`` rows must be
    elementwise equal to the unsharded sampler on shared uniforms (and the
    conditional path is unaffected — bit-equal columns), the zero-mass row
    stays unselectable, and a sharded ``update_map`` reports shard stats."""
    script = textwrap.dedent(
        """
        import numpy as np
        import jax
        from repro.spatial import Map2DSampler

        assert jax.device_count() == 8, jax.device_count()
        rng = np.random.default_rng(0)
        img = rng.random((32, 24)) ** 3
        img[5] = 0.0
        pts = rng.random((4096, 2)).astype(np.float32)

        plain = Map2DSampler(img)
        shard = Map2DSampler(img, sharded=True)
        assert shard.m_marginal % 8 == 0, shard.m_marginal
        r1, c1, _, _ = plain.sample_map(pts)
        r2, c2, _, _ = shard.sample_map(pts)
        assert shard.last_drain["marginal"] == "sharded"
        assert np.array_equal(r1, r2) and np.array_equal(c1, c2)
        assert not (r2 == 5).any()

        st = shard.update_map({5: rng.random(24) + 0.1, 9: img[9]})
        assert st["skipped_rows"] == 1 and st["rebuilt_rows"] == 1
        assert st["marginal_rebuilt"] and "marginal_shards" in st
        r3, _, _, _ = shard.sample_map(pts)
        assert (r3 == 5).any()
        print("SHARDED-2D-OK")
        """
    )
    res = _run(script)
    assert res.returncode == 0, res.stderr
    assert "SHARDED-2D-OK" in res.stdout
