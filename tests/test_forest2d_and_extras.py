"""Paper §5 features: multi-row simultaneous construction, k-ary collapsing;
plus LDS generator properties and stochastic MoE routing coverage."""
import hypothesis
import hypothesis.strategies as st
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.cdf import normalize_weights, np_build_cdf
from repro.core.forest2d import (
    build_forest_rows,
    np_reference_rows,
    sample_forest_rows,
)
from repro.core.lds import hammersley, radical_inverse_base2, sobol
from repro.core.metrics import star_discrepancy_1d

settings = hypothesis.settings(max_examples=15, deadline=None)


@settings
@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    R=st.integers(1, 12),
    W=st.integers(2, 40),
    m=st.integers(1, 64),
)
def test_multirow_forest_matches_oracle(seed, R, W, m):
    """One flat data-parallel pass == per-row searchsorted, for any grid."""
    rng = np.random.default_rng(seed)
    img = rng.random((R, W)) ** 6 + 1e-9
    cdfs = np.stack([np_build_cdf(normalize_weights(r)) for r in img])
    f = build_forest_rows(jnp.asarray(cdfs), m=m)
    B = 512
    rows = rng.integers(0, R, B).astype(np.int32)
    xi = rng.random(B).astype(np.float32)
    got = np.asarray(sample_forest_rows(f, jnp.asarray(rows), jnp.asarray(xi)))
    want = np_reference_rows(cdfs, rows, xi)
    mism = got != want
    if mism.any():  # tied zero-width intervals are equivalent
        assert all(
            cdfs[rows[i]][got[i]] == cdfs[rows[i]][want[i]]
            for i in np.where(mism)[0]
        )
    # inversion property within each row
    lo = cdfs[rows, got]
    hi = cdfs[rows, got + 1]
    assert np.all(lo <= xi) and np.all(xi < hi + 1e-7)


def test_multirow_matches_per_row_build():
    """The flat build must produce the same per-row trees as R separate
    1-D builds (the paper's equivalence claim)."""
    from repro.core import build_forest_from_cdf, sample_forest

    rng = np.random.default_rng(3)
    R, W, m = 5, 33, 16
    img = rng.random((R, W)) ** 4 + 1e-9
    cdfs = np.stack([np_build_cdf(normalize_weights(r)) for r in img])
    f2 = build_forest_rows(jnp.asarray(cdfs), m=m)
    xi = rng.random(1024).astype(np.float32)
    for r in range(R):
        f1 = build_forest_from_cdf(jnp.asarray(cdfs[r]), m)
        a = np.asarray(sample_forest(f1, jnp.asarray(xi)))
        rows = jnp.full((len(xi),), r, jnp.int32)
        b = np.asarray(sample_forest_rows(f2, rows, jnp.asarray(xi)))
        assert np.array_equal(a, b) or np.all(cdfs[r][a] == cdfs[r][b])


def test_forest2d_distribution_preserved_chi2():
    """Chi-square goodness of fit for the 2-D path, mirroring the 1-D
    ``test_distribution_preserved_chi2``: conditional column sampling within
    each row must reproduce that row's distribution."""
    rng = np.random.default_rng(11)
    R, W, m = 8, 48, 32
    img = rng.random((R, W)) ** 2 + 0.05   # bounded below: chi2 approx valid
    cdfs = np.stack([np_build_cdf(normalize_weights(r)) for r in img])
    f = build_forest_rows(jnp.asarray(cdfs), m=m)
    per_row = 1 << 13
    rows = np.repeat(np.arange(R), per_row).astype(np.int32)
    xi = rng.random(R * per_row).astype(np.float32)
    cols = np.asarray(sample_forest_rows(f, jnp.asarray(rows), jnp.asarray(xi)))
    chi2 = 0.0
    for r in range(R):
        counts = np.bincount(cols[r * per_row : (r + 1) * per_row], minlength=W)
        expected = np.diff(cdfs[r]) * per_row
        chi2 += float(np.sum((counts - expected) ** 2 / np.maximum(expected, 1e-9)))
    # dof = R*(W-1) = 376: mean 376, sd ~27.4; 650 is a ~10-sigma guard
    assert chi2 < 650, chi2


@settings
@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    R=st.integers(1, 10),
    W=st.integers(2, 40),
    m=st.integers(1, 48),
)
def test_forest2d_structural_invariants(seed, R, W, m):
    """validate_forest-style invariants for the flat 2-D build: every guide
    entry resolves within its row, and in-order traversal of every (row,
    cell) tree enumerates the cell's leaves ascending behind the row-clamped
    left-overlap leaf."""
    from repro.core.forest2d import validate_forest_rows

    rng = np.random.default_rng(seed)
    img = rng.random((R, W)) ** 4 + 1e-9
    cdfs = np.stack([np_build_cdf(normalize_weights(r)) for r in img])
    f = build_forest_rows(jnp.asarray(cdfs), m=m)
    validate_forest_rows(f)


@settings
@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    R=st.integers(2, 10),
    W=st.integers(2, 32),
)
def test_forest2d_marginal_conditional_consistency(seed, R, W):
    """2-D sampling factorizes (paper Sec. 5): draw the row from the marginal
    (row-mass) forest, the column from the conditional row forest. Exact
    per-draw properties: each stage satisfies its inversion bounds, and for a
    fixed row the conditional stage is a monotone map of xi (so the joint
    warp preserves LDS stratification per row)."""
    from repro.core import build_forest, sample_forest

    rng = np.random.default_rng(seed)
    img = rng.random((R, W)) ** 3 + 1e-9
    row_mass = normalize_weights(img.sum(axis=1))
    marg = build_forest(jnp.asarray(row_mass), 16)
    cond_cdfs = np.stack([np_build_cdf(normalize_weights(r)) for r in img])
    f2 = build_forest_rows(jnp.asarray(cond_cdfs), m=8)

    B = 128
    xi_r = rng.random(B).astype(np.float32)
    xi_c = np.sort(rng.random(B).astype(np.float32))
    rows = np.asarray(sample_forest(marg, jnp.asarray(xi_r)))
    marg_cdf = np.asarray(marg.cdf)
    assert np.all(marg_cdf[rows] <= xi_r) and np.all(xi_r < marg_cdf[rows + 1])

    cols = np.asarray(
        sample_forest_rows(f2, jnp.asarray(rows, jnp.int32), jnp.asarray(xi_c))
    )
    lo = cond_cdfs[rows, cols]
    hi = cond_cdfs[rows, cols + 1]
    assert np.all(lo <= xi_c) and np.all(xi_c < hi + 1e-7)

    # monotone conditional warp within one fixed row
    r0 = jnp.full((B,), int(rows[0]), jnp.int32)
    cols_fixed = np.asarray(sample_forest_rows(f2, r0, jnp.asarray(xi_c)))
    assert np.all(np.diff(cols_fixed) >= 0)


def test_forest2d_depth_bound():
    """Paper Sec. 3: per-cell traversal depth is O(log overlap), not
    O(overlap). Per-row 1-D builds are bit-identical to the flat 2-D build
    (``test_multirow_matches_per_row_build``), so bounding their
    ``depth_stats`` gates the 2-D path against linear-chain regressions:
    a degenerate chain would hit ``o_max`` (~20-26 here), far above the
    2*log2(o_max)+5 radix bound."""
    from repro.core import build_forest_from_cdf, depth_stats

    rng = np.random.default_rng(5)
    R, W, m = 6, 64, 4
    img = rng.random((R, W)) ** 6 + 1e-7
    for r in range(R):
        cdf = np_build_cdf(normalize_weights(img[r]))
        f1 = build_forest_from_cdf(jnp.asarray(cdf), m)
        ds = depth_stats(f1)
        data = cdf[:-1]
        cells = np.clip(np.floor(data * np.float32(m)).astype(int), 0, m - 1)
        o_max = int(np.bincount(cells, minlength=m).max()) + 1
        bound = 2 * int(np.ceil(np.log2(max(o_max, 2)))) + 5
        assert ds["max_depth"] <= bound, (r, ds["max_depth"], o_max, bound)
        assert o_max > bound  # the gate actually distinguishes log from linear


def _fixed_chain_depth(leaf_parent, node_parent):
    """The row builder's depth walk before it stopped at the last chain's
    end: a fixed ``_DEPTH_ITERS``-step chase (the oracle)."""
    from repro.core.forest import _DEPTH_ITERS

    def step(_, state):
        anc, depth = state
        live = anc >= 0
        return (jnp.where(live, node_parent[jnp.clip(anc, 0)], anc),
                depth + live.astype(jnp.int32))

    _, depth = jax.lax.fori_loop(0, _DEPTH_ITERS, step,
                                 (leaf_parent, jnp.zeros_like(leaf_parent)))
    return depth + 1, jnp.int32(_DEPTH_ITERS)


def _row_stack(case):
    """(3, n+1) CDFs and guide cells: one weight family, its mirror image,
    and a row of uniform weights, so the rows' chains differ in length."""
    if case == "zipf":
        w, m = (1.0 / np.arange(1, 4097)) ** 0.75, 4096
    elif case == "spike":
        w, m = np.zeros(300), 16
        w[150] = 1.2
    elif case == "interior_ties":
        w, m = np.random.default_rng(7).random(64) + 0.1, 4
        w[20:40] = 0.0
    elif case == "tied_run":
        w, m = np.zeros(300), 1
        w[0], w[299] = 1.2, 0.8
    elif case == "dyadic_chain":
        w, m = np.array([2.0 ** -(i + 1) for i in range(24)] + [2.0 ** -24]), 1
    else:
        w, m = {"n1": ([3.0], 8), "n2": ([1.0, 2.0], 4),
                "all_leaves": (np.ones(1024), 1024)}[case]
    w = np.asarray(w, np.float32)
    flat = np.random.default_rng(1).random(w.size) + 0.5
    cdfs = np.stack([np_build_cdf(normalize_weights(r))
                     for r in (w, w[::-1], flat)])
    return jnp.asarray(cdfs, jnp.float32), m


@pytest.mark.parametrize("case", ["zipf", "spike", "interior_ties", "tied_run",
                                  "dyadic_chain", "n1", "n2", "all_leaves"])
def test_row_depth_walk_matches_fixed_walk(case, monkeypatch):
    """``build_forest_rows`` walks every row's chains in one loop that stops
    when the longest chain of any row ends: each leaf's depth equals the
    fixed 48-step walk's, the flat forest and its fallback flags are those
    built with the fixed walk, and the trips are the longest leaf-to-root
    chain of the flat forest (``depth_stats``'s numpy walk), capped at 48."""
    from repro.core import depth_stats, forest2d as F2
    from repro.core.forest import RadixForest, _chain_depth

    cdfs, m = _row_stack(case)
    seen = []

    def checked(leaf_parent, node_parent):
        depth, trips = _chain_depth(leaf_parent, node_parent)
        want, _ = _fixed_chain_depth(leaf_parent, node_parent)
        jax.debug.callback(lambda same, t: seen.append((bool(same), int(t))),
                           jnp.all(depth == want), trips)
        return depth, trips

    def build(walk):   # a fresh function, so the walk in place is traced
        monkeypatch.setattr(F2, "_chain_depth", walk)
        f = jax.jit(lambda c: build_forest_rows.__wrapped__(c, m))(cdfs)
        jax.effects_barrier()
        return f

    got, want = build(checked), build(_fixed_chain_depth)
    for key in ("data", "table", "left", "right", "cell_first", "fallback"):
        assert np.array_equal(np.asarray(getattr(got, key)),
                              np.asarray(getattr(want, key))), key
    (same, trips), = seen
    assert same
    stats = depth_stats(RadixForest(got.data, got.table, got.left, got.right,
                                    got.cell_first, got.fallback))
    assert trips == min(max(stats["max_depth"] - 1, 1), 48)
    if case in ("spike", "tied_run"):
        assert trips == 48


# ---------------------------------------------------------------- LDS props


def test_lds_low_discrepancy():
    n = 4096
    assert star_discrepancy_1d(sobol(n, 1)[:, 0]) < 0.002
    assert star_discrepancy_1d(hammersley(n, 2)[:, 1]) < 0.01
    assert star_discrepancy_1d(np.random.default_rng(0).random(n)) > 0.005


def test_sobol_high_dims_distinct_and_nondegenerate():
    """Regression: dims > 7 used to recycle direction polynomials modulo the
    table length, silently duplicating coordinate columns (every
    'independent' pair above dim 7 was perfectly correlated). The extended
    Joe-Kuo table must give pairwise-distinct columns through dim 16 with
    non-degenerate 2D projections, and dims past the table must raise."""
    from repro.core.lds import SOBOL_MAX_DIMS

    n = 256
    p = sobol(n, 16)
    assert p.shape == (n, 16)
    for i in range(16):
        for j in range(i + 1, 16):
            assert not np.array_equal(p[:, i], p[:, j]), (i, j)
            # non-degenerate 2D projection: recycled columns collapsed the
            # pair onto exactly the 16 diagonal cells of a 16x16 grid;
            # genuine Sobol pairs here occupy >= 64 cells (some unscrambled
            # high-dim pairs do sit at that coarse-resolution floor)
            cells = (np.floor(p[:, i] * 16).astype(int),
                     np.floor(p[:, j] * 16).astype(int))
            grid = np.zeros((16, 16), int)
            np.add.at(grid, cells, 1)
            assert np.count_nonzero(grid) >= 64, (i, j, np.count_nonzero(grid))
    # each column is still a (0,1)-sequence in base 2
    for i in range(16):
        assert star_discrepancy_1d(p[:, i]) < 0.02, i
    with pytest.raises(ValueError):
        sobol(8, SOBOL_MAX_DIMS + 1)
    assert sobol(8, SOBOL_MAX_DIMS).shape == (8, SOBOL_MAX_DIMS)


def test_radical_inverse_exact_float32():
    i = np.arange(1024, dtype=np.uint32)
    x = radical_inverse_base2(i)
    assert np.all((x >= 0) & (x < 1))
    assert np.all(np.float32(x).astype(np.float64) == x)  # exactly representable
    assert len(np.unique(np.float32(x))) == 1024


# ------------------------------------------------------ stochastic routing


def test_moe_sampled_routing_marginals():
    """router_noise: expert choice ~ gate distribution via the monotone
    inverse (the paper's mapping inside the MoE layer)."""
    from repro.models.moe import _route

    rng = np.random.default_rng(0)
    T, E, k = 2048, 8, 2
    logits = rng.normal(0, 1.5, (1, T, E))
    gates = jnp.asarray(
        np.exp(logits) / np.exp(logits).sum(-1, keepdims=True), jnp.float32
    )
    xi = jnp.asarray(rng.random((1, T, k)), jnp.float32)
    ids, w = _route(gates, k, xi)
    ids = np.asarray(ids).reshape(-1)
    counts = np.bincount(ids, minlength=E) / ids.size
    expect = np.asarray(gates).mean(axis=(0, 1))
    np.testing.assert_allclose(counts, expect, atol=0.03)
    assert np.all(np.asarray(w) >= 0)


def test_moe_topk_routing_is_default():
    from repro.models.moe import _route

    gates = jnp.asarray([[0.1, 0.6, 0.3], [0.5, 0.2, 0.3]], jnp.float32)
    ids, w = _route(gates, 2, None)
    assert np.array_equal(np.asarray(ids), [[1, 2], [0, 2]])
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


# ----------------------------------------------------------- k-ary collapse


def test_kary_collapse_counts():
    """Paper §5: 'a higher branching factor simply results by collapsing two
    (or more) levels' — a 4-ary traversal visits ceil(depth/2) nodes. We
    verify the counting model: 4-ary loads == ceil(binary_visits / 2)."""
    from repro.core import (
        build_forest,
        np_sample_forest_counting,
    )

    rng = np.random.default_rng(1)
    w = normalize_weights(rng.random(512) ** 10 + 1e-12)
    f = build_forest(jnp.asarray(w), 128)
    xi = rng.random(4096).astype(np.float32)
    idx, loads = np_sample_forest_counting(f, xi)
    tree_visits = loads - 1  # minus the guide-table load
    kary_loads = 1 + np.ceil(tree_visits / 2)
    assert np.all(kary_loads <= loads)
    assert float(kary_loads.mean()) < float(loads.mean()) or tree_visits.max() <= 1


# ------------------------------------------------- parallel alias building


def _alias_mass(q: np.ndarray, alias: np.ndarray) -> np.ndarray:
    """Mass each item ends up with: own cell q_i + sum of (1-q_c) over cells
    aliasing it. Valid table <=> mass == n*p (exactly, in float64)."""
    n = len(q)
    mass = q.astype(np.float64).copy()
    np.add.at(mass, alias, 1.0 - q.astype(np.float64))
    return mass


@settings
@hypothesis.given(
    w=st.lists(
        st.floats(min_value=1e-6, max_value=1e6, allow_nan=False),
        min_size=1, max_size=400,
    ),
)
def test_parallel_alias_is_valid(w):
    from repro.core.alias import build_alias, build_alias_parallel

    w = np.asarray(w, np.float64)
    t = build_alias_parallel(w)
    q, alias = np.asarray(t.q, np.float64), np.asarray(t.alias)
    n = len(w)
    assert np.all((q >= -1e-6) & (q <= 1 + 1e-6))
    mass = _alias_mass(q, alias)
    np.testing.assert_allclose(mass, w / w.sum() * n, rtol=1e-4, atol=1e-4)
    # Vose reference obeys the same equation (sanity of the checker)
    tv = build_alias(w)
    mv = _alias_mass(np.asarray(tv.q, np.float64), np.asarray(tv.alias))
    np.testing.assert_allclose(mv, w / w.sum() * n, rtol=1e-4, atol=1e-4)


def test_parallel_alias_sampling_marginals():
    from repro.core.alias import build_alias_parallel, np_sample_alias

    rng = np.random.default_rng(0)
    w = normalize_weights(rng.random(64) ** 6 + 1e-6)
    t = build_alias_parallel(w)
    xi = rng.random(1 << 16)
    idx = np_sample_alias(np.asarray(t.q, np.float64), np.asarray(t.alias), xi)
    counts = np.bincount(idx, minlength=64)
    expect = w * len(xi)
    chi2 = np.sum((counts - expect) ** 2 / np.maximum(expect, 1e-9))
    assert chi2 < 220, chi2  # 63 dof


def test_parallel_alias_dyadic_boundary_regression():
    """Exact dyadic weights make a heavy's supply end coincide with a light's
    demand boundary; the pre-fix build charged debt to a zero-surplus heavy
    (``npi == 1`` exactly) and broke the telescoping-mass invariant by a full
    0.5. The fixed build gates debt on ``surplus > 0`` and routes it past
    zero-surplus runs — mass must be EXACT here (all values dyadic)."""
    from repro.core.alias import build_alias_parallel

    for w in (
        np.array([0.25, 0.25, 0.5, 1.0]),          # npi = (.5, .5, 1, 2)
        np.array([1.0, 0.5, 0.25, 0.25]),          # heavy-first ordering
        np.array([1, 1, 2, 4, 8], np.float64),     # pow2 ladder, sum 16
        np.array([0.5, 1.0, 0.5, 1.0, 1.0]),       # zero-surplus run
        np.array([2, 1, 1, 2, 1, 1], np.float64),  # npi hits 1 twice
    ):
        t = build_alias_parallel(w)
        mass = _alias_mass(np.asarray(t.q, np.float64), np.asarray(t.alias))
        npi = w / w.sum() * len(w)
        # f32 cast of dyadic values in [0,1] is exact => zero tolerance
        assert np.array_equal(mass, npi), (w, mass, npi)


@settings
@hypothesis.given(
    ints=st.lists(st.integers(min_value=1, max_value=64),
                  min_size=2, max_size=12),
)
def test_parallel_alias_dyadic_family_exact(ints):
    """The dyadic/exact-boundary family: integer weights completed to a
    power-of-two total, so every ``npi = w*n/total`` is exactly
    representable and boundary coincidences (including ``npi == 1``
    zero-surplus heavies) occur constantly. The telescoping-mass invariant
    must hold to float64 exactness (f32 table cast is exact for dyadics
    with <= 24 mantissa bits, which these are)."""
    from repro.core.alias import build_alias_parallel

    s = sum(ints)
    total = 1
    while total < s + 1:
        total <<= 1
    w = np.asarray(ints + [total - s], np.float64)  # sum == total (pow2)
    t = build_alias_parallel(w)
    q, alias = np.asarray(t.q, np.float64), np.asarray(t.alias)
    assert np.all((q >= 0.0) & (q <= 1.0))
    mass = _alias_mass(q, alias)
    np.testing.assert_allclose(mass, w / w.sum() * len(w), rtol=0, atol=1e-9)
