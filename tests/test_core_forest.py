"""Property + unit tests for the radix-tree-forest core (paper Secs. 2-3)."""
import contextlib

import hypothesis
import hypothesis.strategies as st
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    build_cdf,
    build_forest,
    build_forest_apetrei,
    build_forest_from_cdf,
    depth_stats,
    forest_to_numpy,
    normalize_weights,
    np_build_cdf,
    np_sample_cutpoint_binary_counting,
    np_sample_forest_counting,
    sample_binary,
    sample_cutpoint_binary,
    sample_cutpoint_linear,
    sample_forest,
    sample_forest_with_stats,
    sample_linear,
    validate_forest,
)

settings = hypothesis.settings(max_examples=30, deadline=None)


def _same_interval(cdf, a, b):
    """Equal index, or zero-width-tied intervals (same boundary value)."""
    return np.array_equal(a, b) or bool(np.all(cdf[a] == cdf[b]))


weights_strategy = st.lists(
    st.floats(min_value=0.0, max_value=1e6, allow_nan=False, width=32),
    min_size=1,
    max_size=300,
).filter(lambda w: sum(w) > 1e-6)


@settings
@hypothesis.given(
    w=weights_strategy,
    m=st.integers(min_value=1, max_value=512),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_forest_inverts_cdf(w, m, seed):
    """Core property: forest traversal == monotone inverse CDF, for any
    non-negative weights (including zeros) and any guide-table size."""
    f = build_forest(jnp.asarray(w, jnp.float32), m)
    xi = np.random.default_rng(seed).random(512).astype(np.float32)
    got = np.asarray(sample_forest(f, jnp.asarray(xi)))
    oracle = np.asarray(sample_binary(f.cdf, jnp.asarray(xi)))
    cdf = np.asarray(f.cdf)
    assert _same_interval(cdf, got, oracle)
    # Inversion property: P_{i-1} <= xi < P_i
    assert np.all(cdf[got] <= xi) and np.all(xi < cdf[got + 1])


@settings
@hypothesis.given(
    w=weights_strategy.filter(lambda w: all(x > 1e-6 for x in w)),
    m=st.integers(min_value=1, max_value=64),
)
def test_vectorized_builder_matches_apetrei(w, m):
    """The TPU-native builder is bit-identical to the faithful Algorithm-1
    emulation (same trees, same guide table) for positive weights."""
    f = build_forest(jnp.asarray(w, jnp.float32), m)
    ap = build_forest_apetrei(np.asarray(f.cdf), m)
    fn = forest_to_numpy(f)
    assert np.array_equal(fn["table"], ap["table"])
    assert np.array_equal(fn["left"], ap["left"])
    assert np.array_equal(fn["right"], ap["right"])


@settings
@hypothesis.given(
    w=weights_strategy,
    m=st.integers(min_value=1, max_value=128),
)
def test_forest_structure_valid(w, m):
    f = build_forest(jnp.asarray(w, jnp.float32), m)
    validate_forest(f)


@settings
@hypothesis.given(
    w=weights_strategy,
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_monotonicity(w, seed):
    """The paper's central claim vs the Alias Method: the mapping xi -> i is
    non-decreasing, so low-discrepancy structure survives the warp."""
    f = build_forest(jnp.asarray(w, jnp.float32), 32)
    xi = np.sort(np.random.default_rng(seed).random(256).astype(np.float32))
    got = np.asarray(sample_forest(f, jnp.asarray(xi)))
    assert np.all(np.diff(got) >= 0)


@settings
@hypothesis.given(
    w=weights_strategy,
    m=st.integers(min_value=1, max_value=64),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_all_samplers_agree(w, m, seed):
    cdf = build_cdf(jnp.asarray(w, jnp.float32))
    f = build_forest_from_cdf(cdf, m)
    xi = np.random.default_rng(seed).random(256).astype(np.float32)
    xj = jnp.asarray(xi)
    cdf_np = np.asarray(cdf)
    ref = np.asarray(sample_binary(cdf, xj))
    n = len(w)
    for name, got in {
        "linear": np.asarray(sample_linear(cdf, xj)),
        "cut_bin": np.asarray(sample_cutpoint_binary(cdf, f.cell_first, xj)),
        "cut_lin": np.asarray(sample_cutpoint_linear(cdf, f.cell_first, xj, n)),
        "forest": np.asarray(sample_forest(f, xj)),
        "forest_nofb": np.asarray(sample_forest(f, xj, use_fallback=False)),
    }.items():
        assert _same_interval(cdf_np, got, ref), name


def test_distribution_preserved_chi2():
    """Sampled histogram matches p (chi^2 well under a generous bound)."""
    rng = np.random.default_rng(7)
    p = normalize_weights(rng.random(64) ** 4 + 1e-4)
    f = build_forest(jnp.asarray(p), 64)
    n_samples = 1 << 16
    xi = rng.random(n_samples).astype(np.float32)
    idx = np.asarray(sample_forest(f, jnp.asarray(xi)))
    counts = np.bincount(idx, minlength=64)
    expected = p * n_samples
    chi2 = float(np.sum((counts - expected) ** 2 / np.maximum(expected, 1e-9)))
    # 63 dof: mean 63, sd ~11; 200 is a ~12-sigma guard against regression
    assert chi2 < 200, chi2


def test_counting_twins_match_jax():
    rng = np.random.default_rng(3)
    w = normalize_weights(rng.random(200) ** 6 + 1e-9)
    f = build_forest(jnp.asarray(w), 128)
    xi = rng.random(2048).astype(np.float32)
    i_jax, visits = sample_forest_with_stats(f, jnp.asarray(xi))
    i_np, loads = np_sample_forest_counting(f, xi)
    assert np.array_equal(np.asarray(i_jax), i_np)
    # numpy twin counts the guide load too
    assert np.array_equal(np.asarray(visits) + 1, loads)


def test_degenerate_ties_fall_back():
    """Zero-width intervals chain deeper than the 32-level radix bound; the
    build must flag those cells and fallback traversal must stay correct."""
    w = np.zeros(300, np.float32)
    w[150] = 1.0
    f = build_forest(jnp.asarray(w + 1e-12), 16)
    assert int(np.asarray(f.fallback).sum()) >= 1
    xi = np.random.default_rng(0).random(1024).astype(np.float32)
    got = np.asarray(sample_forest(f, jnp.asarray(xi)))
    cdf = np.asarray(f.cdf)
    assert np.all(cdf[got] <= xi) and np.all(xi < cdf[got + 1])


def test_single_interval():
    f = build_forest(jnp.asarray([3.0], jnp.float32), 8)
    xi = jnp.asarray([0.0, 0.3, 0.999], jnp.float32)
    assert np.array_equal(np.asarray(sample_forest(f, xi)), [0, 0, 0])


def test_table1_shape_of_results():
    """Sanity on the Table-1 reproduction: forest beats binary search on
    avg_32 for the high-dynamic-range periodic distributions."""
    n = 256
    rng = np.random.default_rng(0)
    xi = rng.random(1 << 14).astype(np.float32)
    w = normalize_weights((np.arange(n) % 64 + 1.0) ** 35)
    f = build_forest(jnp.asarray(w), 256)
    _, loads_f = np_sample_forest_counting(f, xi)
    _, loads_b = np_sample_cutpoint_binary_counting(
        np.asarray(f.cdf), np.asarray(f.cell_first), np.asarray(f.table), xi
    )
    from repro.core import warp_cost

    assert warp_cost(loads_f) < warp_cost(loads_b)


_FUZZ_KINDS = ("uniform", "powerlaw", "ties", "zeros", "wide", "single")


def _fuzz_weights(kind: str, n: int, rng) -> np.ndarray:
    if kind == "uniform":
        return rng.random(n).astype(np.float32) + np.float32(1e-3)
    if kind == "powerlaw":
        return (rng.random(n).astype(np.float32) ** 8) + np.float32(1e-9)
    if kind == "ties":   # many exact float32 ties -> zero separator distances
        base = rng.random(max(n // 8, 1)).astype(np.float32) + np.float32(1e-3)
        return base[rng.integers(0, len(base), n)]
    if kind == "zeros":  # ~half the intervals have zero width
        w = rng.random(n).astype(np.float32)
        w[rng.random(n) < 0.5] = 0.0
        w[rng.integers(0, n)] = 1.0   # keep the total positive
        return w
    if kind == "wide":   # 60 decades of dynamic range in one vector
        return (10.0 ** rng.uniform(-30, 30, n)).astype(np.float32)
    return rng.random(1).astype(np.float32) + np.float32(0.5)   # single


@pytest.mark.parametrize("m", [1, 7, 64, 1024])
@pytest.mark.parametrize("kind", _FUZZ_KINDS)
def test_fuzz_matrix_builder_bit_identical_and_valid(kind, m):
    """Randomized regression matrix beyond the fixed cases above: every
    weight family (power-law, uniform, exact ties, zeros, single-element,
    1e-30..1e30 spans) x guide-table size must (a) produce a structurally
    valid forest, (b) be bit-identical to the Algorithm-1 emulation, and
    (c) satisfy the inversion property under traversal."""
    rng = np.random.default_rng(1000 * m + _FUZZ_KINDS.index(kind))
    for n in (1,) if kind == "single" else (2, 13, 300):
        w = _fuzz_weights(kind, n, rng)
        f = build_forest(jnp.asarray(w), m)
        validate_forest(f)
        ap = build_forest_apetrei(np.asarray(f.cdf), m)
        fn = forest_to_numpy(f)
        for key in ("table", "left", "right"):
            assert np.array_equal(fn[key], ap[key]), (kind, n, m, key)
        xi = rng.random(256).astype(np.float32)
        got = np.asarray(sample_forest(f, jnp.asarray(xi)))
        cdf = np.asarray(f.cdf)
        assert np.all(cdf[got] <= xi) and np.all(xi < cdf[got + 1]), (kind, n, m)


def test_np_build_cdf_matches_jax():
    rng = np.random.default_rng(11)
    w = rng.random(100).astype(np.float32)
    np.testing.assert_allclose(
        np_build_cdf(w), np.asarray(build_cdf(jnp.asarray(w))), atol=2e-7
    )


def test_batch_cost_is_lane_max():
    """DESIGN §3: predicated batch traversal costs max-per-batch visits —
    the while_loop iteration count equals the deepest lane's node count
    (the hardware analogue of the paper's average_32)."""
    rng = np.random.default_rng(0)
    w = normalize_weights(rng.random(256) ** 15 + 1e-12)
    f = build_forest(jnp.asarray(w), 64)
    xi = jnp.asarray(rng.random(1024), jnp.float32)
    _, visits = sample_forest_with_stats(f, xi)
    v = np.asarray(visits)
    # per-32-lane groups: cost of the group = its max (all lanes step together)
    groups = v[: 1024 // 32 * 32].reshape(-1, 32)
    assert np.all(groups.max(axis=1) >= groups.mean(axis=1))
    assert v.max() <= 64  # bounded by MAX_DEPTH guard for real CDFs


# ------------------------------------------------ the depth guard's walk


def _fixed_chain_depth(leaf_parent, node_parent):
    """The depth guard's walk before it stopped at the last chain's end: a
    fixed ``_DEPTH_ITERS``-step chase (the oracle)."""
    from repro.core.forest import _DEPTH_ITERS

    def step(_, state):
        anc, depth = state
        live = anc >= 0
        return (jnp.where(live, node_parent[jnp.clip(anc, 0)], anc),
                depth + live.astype(jnp.int32))

    _, depth = jax.lax.fori_loop(0, _DEPTH_ITERS, step,
                                 (leaf_parent, jnp.zeros_like(leaf_parent)))
    return depth + 1, jnp.int32(_DEPTH_ITERS)


@contextlib.contextmanager
def _depth_walk(walk):
    """Run the builders with ``walk`` in place of ``_chain_depth``. Builds
    inside trace anew (``_build_with`` jits a fresh function each time, and
    the sharded builder's program cache is dropped on entry and exit)."""
    from repro.core import forest as F, forest2d as F2
    from repro.dist import forest as DF

    saved = F._chain_depth
    F._chain_depth = F2._chain_depth = walk
    DF._windowed_builder.cache_clear()
    try:
        yield
    finally:
        F._chain_depth = F2._chain_depth = saved
        DF._windowed_builder.cache_clear()


def _checked_walk(seen: list):
    """The builders' own walk, which also hands each call's ``(depth equals
    the fixed walk's, trips)`` to ``seen`` from inside the program, under
    ``jit``, ``vmap`` and ``shard_map`` alike (one call per row or shard)."""
    from repro.core.forest import _chain_depth

    def walk(leaf_parent, node_parent):
        depth, trips = _chain_depth(leaf_parent, node_parent)
        want, _ = _fixed_chain_depth(leaf_parent, node_parent)
        jax.debug.callback(lambda same, t: seen.append((bool(same), int(t))),
                           jnp.all(depth == want), trips)
        return depth, trips

    return walk


def _chain_weights(case):
    """(weights, guide cells) of one forest for the depth-walk tests."""
    if case == "zipf":                       # Zipf^0.75 at m = n
        n = 4096
        return (1.0 / np.arange(1, n + 1)) ** 0.75, n
    if case == "spike":                      # 151 ties at 0, 149 at the top
        w = np.zeros(300)
        w[150] = 1.2
        return w, 16
    if case == "interior_ties":              # 20 ties inside one cell
        w = np.random.default_rng(7).random(64) + 0.1
        w[20:40] = 0.0
        return w, 4
    if case == "tied_run":                   # 298 ties: saturates at 48
        w = np.zeros(300)
        w[0], w[299] = 1.2, 0.8
        return w, 1
    if case == "dyadic_chain":               # 24 levels in one cell
        k = 24
        return [2.0 ** -(i + 1) for i in range(k)] + [2.0 ** -k], 1
    if case == "n1":
        return [3.0], 8
    if case == "n2":
        return [1.0, 2.0], 4
    assert case == "all_leaves"              # one interval per guide cell
    return np.ones(1024), 1024


WEIGHT_FAMILIES = ("zipf", "spike", "interior_ties", "tied_run",
                   "dyadic_chain", "n1", "n2")
CHAIN_CASES = WEIGHT_FAMILIES + ("all_leaves",)


def _longest_chain(f: dict) -> int:
    """Longest leaf-to-root chain of a forest from ``depth_stats``'s numpy
    walk down every cell's tree: the nodes met above the deepest leaf. Every
    leaf hangs at least under its own cell's root slot, so it is at least 1."""
    from repro.core.forest import RadixForest

    fields = {k: f[k] for k in RadixForest._fields}
    return max(depth_stats(RadixForest(**fields))["max_depth"] - 1, 1)


def _build_with(builder, w, m):
    """One build of ``w`` (and, for the pool, its mirror image) through
    ``builder``, traced anew, as numpy arrays keyed by field."""
    from repro.pool.batched import build_forest_batched_from_cdf

    cdf = build_cdf(jnp.asarray(np.asarray(w, np.float32)))
    if builder == "forest":
        out = jax.jit(lambda c: build_forest_from_cdf.__wrapped__(c, m))(cdf)
    elif builder == "pool":
        cdfs = jnp.stack([cdf, build_cdf(jnp.asarray(
            np.asarray(w, np.float32)[::-1]))])
        out = jax.jit(lambda c: build_forest_batched_from_cdf.__wrapped__(
            c, m))(cdfs)
    else:
        from jax.sharding import Mesh

        from repro.dist import forest as DF

        D = max(d for d in (1, 2, 4, 8)
                if d <= jax.device_count() and m % d == 0)
        mesh = Mesh(np.array(jax.devices()[:D]), ("data",))
        out = DF.gather_forest(DF.build_forest_from_cdf_sharded(
            cdf, m, mesh=mesh))
    jax.effects_barrier()
    return {k: np.asarray(v) for k, v in out._asdict().items()}


@pytest.mark.parametrize("builder", ["forest", "pool", "sharded"])
@pytest.mark.parametrize("case", WEIGHT_FAMILIES)
def test_depth_walk_matches_fixed_walk(case, builder):
    """The depth guard's walk stops when the last chain ends, yet every
    leaf's depth equals the fixed 48-step walk's, and the whole forest and
    its fallback flags are those built with the fixed walk: the 1-D builder,
    the vmapped pool builder and the sharded builder at this process's
    device count."""
    w, m = _chain_weights(case)
    seen: list = []
    with _depth_walk(_checked_walk(seen)):
        got = _build_with(builder, w, m)
    with _depth_walk(_fixed_chain_depth):
        want = _build_with(builder, w, m)
    assert seen and all(same for same, _ in seen), seen
    assert all(1 <= t <= 48 for _, t in seen), seen
    for key in want:
        assert np.array_equal(got[key], want[key]), key
    if case in ("spike", "tied_run", "dyadic_chain"):
        assert got["fallback"].any()


@pytest.mark.parametrize("case", CHAIN_CASES)
def test_depth_walk_trip_count(case):
    """The walk takes as many trips as the forest's longest leaf-to-root
    chain, capped at 48: one for a forest of lone leaves (no internal node),
    48 for a tied run deeper than that."""
    w, m = _chain_weights(case)
    seen: list = []
    with _depth_walk(_checked_walk(seen)):
        f = _build_with("forest", w, m)
    (same, trips), = seen
    assert same
    assert trips == min(_longest_chain(f), 48)
    expect = {"tied_run": 48, "spike": 48, "dyadic_chain": 25,
              "all_leaves": 1, "n1": 1, "n2": 1}
    if case in expect:
        assert trips == expect[case]
