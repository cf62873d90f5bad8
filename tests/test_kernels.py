"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    MAX_DEPTH,
    build_forest,
    normalize_weights,
    sample_binary,
    sample_forest,
)
from repro.core.cdf import build_cdf
from repro.core.forest2d import build_forest_rows, sample_forest_rows
from repro.kernels import ops, ref
from repro.kernels.cdf_scan import cdf_scan
from repro.kernels.forest_delta import forest_delta, forest_delta_update
from repro.kernels.forest_sample import forest_sample
from repro.kernels.sample_tiled import sample_rows


@pytest.mark.parametrize("B,V", [(1, 100), (4, 512), (3, 1000), (8, 4096), (2, 50257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("softmax", [True, False])
def test_cdf_scan_matches_ref(B, V, dtype, softmax):
    rng = np.random.default_rng(B * V)
    if softmax:
        x = jnp.asarray(rng.normal(0, 3, (B, V)), dtype)
    else:
        x = jnp.asarray(rng.random((B, V)) + 1e-3, dtype)
    got = cdf_scan(x, softmax=softmax, interpret=True)
    want = ref.ref_cdf_scan(x, softmax=softmax)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)
    assert np.all(np.diff(np.asarray(got), axis=-1) >= -1e-6)


@pytest.mark.parametrize("B,V,k", [(4, 511, 1), (2, 4096, 4), (1, 50257, 2), (16, 1024, 1)])
@pytest.mark.parametrize("tile", [128, 512])
def test_sample_rows_matches_ref(B, V, k, tile):
    rng = np.random.default_rng(V + k)
    logits = jnp.asarray(rng.normal(0, 4, (B, V)), jnp.float32)
    cdf = ref.ref_cdf_scan(logits)
    xi = jnp.asarray(rng.random((B, k)), jnp.float32)
    got = sample_rows(cdf, xi, tile=tile, interpret=True)
    want = ref.ref_sample_rows(cdf, xi)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,m,B", [(64, 16, 333), (1000, 256, 4096), (4096, 1024, 1000)])
@pytest.mark.parametrize("power", [1, 8, 20])
def test_forest_sample_kernel_matches_oracle(n, m, B, power):
    rng = np.random.default_rng(n + power)
    w = normalize_weights(rng.random(n) ** power + 1e-9)
    f = build_forest(jnp.asarray(w), m)
    xi = jnp.asarray(rng.random(B), jnp.float32)
    got = forest_sample(f.cdf, f.table, f.left, f.right, xi, interpret=True)
    oracle = sample_binary(f.cdf, xi)
    cdf = np.asarray(f.cdf)
    g, o = np.asarray(got), np.asarray(oracle)
    assert np.array_equal(g, o) or np.all(cdf[g] == cdf[o])


@pytest.mark.parametrize(
    "spec",
    [
        ("spike_at_zero", 150, None),      # 151 exact ties at 0.0
        ("interior_ties", 0, 299),         # 299 exact ties at 0.6 (left spine)
    ],
)
def test_forest_sample_kernel_degenerate_fallback(spec):
    """Exact tied weights build zero-width chains hundreds of levels deep —
    far past the kernel's ``depth=40`` trip count — and the build flags those
    cells. The kernel + ref paths with the ``cell_first``/``fallback`` side
    tables must agree *elementwise* with ``core.sample.sample_forest``
    (pre-resolution makes that true by construction). The raw no-side-table
    descent also agrees: equal split keys send every lane the same way at
    every tied node, so a tied spine collapses to <= 2 effective branches and
    the 40-trip cap is never hit by a real uniform (a finding this test
    pins — deep *leaf* depth does not imply deep *traversal*)."""
    from repro.core import sample_forest

    _, hot, hot2 = spec
    w = np.zeros(300, np.float32)
    w[hot] = 1.2
    if hot2 is not None:
        w[hot2] = 0.8
    f = build_forest(jnp.asarray(w), 16)
    assert int(np.asarray(f.fallback).sum()) >= 1
    xi = jnp.asarray(np.random.default_rng(1).random(2048), jnp.float32)
    core = np.asarray(sample_forest(f, xi))
    kern = np.asarray(
        forest_sample(
            f.cdf, f.table, f.left, f.right, xi, f.cell_first, f.fallback,
            interpret=True,
        )
    )
    refp = np.asarray(ops.forest_sample(f, xi, use_pallas=False))
    raw = np.asarray(
        forest_sample(f.cdf, f.table, f.left, f.right, xi, interpret=True)
    )
    assert np.array_equal(kern, core)
    assert np.array_equal(refp, core)
    assert np.array_equal(raw, core)
    cdf = np.asarray(f.cdf)
    xin = np.asarray(xi)
    assert np.all(cdf[kern] <= xin) and np.all(xin < cdf[kern + 1])


def test_forest_sample_kernel_deep_adversarial():
    """Distinct-key dyadic chain ~24 levels deep in ONE cell — adversarially
    close to the kernel's depth=40 cap but legitimately resolvable by pure
    descent. The raw kernel must match no-fallback core descent, and the
    side-table kernel must match fallback core (the build flags the cell:
    depth >> log2(overlap))."""
    from repro.core import depth_stats, sample_forest

    k = 24
    w = np.asarray([2.0 ** -(i + 1) for i in range(k)] + [2.0 ** -k], np.float32)
    f = build_forest(jnp.asarray(w), 1)
    assert depth_stats(f)["max_depth"] >= k
    xi = jnp.asarray(np.random.default_rng(0).random(4096), jnp.float32)
    core_fb = np.asarray(sample_forest(f, xi))
    core_raw = np.asarray(sample_forest(f, xi, use_fallback=False))
    kern_fb = np.asarray(
        forest_sample(
            f.cdf, f.table, f.left, f.right, xi, f.cell_first, f.fallback,
            interpret=True,
        )
    )
    kern_raw = np.asarray(
        forest_sample(f.cdf, f.table, f.left, f.right, xi, interpret=True)
    )
    assert np.array_equal(kern_fb, core_fb)
    assert np.array_equal(kern_raw, core_raw)
    assert np.array_equal(core_fb, core_raw)  # no zero-width ties here
    cdf = np.asarray(f.cdf)
    xin = np.asarray(xi)
    assert np.all(cdf[kern_fb] <= xin) and np.all(xin < cdf[kern_fb + 1])


def _fixed_trip_sample(cdf, table, left, right, dist_id, xi, cell_first,
                       fallback, depth=MAX_DEPTH):
    """The fixed-trip XLA descent that the ops ran before they stopped at
    the deepest lane: a 32-trip bisection over every lane, then a
    ``depth``-trip descent. Stacked (B, .) tables; one forest is a stack of
    one row with ``dist_id = 0``."""
    B, m = table.shape
    n = left.shape[1]
    raw = dist_id.astype(jnp.int32)
    did = jnp.clip(raw, 0, B - 1)
    g = jnp.clip(jnp.floor(xi * jnp.float32(m)).astype(jnp.int32), 0, m - 1)
    j = jnp.where(raw >= 0, table[did, g], -1)
    flagged = fallback[did, g] & (j >= 0)

    def bisect_body(_, state):
        lo, hi = state
        mid = (lo + hi + 1) >> 1
        ge = xi >= cdf[did, mid]
        return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid - 1)

    lo, _ = jax.lax.fori_loop(
        0, 32, bisect_body, (cell_first[did, g], cell_first[did, g + 1]))
    j = jnp.where(flagged, ~lo, j)

    def body(_, j):
        jj = jnp.clip(j, 0, n - 1)
        nxt = jnp.where(xi < cdf[did, jj], left[did, jj], right[did, jj])
        return jnp.where(j >= 0, nxt, j)

    return ~jax.lax.fori_loop(0, depth, body, j)


def _descent_weights(case):
    """(weights, guide cells) of one forest for the descent-loop tests."""
    if case == "zipf":
        n = 4096
        return (1.0 / np.arange(1, n + 1)) ** 0.75, n   # m = n
    if case in ("spike_at_zero", "interior_ties"):
        w = np.zeros(300)
        if case == "spike_at_zero":
            w[150] = 1.2                                  # 151 ties at 0.0
        else:
            w[0], w[299] = 1.2, 0.8                       # 299 ties at 0.6
        return w, 16
    if case == "dyadic_chain":
        k = 24                                            # 24 levels, 1 cell
        return [2.0 ** -(i + 1) for i in range(k)] + [2.0 ** -k], 1
    assert case == "all_leaves"          # one interval per guide cell
    return np.ones(1024), 1024


DESCENT_CASES = ("zipf", "spike_at_zero", "interior_ties", "dyadic_chain",
                 "all_leaves")


def _one_forest_callers(op, f, w, m, xi):
    """The drain of the forest ``f`` of weights ``w`` through ``op``."""
    if op == "forest_sample":
        return ops.forest_sample(f, xi, use_pallas=False)
    if op == "core.sample_forest":
        return sample_forest(f, xi)
    from jax.sharding import Mesh

    from repro.dist import forest as DF

    D = max(d for d in (1, 2, 4, 8)
            if d <= jax.device_count() and m % d == 0)
    mesh = Mesh(np.array(jax.devices()[:D]), ("data",))
    sf = DF.build_forest_sharded(w, m, mesh=mesh)
    for k in ("cdf", "table", "cell_first", "fallback"):
        assert np.array_equal(np.asarray(getattr(sf, k)),
                              np.asarray(getattr(f, k))), k
    return DF.sample_sharded(sf, xi, mesh=mesh,
                             routed=op == "dist.sample_sharded_routed")


ONE_FOREST_CALLERS = ("forest_sample", "core.sample_forest",
                      "dist.sample_sharded_routed",
                      "dist.sample_sharded_replicated")


@pytest.mark.parametrize(
    "op,case",
    [(op, case)
     for op in ONE_FOREST_CALLERS + (
         "forest_sample_batched", "forest_sample_batched_streams",
         "core.sample_forest_rows")
     for case in DESCENT_CASES + ("sentinel_lanes",)
     if not (op in ONE_FOREST_CALLERS + ("core.sample_forest_rows",)
             and case == "sentinel_lanes")],
)
def test_xla_descent_matches_fixed_trip_loop(op, case):
    """Every caller of the one XLA descent (``core.sample.descend``) stops
    when its deepest lane is done, yet answers elementwise as the fixed
    ``MAX_DEPTH``-trip descent behind a fixed 32-trip bisection does: deep
    chains, flagged cells, sentinel lanes, and a batch in which no lane
    descends at all. The callers: the three ``ops`` drains, the core
    sampler, the row-forest sampler and both sharded drains."""
    from repro.core.lds import qmc_point
    from repro.pool.batched import batched_from_row_forest, build_forest_batched

    rng = np.random.default_rng(0)
    w, m = _descent_weights("zipf" if case == "sentinel_lanes" else case)
    w = np.asarray(w, np.float32)
    Q = 4096
    if op in ONE_FOREST_CALLERS:
        wn = jnp.asarray(normalize_weights(w))
        f = build_forest(wn, m)
        xi = jnp.asarray(rng.random(Q), jnp.float32)
        got = _one_forest_callers(op, f, wn, m, xi)
        stack = [x[None] for x in f]
        want = _fixed_trip_sample(*stack[:4], jnp.zeros(Q, jnp.int32), xi,
                                  *stack[4:])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    rows = [w, w[::-1], w] if case == "all_leaves" else [
        w, w[::-1], rng.random(w.size).astype(np.float32) + 1e-3]
    lo = -1 if case == "sentinel_lanes" else 0
    did = jnp.asarray(rng.integers(lo, len(rows), Q), jnp.int32)
    if op == "core.sample_forest_rows":
        cdf_rows = jnp.stack([build_cdf(jnp.asarray(normalize_weights(r)))
                              for r in rows])
        rf = build_forest_rows(cdf_rows, m)
        bf = batched_from_row_forest(rf, cdf_rows)
        xi = jnp.asarray(rng.random(Q), jnp.float32)
        got = sample_forest_rows(rf, did, xi)
        # the row forest compares against its own lower bounds
        want = _fixed_trip_sample(rf.data.reshape(len(rows), -1), bf.table,
                                  bf.left, bf.right, did, xi, bf.cell_first,
                                  bf.fallback)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    bf = build_forest_batched(jnp.asarray(np.stack(rows)), m)
    if op == "forest_sample_batched":
        xi = jnp.asarray(rng.random(Q), jnp.float32)
        got = ops.forest_sample_batched(bf, did, xi, use_pallas=False)
    else:
        counter = jnp.asarray(rng.integers(0, 1 << 20, Q), jnp.uint32)
        offset = jnp.asarray(rng.integers(0, 1 << 24, Q), jnp.uint32)
        got, xi = ops.forest_sample_batched_streams(
            bf, did, counter, offset, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(xi),
                                      np.asarray(qmc_point(counter, offset)))
    want = _fixed_trip_sample(bf.cdf, bf.table, bf.left, bf.right, did, xi,
                              bf.cell_first, bf.fallback)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if case == "sentinel_lanes":
        assert np.all(np.asarray(got)[np.asarray(did) < 0] == 0)


def test_descent_trip_cap_is_max_depth():
    """One trip cap for every XLA descent. Zero weights after the last mass
    whose float32 sum falls one ulp short of 1 leave 86 keys tied at
    0.99999994 on a right spine, which the uniform at that key walks: 90
    trips, more than 64. With the side tables off, the ``ops`` drain and
    the core sampler must agree and answer valid intervals."""
    from repro.core import depth_stats, sample_forest_with_stats

    w = np.zeros(250)
    w[[14, 103, 108, 144, 163]] = 2.0 ** -np.array([20, 6, 1, 18, 8])
    f = build_forest(jnp.asarray(normalize_weights(w)), 1)
    assert 64 < depth_stats(f)["max_depth"] < MAX_DEPTH
    cdf = np.asarray(f.cdf)
    xi = np.concatenate([np.unique(cdf[:-1]),
                         np.random.default_rng(0).random(4096)])
    xi = jnp.asarray(xi[xi < 1], jnp.float32)
    assert int(np.asarray(sample_forest_with_stats(f, xi)[1]).max()) > 64
    got = np.asarray(ops.forest_sample(f, xi, use_pallas=False,
                                       degenerate=False))
    core = np.asarray(sample_forest(f, xi, use_fallback=False))
    np.testing.assert_array_equal(got, core)
    x = np.asarray(xi)
    assert np.all((got >= 0) & (got < cdf.size - 1))
    assert np.all((cdf[got] <= x) & (x < cdf[got + 1]))


@pytest.mark.parametrize("case", DESCENT_CASES)
def test_xla_descent_trip_counts(case):
    """The descent takes as many trips as its deepest lane loads nodes
    (``sample_forest_with_stats``); the bisection as many as its longest
    lane needs to halve its cell's [lo, hi] down to the index it drew, at
    most ceil(log2(span + 1)) for the widest flagged span."""
    from repro.core import sample_forest_with_stats

    w, m = _descent_weights(case)
    f = build_forest(jnp.asarray(normalize_weights(np.asarray(w, np.float32))),
                     m)
    xi = jnp.asarray(np.random.default_rng(3).random(4096), jnp.float32)
    _, trips, no_bisect = ref.ref_forest_descent(
        f.cdf, f.table, f.left, f.right, xi)
    visits = np.asarray(sample_forest_with_stats(f, xi)[1])
    assert int(trips) == visits.max()
    assert int(no_bisect) == 0
    if case == "all_leaves":
        assert int(trips) == 0

    idx, _, bisect_trips = ref.ref_forest_descent(
        f.cdf, f.table, f.left, f.right, xi, f.cell_first, f.fallback)
    g = np.clip(np.floor(np.asarray(xi) * np.float32(m)).astype(np.int64),
                0, m - 1)
    fb, cf = np.asarray(f.fallback), np.asarray(f.cell_first)
    flagged = fb[g] & (np.asarray(f.table)[g] >= 0)
    lo, hi = cf[g][flagged], cf[g + 1][flagged]
    # halve each flagged lane's [lo, hi] towards the index it drew
    target, need = np.asarray(idx)[flagged], np.zeros(lo.size, np.int64)
    while np.any(lo < hi):
        mid = (lo + hi + 1) >> 1
        need += lo < hi
        lo, hi = (np.where(target >= mid, mid, lo),
                  np.where(target >= mid, hi, mid - 1))
    assert int(bisect_trips) == need.max(initial=0)
    span = (cf[g + 1] - cf[g])[flagged].max(initial=0)
    assert int(bisect_trips) <= int(np.ceil(np.log2(span + 1)))
    if case in ("spike_at_zero", "interior_ties", "dyadic_chain"):
        assert flagged.any() and int(bisect_trips) > 0


@pytest.mark.parametrize("n,m", [(2, 1), (100, 7), (1023, 64), (8192, 4096)])
def test_forest_delta_matches_ref(n, m):
    rng = np.random.default_rng(n)
    data = jnp.asarray(np.sort(rng.random(n)).astype(np.float32))
    got = forest_delta(data, m, interpret=True)
    want = ref.ref_forest_delta(data, m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m", [7, 64, 1024, 4096])
def test_forest_delta_matches_core_separator_distances(m):
    """The kernel must agree bitwise with the distance array the tree
    builder actually consumes (core._separator_distances over clipped
    cells) — pinned on the adversarial boundary case of a huge leading
    weight pushing every trailing tied lower bound to 1 - 2^-24, the
    closest data gets to the floor(data * m) == m edge."""
    from repro.core.cdf import build_cdf, lower_bounds
    from repro.core.forest import _cells, _separator_distances

    w = np.full(300, 1e-30, np.float32)
    w[0] = 1.0
    data = lower_bounds(build_cdf(jnp.asarray(w)))
    want = np.asarray(_separator_distances(data, _cells(data, m)))
    got = np.asarray(forest_delta(data, m, interpret=True))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(ref.ref_forest_delta(data, m)), want
    )


@pytest.mark.parametrize("n,m", [(2, 1), (100, 7), (1023, 64)])
def test_forest_delta_update_matches_ref(n, m):
    """The delta-update kernel: new distances == forest_delta(new data), the
    changed mask == exact bit-pattern inequality, and the pallas/ref ops
    dispatch agrees."""
    rng = np.random.default_rng(n + 1)
    old = np.sort(rng.random(n)).astype(np.float32)
    new = old.copy()
    moved = rng.random(n) < 0.3
    new[moved] = np.nextafter(new[moved], np.float32(1.0))
    d_got, c_got = forest_delta_update(
        jnp.asarray(old), jnp.asarray(new), m, interpret=True
    )
    d_ref, c_ref = ref.ref_forest_delta_update(
        jnp.asarray(old), jnp.asarray(new), m
    )
    np.testing.assert_array_equal(np.asarray(d_got), np.asarray(d_ref))
    np.testing.assert_array_equal(np.asarray(c_got), np.asarray(c_ref))
    np.testing.assert_array_equal(
        np.asarray(d_got), np.asarray(forest_delta(jnp.asarray(new), m,
                                                   interpret=True))
    )
    np.testing.assert_array_equal(
        np.asarray(c_got), old.view(np.uint32) != new.view(np.uint32)
    )
    via_ops = ops.forest_delta_update(
        jnp.asarray(old), jnp.asarray(new), m, use_pallas=False
    )
    np.testing.assert_array_equal(np.asarray(via_ops[0]), np.asarray(d_got))
    np.testing.assert_array_equal(np.asarray(via_ops[1]), np.asarray(c_got))


def test_ops_dispatch_consistency():
    """use_pallas=True/False must agree (kernel vs reference path)."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 2, (4, 777)), jnp.float32)
    a = ops.fused_cdf(logits, use_pallas=True)
    b = ops.fused_cdf(logits, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)

    xi = jnp.asarray(rng.random((4, 2)), jnp.float32)
    ia = ops.sample_rows(a, xi, use_pallas=True)
    ib = ops.sample_rows(b, xi, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))


def test_end_to_end_decode_sampling_path():
    """logits -> fused CDF -> tiled sampler == softmax ground truth marginals.

    The kernel takes few uniforms per row (decode semantics), so replicate
    the row to gather S samples of one distribution.
    """
    rng = np.random.default_rng(42)
    V, S, k = 1031, 2048, 4
    logits = jnp.asarray(rng.normal(0, 2, (1, V)), jnp.float32)
    cdf = ops.fused_cdf(logits)
    p = np.asarray(jax.nn.softmax(logits, axis=-1))[0]
    rows = jnp.broadcast_to(cdf, (S // k, V))
    xi = jnp.asarray(rng.random((S // k, k)), jnp.float32)
    idx = np.asarray(ops.sample_rows(rows, xi)).ravel()
    counts = np.bincount(idx, minlength=V)
    top = p.argmax()
    exp, got = p[top] * S, counts[top]
    sd = np.sqrt(max(exp * (1 - p[top]), 1.0))
    assert abs(got - exp) < 5 * sd


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 32), (2, 96, 4, 2, 64), (1, 256, 8, 2, 32), (2, 64, 2, 1, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, S, H, KV, hd, causal, dtype):
    from repro.kernels.flash_attention import flash_attention

    rng = np.random.default_rng(S + H)
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, hd)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)), dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    want = ref.ref_flash_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_ragged_causal():
    """Non-divisible sequence lengths exercise the padding path."""
    from repro.kernels.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (1, 100, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 100, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, 100, 2, 32)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    want = ref.ref_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_ops_implementation_policy(monkeypatch, backend):
    """One dispatch point: None takes the policy (kernels compiled on a TPU,
    references elsewhere), True off a TPU is the interpreter, and the ops
    Mosaic refuses stay on XLA on every backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    tpu = backend == "tpu"
    for op in ops.OPS:
        refused = op in ops.XLA_ONLY
        assert ops.implementation(op, False) == "xla"
        assert ops.implementation(op) == ("pallas" if tpu and not refused else "xla")
        want = "xla" if tpu and refused else ("pallas" if tpu else "interpret")
        assert ops.implementation(op, True) == want
    assert ops.implementations() == {op: ops.implementation(op) for op in ops.OPS}
    with pytest.raises(ValueError):
        ops.implementation("no_such_op")
