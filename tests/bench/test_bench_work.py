"""The benchmark's work counts: its copy of the Table-1 load model agrees
with ``repro.core.counting`` on small forests, and the byte counts follow
the shapes."""
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_path import ROOT  # noqa: F401
from bench.work import build, drain, loads
from repro.configs.paper_workloads import TABLE1
from repro.core import build_forest
from repro.core.cdf import build_cdf
from repro.core.counting import np_sample_forest_counting
from repro.core.forest2d import build_forest_rows

DISTS = {**TABLE1, "zipf^0.75": lambda n: np.arange(1, n + 1) ** -0.75}


@pytest.mark.parametrize("name", sorted(DISTS))
@pytest.mark.parametrize("n,m", [(256, 256), (1000, 333)])
def test_node_loads_match_the_programs_counting(name, n, m):
    w = np.asarray(DISTS[name](n), np.float64)
    f = build_forest(jnp.asarray(w / w.sum(), jnp.float32), m)
    xi = np.random.default_rng(0).random(1 << 12).astype(np.float32)
    leaf_ref, loads_ref = np_sample_forest_counting(f, xi)
    cdf = np.asarray(f.cdf)
    leaf, node = loads.node_loads(cdf[:-1], np.asarray(f.table),
                                  np.asarray(f.left), np.asarray(f.right), xi)
    assert np.array_equal(leaf, leaf_ref)
    assert np.array_equal(node, loads_ref - 1)  # the model's guide load


def test_flat_row_forests_count_like_single_forests():
    rng = np.random.default_rng(1)
    W, R = 64, 5
    rows = rng.random((R, W)) ** 4 + 1e-3
    rows /= rows.sum(axis=1, keepdims=True)
    cdf_rows = jnp.stack([build_cdf(jnp.asarray(r, jnp.float32)) for r in rows])
    rf = build_forest_rows(cdf_rows, m=W)
    slot = rng.integers(0, R, 2048)
    xi = rng.random(2048).astype(np.float32)
    leaf, node = loads.node_loads(np.asarray(rf.data), np.asarray(rf.table),
                                  np.asarray(rf.left), np.asarray(rf.right),
                                  xi, cell_base=slot * W, m=W)
    for r in range(R):
        f = build_forest(jnp.asarray(rows[r], jnp.float32), W)
        sel = slot == r
        leaf_ref, loads_ref = np_sample_forest_counting(f, xi[sel])
        assert np.array_equal(leaf[sel] - r * W, leaf_ref)
        assert np.array_equal(node[sel], loads_ref - 1)


def test_byte_counts():
    assert drain.bytes_per_draw(0.0) == 12
    assert drain.bytes_per_draw(1.5) == 12 + 18
    assert drain.bytes_per_draw(1.0, 2.0) == 24 + 36
    n, m = 3_000_000, 3_000_000
    assert build.bytes_per_build(n, m) == 16 * n + 4 + 9 * m + 4
