"""``repro.trace``: the program's device scopes reach the compiled HLO under
stable names, its host spans reach the profiler's trace, and its counters
count the bytes the host boundaries copy."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.configs.paper_workloads import env_map_2d
from repro.core import build_forest, build_forest_from_cdf
from repro.core.cdf import build_cdf
from repro.kernels import ops
from repro.spatial import Map2DSampler
from repro.spatial.map2d import _fused_sample

OP_NAME = re.compile(r'op_name="([^"]*)"')


def scopes_in_hlo(compiled_text: str) -> set[str]:
    """The dotted scope names of every ``op_name`` in compiled HLO text."""
    return {name for path in OP_NAME.findall(compiled_text)
            for name in re.findall(r"[A-Za-z_]\w*\.\w+", path)}


def _weights(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).random(n) + 0.01,
                       jnp.float32)


def test_counters_count_and_reset():
    trace.reset_counters()
    trace.count("x", 3)
    trace.count("x", 4)
    trace.count("y", np.int64(2))
    assert trace.counters() == {"x": 7, "y": 2}
    snapshot = trace.counters()
    trace.count("x", 1)
    assert snapshot["x"] == 7          # a copy, not the live counters
    trace.reset_counters()
    assert trace.counters() == {}


def test_scope_names_every_op_traced_under_it():
    @jax.jit
    def f(x):
        with trace.scope("unit.outer"):
            y = jnp.sort(x) * 2
        return y + 1

    text = f.lower(jnp.arange(8.0)).compile().as_text()
    assert "unit.outer" in scopes_in_hlo(text)
    # a scope is metadata: the result is the unscoped computation's
    np.testing.assert_array_equal(f(jnp.arange(8.0)), jnp.arange(8.0) * 2 + 1)


def test_build_phases_are_scoped_in_the_compiled_build():
    cdf = build_cdf(_weights(512))
    text = build_forest_from_cdf.lower(cdf, 512).compile().as_text()
    assert {"forest.separators", "forest.cell_trees",
            "forest.depth_guard"} <= scopes_in_hlo(text)


@pytest.mark.parametrize("degenerate", [False, True])
def test_drain_is_scoped_in_the_compiled_drain(degenerate):
    forest = build_forest(_weights(512), 512)
    drain = jax.jit(functools.partial(ops.forest_sample,
                                      degenerate=degenerate))
    xi = jnp.linspace(0.0, 0.999, 256, dtype=jnp.float32)
    text = drain.lower(forest, xi).compile().as_text()
    assert "ops.forest_sample" in scopes_in_hlo(text)


def test_fused_map_sample_holds_both_descents():
    sampler = Map2DSampler(env_map_2d(16, 32))
    assert sampler._fused
    cls = next(iter(sampler.classes.values()))
    u = v = jnp.linspace(0.0, 0.999, 128, dtype=jnp.float32)
    text = _fused_sample.lower(
        sampler._marginal, cls.forest, sampler._slot_j, sampler._widths_j,
        u, v, use_pallas=None, marg_degenerate=False, cond_degenerate=False,
        coalesce=True).compile().as_text()
    assert {"ops.forest_sample", "ops.forest_sample_batched"} <= (
        scopes_in_hlo(text))


def test_scoped_ops_keep_their_names_and_signatures():
    import inspect

    for op in ops.OPS:
        fn = getattr(ops, op)
        assert fn.__name__ == op
        assert "use_pallas" in inspect.signature(fn).parameters


@pytest.mark.parametrize("points", [1000, 4096])
def test_sample_map_counts_16_bytes_a_point(points):
    sampler = Map2DSampler(env_map_2d(16, 32))
    pts = np.random.default_rng(points).random((points, 2)).astype(np.float32)
    trace.reset_counters()
    row, col, _, _ = sampler.sample_map(pts)
    assert row.dtype == col.dtype == np.int32
    assert trace.counters() == {"host.bytes_in": 8 * points,
                                "host.bytes_out": 8 * points}


def test_unfused_sample_map_counts_its_copies():
    rows = [np.ones(5), np.ones(40), np.ones(3)]     # two size classes
    sampler = Map2DSampler(rows)
    assert not sampler._fused
    pts = np.random.default_rng(1).random((100, 2)).astype(np.float32)
    trace.reset_counters()
    sampler.sample_map(pts)
    c = trace.counters()
    assert c["host.bytes_in"] >= 4 * 100 + 8 * 100   # u, then slot ids + v
    assert c["host.bytes_out"] >= 4 * 100 + 4 * 100  # rows, then columns


def test_update_map_counts_its_pulls_and_uploads():
    img = env_map_2d(16, 32)
    sampler = Map2DSampler(img)
    trace.reset_counters()
    stats = sampler.update_map({3: img[3] * 2.0 + 1.0})
    assert stats["rebuilt_rows"] == 1 and stats["marginal_rebuilt"]
    c = trace.counters()
    # one skip flag for the touched row, the marginal's old and new CDFs,
    # and the class's and the marginal's degenerate flags: the class's CDF
    # stack stays on the device
    assert c["host.bytes_out"] == 1 + 2 * 17 * 4 + 2
    assert c["host.bytes_in"] >= 32 * 4 + 16 * 4
    assert c["map2d.rows_rebuilt"] == 1 and c["map2d.rows_skipped"] == 0


def test_update_map_counts_skipped_rows():
    img = env_map_2d(16, 32)
    sampler = Map2DSampler(img)
    trace.reset_counters()
    stats = sampler.update_map({3: img[3] * 2.0 + 1.0, 5: img[5], 9: img[9]})
    assert stats["rebuilt_rows"] == 1 and stats["skipped_rows"] == 2
    c = trace.counters()
    assert c["map2d.rows_rebuilt"] == 1 and c["map2d.rows_skipped"] == 2


def test_row_build_phases_are_scoped_in_the_compiled_build():
    from repro.core.forest2d import build_forest_rows

    cdf_rows = jax.vmap(build_cdf)(jnp.stack([_weights(64, s)
                                              for s in range(4)]))
    text = build_forest_rows.lower(cdf_rows, 64).compile().as_text()
    assert {"forest2d.separators", "forest2d.cell_trees",
            "forest2d.depth_guard"} <= scopes_in_hlo(text)


def test_update_skip_key_is_scoped_in_the_compiled_comparison():
    from repro.spatial.map2d import _rows_changed

    sampler = Map2DSampler(env_map_2d(16, 32))
    cls = next(iter(sampler.classes.values()))
    slots = jnp.asarray([1, 4, 7], jnp.int32)
    text = _rows_changed.lower(cls.cdf_rows, slots,
                               cls.cdf_rows[:3]).compile().as_text()
    assert "map2d.skip_key" in scopes_in_hlo(text)
