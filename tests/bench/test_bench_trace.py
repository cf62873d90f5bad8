"""The reduction from a profiler trace to busy time, idle share and device
time per benchmark span: on synthetic intervals, and on a small trace
recorded from a CPU program."""
import time

import jax
import jax.numpy as jnp
import pytest

from _bench_path import ROOT  # noqa: F401
from bench import trace as tracing


def test_union_overlap_gaps():
    u = tracing.union([(3, 4, "c"), (0, 1, "a"), (0.5, 2, "b")])
    assert u == [(0, 2), (3, 4)]
    assert tracing.measure(u) == 3
    assert tracing.overlap(u, [(1, 3.5)]) == pytest.approx(1.5)
    assert tracing.gaps(u, -1, 5) == [(-1, 0), (2, 3), (4, 5)]
    assert tracing.clip(u, 1, 3.5) == [(1, 2), (3, 3.5)]


def test_reduce_synthetic_trace():
    tr = tracing.Trace(
        device_ops={"d": [(1.0, 2.0, "a"), (1.5, 2.5, "b"), (4.0, 5.0, "a")]},
        spans=[(0.0, 6.0, "bench.window"), (0.5, 3.0, "bench.drain"),
               (3.5, 5.5, "bench.build")])
    r = tracing.reduce(tr)
    assert r["window_s"] == 6.0
    assert r["busy_s"] == pytest.approx(2.5)
    assert r["span_device_s"]["bench.drain"] == pytest.approx(1.5)
    assert r["span_device_s"]["bench.build"] == pytest.approx(1.0)
    assert r["span_count"] == {"bench.window": 1, "bench.drain": 1,
                               "bench.build": 1}
    # each gap goes to the narrowest span holding its midpoint
    idle = dict(r["breakdown"]["idle_gaps"])
    assert idle["bench.drain"] == pytest.approx(1.0)    # 0-1
    assert idle["bench.window"] == pytest.approx(1.5)   # 2.5-4
    assert idle["bench.build"] == pytest.approx(1.0)    # 5-6
    assert dict(r["breakdown"]["device_ops"])["a"] == pytest.approx(2.0)


def test_reduce_needs_a_window_and_device_work():
    with pytest.raises(ValueError):
        tracing.reduce(tracing.Trace(device_ops={"d": [(0, 1, "a")]}))


def test_reduce_a_recorded_cpu_trace(tmp_path):
    @jax.jit
    def work(x):
        return jnp.sort(x) * 2

    x = jnp.arange(1 << 18, dtype=jnp.float32)[::-1]
    work(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.drain"):
                work(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    r = tracing.reduce(tracing.load(tmp_path, "cpu"))
    assert r["span_count"]["bench.drain"] == 3
    assert 0 < r["busy_s"] < r["window_s"]
    drain = r["span_device_s"]["bench.drain"]
    assert 0 < drain <= r["busy_s"] + 1e-9
    idle = dict(r["breakdown"]["idle_gaps"])
    assert idle["bench.window"] >= 3 * 0.02 * 0.9    # the sleeps
    assert len(r["breakdown"]["device_ops"]) <= 10
