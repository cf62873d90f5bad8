"""Every traffic mix and configuration is made from the seed alone: the same
seed gives the same inputs, another seed other inputs."""
import json

import numpy as np
import pytest

from _bench_path import ROOT
from bench import traffic as T
from bench.manifest import Bench

SEEDS = [0, 2**31 + 5, 2**40 + 3]
KEYS = {"draws_per_step", "points", "ring", "kept_steps"}


@pytest.mark.parametrize("path", sorted((ROOT / "bench" / "traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_traffic_file_has_the_generators_keys(path):
    mix = json.loads(path.read_text())
    assert KEYS <= set(mix)
    assert mix["points"] in ("prng", "qmc2d")
    assert mix["ring"] >= 1 and mix["kept_steps"] >= 1


@pytest.mark.parametrize("seed", SEEDS)
def test_uniform_ring_is_deterministic(seed):
    a = T.uniform_ring(seed, 3, 1024)
    b = T.uniform_ring(seed, 3, 1024)
    c = T.uniform_ring(seed + 1, 3, 1024)
    for x, y, z in zip(a, b, c):
        assert np.array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))
        assert 0.0 <= float(x.min()) and float(x.max()) < 1.0
    assert not np.array_equal(np.asarray(a[0]), np.asarray(a[1]))


@pytest.mark.parametrize("seed", SEEDS)
def test_drift_ring_is_deterministic_and_moves_every_row(seed):
    base = np.arange(1, 513, dtype=np.float32)
    a = T.drift_ring(base, seed, 2, 0.1, 0.75)
    b = T.drift_ring(base, seed, 2, 0.1, 0.75)
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
    assert np.all(np.asarray(a[0]) != np.asarray(a[1]))
    flat = T.drift_ring(base, seed, 1, 0.0, 0.75)[0]
    np.testing.assert_allclose(np.asarray(flat), base ** 0.75, rtol=1e-6)


@pytest.mark.parametrize("seed", SEEDS)
def test_qmc2d_ring_is_deterministic(seed):
    a = T.qmc2d_ring(seed, 2, 4096)
    b = T.qmc2d_ring(seed, 2, 4096)
    assert np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], a[1])
    assert a[0].dtype == np.float32 and a[0].min() >= 0 and a[0].max() < 1


def test_qmc2d_is_the_programs_2d_stream_point():
    from repro.core.lds import qmc2_point_np

    c = np.arange(1 << 12, dtype=np.uint32)
    u, v = qmc2_point_np(c, np.uint32(12345), np.uint32(987654))
    pts = T.qmc2d(1 << 12, 12345, 987654)
    assert np.array_equal(pts[:, 0], u) and np.array_equal(pts[:, 1], v)


def test_reservoir_is_seeded():
    def sample(seed):
        r = T.Reservoir(4, seed)
        for s in range(1000):
            r.offer(s)
        return r.items

    assert sample(7) == sample(7)
    assert sample(7) != sample(8)
    assert len(set(sample(7))) == 4


@pytest.mark.parametrize("name,small,per_seed", [
    ("word2vec-googlenews-3m", {"vocab_size": 4096}, True),
    ("envmap-4k", {"width": 128, "height": 64}, False),   # one fixed map
])
def test_config_data_is_deterministic(name, small, per_seed):
    bench = Bench(ROOT)
    cfg = {**bench.config(name), **small}
    data = bench.config_data(name)
    a = np.asarray(data.make(cfg, 2**31 + 5))
    b = np.asarray(data.make(cfg, 2**31 + 5))
    c = np.asarray(data.make(cfg, 6))
    assert np.array_equal(a, b)
    assert np.array_equal(a, c) != per_seed
    assert np.all(a > 0) and np.all(np.isfinite(a))
