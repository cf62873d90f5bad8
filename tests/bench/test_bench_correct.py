"""``correct`` decides by the reference: each cell at a small size on the
CPU is correct as the program stands, and not correct with the control
(the bfloat16 reference in the program's place) or with the timed path
broken underneath: an answer altered where it is produced, half of the
batch left out, or an update that returns its state unchanged."""
import time

import numpy as np
import pytest

from _bench_path import ROOT
from bench.harness import measure
from bench.manifest import Bench

SMALL = {
    "w2v3m-neg": dict(vocab_size=20000, guide_cells=20000,
                      draws_per_step=4096),
    "w2v3m-reweight": dict(vocab_size=20000, guide_cells=20000,
                           draws_per_step=4096),
    "envmap4k-frame": dict(width=256, height=128, draws_per_step=8192),
}
CELLS = sorted(SMALL)


def run(cell, seed=2**31 + 21, control=False):
    return measure(Bench(ROOT), cell, seed, 0.3, False,
                   t_start=time.perf_counter(), chip=False,
                   sizes=SMALL[cell], control=control, log=lambda s: None)


def _altered_1d(monkeypatch, fault):
    from bench.systems import forest1d
    from repro.kernels import ops

    orig = ops.forest_sample

    def broken(forest, xi, *a, **k):
        idx = orig(forest, xi, *a, **k)
        return fault(idx, forest.n)

    monkeypatch.setattr(ops, "forest_sample", broken)
    forest1d.bench_drain.clear_cache()   # the drain traces ops anew
    yield
    forest1d.bench_drain.clear_cache()


def _altered_2d(monkeypatch, fault):
    from repro.spatial import Map2DSampler

    orig = Map2DSampler.sample_map

    def broken(self, pts):
        row, col, u, v = orig(self, pts)
        return row, fault(col, int(self.widths[0])), u, v

    monkeypatch.setattr(Map2DSampler, "sample_map", broken)
    yield


def answer_altered(idx, n):
    return (idx + 1) % n


def half_left_out(idx, n):
    half = idx.shape[0] // 2
    if isinstance(idx, np.ndarray):
        idx = idx.copy()
        idx[half:] = 0
        return idx
    return idx.at[half:].set(0)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = run(cell, control=True)
    assert not r["correct"], r["checks"]
    assert max(c["value"] / c["limit"] for c in r["checks"].values()) > 3


@pytest.mark.parametrize("fault", [answer_altered, half_left_out],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    broken = _altered_1d if cell.startswith("w2v") else _altered_2d
    for _ in broken(monkeypatch, fault):
        r = run(cell)
        assert not r["correct"], r["checks"]


def test_update_that_keeps_its_state_is_not_correct(monkeypatch):
    import repro.core

    orig = repro.core.build_forest
    first = []

    def stale(weights, m, *a, **k):
        if not first:
            first.append(orig(weights, m, *a, **k))
        return first[0]

    monkeypatch.setattr(repro.core, "build_forest", stale)
    r = run("w2v3m-reweight")
    assert not r["correct"], r["checks"]
