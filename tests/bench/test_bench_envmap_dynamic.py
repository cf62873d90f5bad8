"""The cell ``envmap4k-dynamic`` at a small size on the CPU: a sound run is
correct, and the control (the bfloat16 reference in the program's place), an
altered column and an ``update_map`` that keeps its old state are not; its
manifest entries keep the manifest's form."""
import time

import pytest

from _bench_path import ROOT
from bench.harness import measure
from bench.manifest import Bench, validate

CELL = "envmap4k-dynamic"
SMALL = dict(width=256, height=128, moving_sun_row=40, moving_sun_col0=64,
             band_rows=16, sun_step_px=8, draws_per_step=8192)


def run(seed=2**31 + 33, control=False):
    return measure(Bench(ROOT), CELL, seed, 0.3, False,
                   t_start=time.perf_counter(), chip=False, sizes=SMALL,
                   control=control, log=lambda s: None)


def test_manifest_with_the_cell_is_valid():
    bench = Bench(ROOT)
    assert validate(bench) == []
    assert {m["name"] for m in bench.per_layer(CELL)} == {
        "drain_ms", "idle_share", "map_update_ms", "map_update_roofline",
        "map_update_idle_ms"}
    assert {m["name"] for m in bench.end_to_end(CELL)} == {
        "samples_per_s", "step_p95_ms", "setup_s"}


def test_sound_run_is_correct():
    r = run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"samples_per_s", "step_p95_ms", "setup_s"}


def test_control_is_not_correct():
    r = run(control=True)
    assert not r["correct"], r["checks"]
    assert max(c["value"] / c["limit"] for c in r["checks"].values()) > 3


def test_altered_column_is_not_correct(monkeypatch):
    from repro.spatial import Map2DSampler

    orig = Map2DSampler.sample_map

    def altered(self, pts):
        row, col, u, v = orig(self, pts)
        return row, (col + 1) % int(self.widths[0]), u, v

    monkeypatch.setattr(Map2DSampler, "sample_map", altered)
    r = run()
    assert not r["correct"], r["checks"]


def test_update_that_keeps_its_state_is_not_correct(monkeypatch):
    from repro.spatial import Map2DSampler

    monkeypatch.setattr(Map2DSampler, "update_map",
                        lambda self, rows, **_: {"rebuilt_rows": 0})
    r = run()
    assert not r["correct"], r["checks"]
    assert r["checks"]["col_gap"]["value"] > 3 * r["checks"]["col_gap"]["limit"]
