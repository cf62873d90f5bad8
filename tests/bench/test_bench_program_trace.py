"""The program's marks in a trace: device time per scope is a union of op
intervals, an idle gap goes to the innermost ``repro.`` span, the scope
path of a CPU op is found through the HLO the trace keeps, and the traced
run of ``bench/program_trace.py`` reads the seven program metrics while the
benchmark's own reduction and readers read what they read without it."""
import copy
import json
import shutil
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _bench_path import ROOT
from bench import scopes
from bench import trace as tracing
from bench.manifest import Bench, validate
from bench.program_trace import PROGRAM_METRICS, profile

SMALL = {
    "w2v3m-neg": dict(vocab_size=20000, guide_cells=20000,
                      draws_per_step=4096),
    "w2v3m-reweight": dict(vocab_size=20000, guide_cells=20000,
                           draws_per_step=4096),
    "envmap4k-frame": dict(width=256, height=128, draws_per_step=8192),
}


def _synthetic():
    tr = tracing.Trace(
        device_ops={"d": [(1.0, 3.0, "while.1"), (1.5, 2.0, "fusion.2"),
                          (2.5, 3.5, "fusion.3"), (4.0, 5.0, "fusion.4"),
                          (5.5, 6.0, "copy.5")]},
        spans=[(0.0, 9.0, "bench.window"), (0.5, 6.2, "bench.drain")])
    marks = scopes.Marks(
        ops={"d": [(1.0, 3.0, "jit(f)/ops.a/while"),
                   (1.5, 2.0, "jit(f)/ops.a/while/body/mul"),
                   (2.5, 3.5, "jit(f)/ops.a/add"),
                   (4.0, 5.0, "jit(f)/vmap(forest.b)/gather"),
                   (5.5, 6.0, "")]},
        spans=[(0.0, 7.0, "repro.outer"), (3.5, 4.0, "repro.inner"),
               (10.0, 11.0, "repro.after")])
    return tr, marks


def test_scope_time_is_a_union_and_gaps_go_to_the_innermost_span():
    tr, marks = _synthetic()
    r = scopes.reduce(tr, marks)
    # ops.a: [1, 3] holds [1.5, 2]; with [2.5, 3.5] the union is 2.5 s,
    # where the sum of the three intervals would be 3.5 s
    assert r["scope_device_s"] == pytest.approx({"ops.a": 2.5, "forest.b": 1.0})
    scoped = dict(r["breakdown"]["device_scopes"])
    assert scoped[scopes.UNSCOPED] == pytest.approx(0.5)
    # busy [1, 3.5], [4, 5], [5.5, 6] in the window [0, 9]
    gaps = dict(r["breakdown"]["program_gaps"])
    assert gaps["repro.inner"] == pytest.approx(0.5)        # 3.5-4
    assert gaps["repro.outer"] == pytest.approx(1.0 + 0.5)  # 0-1, 5-5.5
    assert gaps[scopes.OUTSIDE] == pytest.approx(3.0)       # 6-9, mid 7.5
    assert r["program_gap_s"] == pytest.approx(
        {"repro.inner": 0.5, "repro.outer": 1.5})


def test_the_benchmark_reduction_reads_the_same_beside_the_program_marks():
    tr, marks = _synthetic()
    before = tracing.reduce(tr)
    kept = copy.deepcopy(tr)
    scopes.reduce(tr, marks)
    assert tr == kept and tracing.reduce(tr) == before
    assert before["span_device_s"] == pytest.approx(
        {"bench.window": 4.0, "bench.drain": 4.0})
    assert dict(before["breakdown"]["idle_gaps"]) == pytest.approx(
        {"bench.drain": 2.0, "bench.window": 3.0})


def test_reduce_needs_a_window_and_device_work():
    tr, _ = _synthetic()
    with pytest.raises(ValueError):
        scopes.reduce(tr, scopes.Marks())


def test_a_recorded_cpu_trace_names_its_scopes_through_the_hlo(tmp_path):
    from repro.trace import scope, span

    @jax.jit
    def work(x):
        with scope("ops.sorting"):
            y = jnp.sort(x)
        with scope("forest.looping"):
            y = jax.lax.fori_loop(0, 4, lambda i, c: c * 1.5 + i, y)
        return y

    x = jnp.arange(1 << 18, dtype=jnp.float32)[::-1]
    work(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.drain"):
                with span("repro.unit.dispatch"):
                    y = work(x)
                with span("repro.unit.wait"):
                    y.block_until_ready()
            with span("repro.unit.host"):
                time.sleep(0.05)
    jax.profiler.stop_trace()

    tr = tracing.load(tmp_path, "cpu")
    red = tracing.reduce(tr)
    assert set(red["span_count"]) == {"bench.window", "bench.drain"}
    assert {n for n, _ in red["breakdown"]["idle_gaps"]} <= {
        "bench.window", "bench.drain"}
    marks = scopes.load(tmp_path, "cpu")
    assert {n for *_, n in marks.spans} == {
        "repro.unit.dispatch", "repro.unit.wait", "repro.unit.host"}
    assert marks.ops["/host:CPU"] and len(marks.ops["/host:CPU"]) == len(
        tr.device_ops["/host:CPU"])
    r = scopes.reduce(tr, marks)
    scoped = r["scope_device_s"]
    assert {"ops.sorting", "forest.looping"} <= set(scoped)
    total = sum(scoped.values()) + dict(
        r["breakdown"]["device_scopes"])[scopes.UNSCOPED]
    assert total == pytest.approx(red["busy_s"], rel=1e-9, abs=1e-9)
    # each sleep is a gap; a slow dispatch can move a gap's midpoint into
    # the next repro.unit.dispatch, never out of the program's spans
    assert "repro.unit.host" in r["program_gap_s"]
    assert sum(r["program_gap_s"].values()) >= 3 * 0.05 * 0.9


def test_hlo_op_names_reads_the_trace_metadata(tmp_path):
    from repro.trace import scope

    @jax.jit
    def tagged(x):
        with scope("unit.tag"):
            return x * 3 + 1

    tagged(jnp.ones(64)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    tagged(jnp.ones(64)).block_until_ready()
    jax.profiler.stop_trace()
    (pb,) = tmp_path.rglob("*.xplane.pb")
    names = scopes.hlo_op_names(pb.read_bytes())
    tables = [t for m, t in names.items() if m.startswith("jit_tagged(")]
    assert tables and any("unit.tag" in scopes.scopes_of(p)
                          for p in tables[0].values())


def test_seven_program_metrics_validate_in_the_manifest(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "tests" / "bench").mkdir(parents=True)
    path = tmp_path / "BENCHMARK.json"
    m = json.loads(path.read_text())
    m["per_layer"] += PROGRAM_METRICS
    path.write_text(json.dumps(m))
    assert len(PROGRAM_METRICS) == 7
    assert validate(Bench(tmp_path)) == []


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_program_trace_reads_the_program_metrics(cell, tmp_path, monkeypatch):
    """A small traced run on the CPU: the seven readers read what their
    manifest entries promise in this cell, and the benchmark's six readers
    read from the kept trace exactly what the harness reported."""
    monkeypatch.setattr(Bench, "peaks",
                        lambda self, kind: {"hbm_bytes_per_s": 1e9})
    bench = Bench(ROOT)
    r = profile(bench, cell, 2**31 + 5, 0.3, tmp_path, chip=False,
                sizes=SMALL[cell], log=lambda s: None)
    got = r["program"]["metrics"]
    expected = {m["name"] for m in PROGRAM_METRICS if cell in m["workloads"]}
    assert expected <= set(got)
    steps = None
    if cell == "envmap4k-frame":
        moved = r["program"]["counters"]
        assert moved["host.bytes_in"] == moved["host.bytes_out"]
        steps = moved["host.bytes_in"] // (8 * SMALL[cell]["draws_per_step"])
        assert got["host_mb"]["value"] == pytest.approx(
            16 * SMALL[cell]["draws_per_step"] / 1e6, rel=1e-12)
        assert set(r["program"]["program_gap_s"]) <= {
            "repro.map2d.sample", "repro.map2d.copy_in",
            "repro.map2d.dispatch", "repro.map2d.wait",
            "repro.map2d.copy_out"}
    if cell == "w2v3m-reweight":
        build = sum(got[k]["value"] for k in (
            "separators_ms", "cell_trees_ms", "depth_guard_ms"))
        assert build <= r["metrics"]["build_ms"]["value"] * (1 + 1e-9)
    tr = tracing.load(tmp_path, "cpu")
    red = tracing.reduce(tr)
    assert steps is None or steps == red["span_count"]["bench.step"]
    # the readers that need no work counts; the rooflines read the same
    # span fields over the same work
    ctx = SimpleNamespace(**red, work={}, hbm_bytes_per_s=1e9)
    for name in ("drain_ms", "build_ms", "idle_share"):
        if name in r["metrics"]:
            assert bench.reader(name)(ctx) == r["metrics"][name]["value"]
    assert np.isfinite([v["value"] for v in got.values()]).all()


def test_the_program_host_spans_reach_the_trace(tmp_path):
    """The span names the program writes at its host boundaries, as the
    trace holds them: a rename fails here."""
    import repro.core
    from repro.configs.paper_workloads import env_map_2d
    from repro.kernels import ops
    from repro.spatial import Map2DSampler

    w = jnp.asarray(np.random.default_rng(0).random(256) + 0.01, jnp.float32)
    img = env_map_2d(16, 32)
    sampler = Map2DSampler(img)
    pts = np.random.default_rng(1).random((64, 2)).astype(np.float32)
    jax.profiler.start_trace(str(tmp_path))
    forest = repro.core.build_forest(w, 256)
    ops.forest_sample(forest, jnp.full((8,), 0.5)).block_until_ready()
    sampler.sample_map(pts)
    sampler.update_map({2: img[2] + 1.0})
    jax.profiler.stop_trace()
    names = {n for *_, n in scopes.load(tmp_path, "cpu").spans}
    assert names == {
        "repro.build_forest", "repro.build_cdf",
        "repro.build_forest_from_cdf", "repro.ops.degenerate_read",
        "repro.map2d.sample", "repro.map2d.copy_in", "repro.map2d.dispatch",
        "repro.map2d.wait", "repro.map2d.copy_out", "repro.map2d.update",
        "repro.map2d.cdf_pull", "repro.map2d.fallback_read"}


def test_op_path_finds_a_cached_program_by_its_name():
    names = {"jit_f(5)": {"sort.0": "jit(f)/ops.a/sort"},
             "jit_g(6)": {"sort.0": "jit(g)/ops.b/sort"},
             "jit_h(7)": {"add.1": "jit(h)/ops.c/add"},
             "jit_h(8)": {"add.1": "jit(h)/ops.d/add"}}
    assert scopes.op_path(names, "jit_f(5)", "sort.0") == "jit(f)/ops.a/sort"
    # loaded from the persistent cache: the HLO is kept under another id
    assert scopes.op_path(names, "jit_f(4)", "sort.0") == "jit(f)/ops.a/sort"
    assert scopes.op_path(names, "jit_f(4)", "mul.2") == ""
    # two programs of one name that disagree name no scope
    assert scopes.op_path(names, "jit_h(9)", "add.1") == ""
