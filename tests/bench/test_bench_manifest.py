"""BENCHMARK.json's form, and that a configuration, a traffic mix, a
per-layer metric and a cell are added by new files and manifest entries
alone."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from _bench_path import ROOT
from bench.harness import measure
from bench.manifest import Bench, validate

SMALL_W2V = dict(vocab_size=4096, guide_cells=4096, draws_per_step=2048)


def _copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "tests" / "bench").mkdir(parents=True)
    return tmp_path


def _edit(root, fn):
    path = root / "BENCHMARK.json"
    m = json.loads(path.read_text())
    fn(m)
    path.write_text(json.dumps(m))
    return Bench(root)


def test_manifest_is_valid():
    assert validate(Bench(ROOT)) == []


@pytest.mark.parametrize("kind,field,value", [
    ("workloads", "name", "w2v 3m"),           # space
    ("workloads", "name", "w2v/3m"),           # slash
    ("configs", "name", "-leading-dash"),
    ("end_to_end", "unit", "samples per s"),   # space in a unit
    ("per_layer", "unit", "µs"),          # not a to z
    ("per_layer", "name", "x" * 65),           # too long
])
def test_bad_names_and_units_are_refused(tmp_path, kind, field, value):
    bench = _edit(_copy(tmp_path), lambda m: m[kind][0].__setitem__(field, value))
    assert validate(bench)


def test_moves_must_be_reported_in_each_listed_cell(tmp_path):
    def move_build_to_all_cells(m):
        build = next(p for p in m["per_layer"] if p["name"] == "build_ms")
        build["workloads"] = [w["name"] for w in m["workloads"]]
    errs = validate(_edit(_copy(tmp_path), move_build_to_all_cells))
    assert any("does not report 'update_p95_ms'" in e for e in errs)


def test_unknown_moves_is_refused(tmp_path):
    bench = _edit(_copy(tmp_path),
                  lambda m: m["per_layer"][0].__setitem__("moves", "mfu"))
    assert any("moves" in e for e in validate(bench))


def test_new_config_traffic_metric_and_cell_need_only_new_files(tmp_path):
    """A later change adds a smaller vocabulary, a mix with bigger steps, a
    metric and the cell that uses them: new files and manifest entries
    only, and the harness finds each by its name."""
    root = _copy(tmp_path)
    cfgs = root / "bench" / "configs"
    small = json.loads((cfgs / "word2vec-googlenews-3m.json").read_text())
    small.update(name="w2v-small", vocab_size=8192, guide_cells=8192)
    (cfgs / "w2v-small.json").write_text(json.dumps(small))
    shutil.copy(cfgs / "word2vec-googlenews-3m.py", cfgs / "w2v-small.py")
    mix = json.loads((root / "bench/traffic/negatives.json").read_text())
    mix.update(draws_per_step=4096)
    (root / "bench/traffic/big-steps.json").write_text(json.dumps(mix))
    (root / "bench/metrics/drain_calls.py").write_text(
        "def read(ctx):\n    return float(ctx.span_count.get('bench.drain', 0))"
        " or None\n")
    shutil.copy(root / "bench/limits/w2v3m-neg.json",
                root / "bench/limits/small-big.json")

    def add(m):
        m["configs"].append({"name": "w2v-small", "source": "https://example.org",
                             "file": "bench/configs/w2v-small.json",
                             "reduced": ["vocab_size", "guide_cells"],
                             "why": "test"})
        m["workloads"].append({"name": "small-big", "config": "w2v-small",
                               "traffic": "big-steps", "chips": 1,
                               "why": "test"})
        m["per_layer"].append({"name": "drain_calls", "unit": "calls",
                               "better": "higher", "source": "device_trace",
                               "layer": "drain", "moves": "samples_per_s",
                               "workloads": ["small-big"]})
        for p in m["per_layer"]:
            if p["name"] == "idle_share":
                p["workloads"].append("small-big")
    bench = _edit(root, add)
    assert validate(bench) == []
    assert bench.config("w2v-small")["vocab_size"] == 8192
    assert bench.traffic("big-steps")["draws_per_step"] == 4096
    bench.peaks = lambda kind: {"hbm_bytes_per_s": 1e11}
    r = measure(bench, "small-big", 2**31 + 11, 0.3, True,
                t_start=time.perf_counter(), chip=False, log=lambda s: None)
    assert r["correct"]
    assert set(r["metrics"]) == {"idle_share", "drain_calls"}
    assert r["metrics"]["drain_calls"]["value"] >= 1


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "w2v3m-neg", "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_run_refuses_the_cpu_and_prints_no_result():
    p = _run(ROOT, {"PYTHONPATH": "src"})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p.stdout)


def test_run_fails_in_a_checkout_without_the_program(tmp_path):
    p = _run(_copy(tmp_path), {"PYTHONPATH": "src"})
    assert p.returncode != 0
    _no_result(p.stdout)
