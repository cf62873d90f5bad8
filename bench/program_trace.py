"""Run one cell traced, as ``bench/run.py --trace 1`` does, and read the
program's own marks from that run's trace:

    python bench/program_trace.py --workload <cell> --seed <n> [--seconds 5] [--out DIR]

The harness reduces a trace to the benchmark's spans and deletes it. Here the
same traced run keeps it (in ``--out``), ``bench.scopes`` reduces it again to
device time per program scope and idle time per ``repro.`` host span, the
program's host counters are counted over the window alone, and the readers of
``bench/metrics/`` named in ``PROGRAM_METRICS`` read both beside the
harness's own fields. The last line of standard output is a JSON object: the
run's result, the scope reduction, the counters and the readings. It compiles
every program anew (see ``main``), so its ``setup_s`` is a cold one.

``PROGRAM_METRICS`` are the manifest entries these readers would take in
``BENCHMARK.json``; the harness does not pass them the fields they read yet.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

T_START = time.perf_counter()

PROGRAM_METRICS = [
    {"name": "descent_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "drain", "moves": "samples_per_s",
     "workloads": ["w2v3m-neg", "envmap4k-frame", "w2v3m-reweight"]},
    {"name": "batched_descent_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "drain", "moves": "samples_per_s",
     "workloads": ["envmap4k-frame"]},
    {"name": "separators_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "build", "moves": "update_p95_ms",
     "workloads": ["w2v3m-reweight"]},
    {"name": "cell_trees_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "build", "moves": "update_p95_ms",
     "workloads": ["w2v3m-reweight"]},
    {"name": "depth_guard_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "build", "moves": "update_p95_ms",
     "workloads": ["w2v3m-reweight"]},
    {"name": "host_gap_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "host API", "moves": "step_p95_ms",
     "workloads": ["envmap4k-frame", "w2v3m-reweight"]},
    {"name": "host_mb", "unit": "MB", "better": "lower",
     "source": "program_counter", "layer": "host API",
     "moves": "samples_per_s", "workloads": ["envmap4k-frame"]},
]


def profile(bench, workload: str, seed: int, seconds: float, out: Path, *,
            chip: bool = True, sizes: dict | None = None, log=print) -> dict:
    """One traced run of ``workload`` through ``bench.harness.measure``,
    its trace kept in ``out``; returns the result with the program's marks."""
    from types import SimpleNamespace
    from unittest import mock

    import jax

    from bench import harness, scopes
    from bench import trace as tracing
    from repro import trace as program

    window = {}
    start_trace, stop_trace = jax.profiler.start_trace, jax.profiler.stop_trace

    def start(*args, **kwargs):
        start_trace(*args, **kwargs)
        program.reset_counters()   # the window opens next

    def stop():
        window["counters"] = program.counters()
        stop_trace()

    out.mkdir(parents=True, exist_ok=True)
    with mock.patch.object(jax.profiler, "start_trace", start), \
            mock.patch.object(jax.profiler, "stop_trace", stop), \
            mock.patch.object(harness.tempfile, "mkdtemp",
                              lambda **_: str(out)), \
            mock.patch.object(harness.shutil, "rmtree",
                              lambda *_, **__: None):
        result = harness.measure(bench, workload, seed, seconds, True,
                                 t_start=T_START, chip=chip, sizes=sizes,
                                 log=log)
    platform = result["device"]["platform"]
    tr = tracing.load(out, platform)
    marks = scopes.reduce(tr, scopes.load(out, platform))
    ctx = SimpleNamespace(**tracing.reduce(tr), counters=window["counters"],
                          scope_device_s=marks["scope_device_s"],
                          program_gap_s=marks["program_gap_s"])
    readings = {}
    for m in PROGRAM_METRICS:
        value = bench.reader(m["name"])(ctx)
        if value is not None:
            readings[m["name"]] = {"value": value, "unit": m["unit"]}
    return {**result, "program": {**marks, "counters": window["counters"],
                                  "metrics": readings}}


def main(argv=None, *, root: Path) -> int:
    import argparse
    import json
    import tempfile

    import jax

    from bench.harness import NoAccelerator
    from bench.manifest import Bench

    # The persistent compilation cache keys a program without its debug
    # information, so a program cached before a scope was added or renamed
    # comes back with its old op_name metadata: compile every program anew.
    jax.config.update("jax_enable_compilation_cache", False)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", type=Path, default=None,
                    help="keep the trace here (default: a new temporary "
                         "directory)")
    args = ap.parse_args(argv)
    out = args.out or Path(tempfile.mkdtemp(prefix="bench_program_trace_"))
    try:
        result = profile(Bench(root), args.workload, args.seed, args.seconds,
                         out, log=lambda s: print(s, flush=True))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"trace_dir={out}", flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]  # not bench/ itself
    sys.exit(main(root=ROOT))
