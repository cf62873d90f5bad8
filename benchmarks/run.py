"""Benchmark harness: one module per paper table/figure + systems benches.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--json OUT.json]

Prints ``name,...`` CSV lines per benchmark (format per module docstrings).
``--json`` additionally writes a machine-readable record ``{section:
{lines: [...], seconds: float}}`` — ``BENCH_baseline.json`` in the repo root
is one such record, committed so future PRs have a perf trajectory to diff
against (same CSV keys, CPU, --quick).
"""
from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", dest="json_out", metavar="OUT.json", default=None)
    args = ap.parse_args()
    quick, json_out = args.quick, args.json_out
    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import (
        construction,
        convergence,
        pool,
        sampling_throughput,
        serving_diversity,
        spatial,
        table1,
    )

    sections = [
        ("Table 1 (load counts)", table1.main),
        ("Figs 7/9/1 (QMC convergence & discrepancy)",
         (lambda: _convergence_quick()) if quick else convergence.main),
        ("Construction throughput", construction.main),
        ("Pool construction", pool.main_construction),
        ("Sampling throughput", sampling_throughput.main),
        ("Pool sampling", pool.main_sampling),
        ("Pool snapshot", pool.main_snapshot),
        ("Serving best-of-n diversity", serving_diversity.main),
        ("Map2D construction", spatial.main_construction),
        ("Map2D sampling", spatial.main_sampling),
    ]
    record: dict[str, dict] = {}
    for title, fn in sections:
        t0 = time.time()
        print(f"# === {title} ===", flush=True)
        lines = []
        for line in fn():
            print(line, flush=True)
            lines.append(line)
        dt = time.time() - t0
        print(f"# ({dt:.1f}s)", flush=True)
        record[title] = {"lines": lines, "seconds": round(dt, 2)}
    if json_out:
        meta = {"quick": quick}
        with open(json_out, "w") as fh:
            json.dump({"meta": meta, "sections": record}, fh, indent=2)
        print(f"# wrote {json_out}", flush=True)


def _convergence_quick():
    from benchmarks import convergence

    out = []
    for n, e_inv, e_ali in convergence.run_1d(max_log2=14):
        out.append(
            f"fig7_1d,n={n},err_inverse={e_inv:.3e},err_alias={e_ali:.3e},"
            f"ratio={e_ali / max(e_inv, 1e-30):.2f}"
        )
    for n, e_inv, e_ali in convergence.run_2d(max_log2=14, h=64, w=128):
        out.append(
            f"fig9_2d,n={n},err_inverse={e_inv:.3e},err_alias={e_ali:.3e},"
            f"ratio={e_ali / max(e_inv, 1e-30):.2f}"
        )
    d = convergence.run_discrepancy(2048)
    out.append(
        f"fig1_discrepancy,input={d['input']:.4f},inverse={d['inverse']:.4f},"
        f"alias={d['alias']:.4f}"
    )
    return out


if __name__ == "__main__":
    main()
