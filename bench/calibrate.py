"""Readings that limits are set from, at a cell's own size, in one process:

    python bench/calibrate.py --workload <cell> --seeds 1-12 --control-seeds 101-103

For each program seed and each control seed (the bfloat16 reference in the
program's place) it runs the cell's closed loop for ``--seconds`` and the
comparison with the reference, and prints one JSON line of readings; the
last line holds, per compared number, the largest program reading (the
lower end of its limit) and the smallest control reading (the upper end).
Needs the chip, like ``bench/run.py``.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += range(int(lo), int(hi or lo) + 1)
    return out


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    from bench.harness import NoAccelerator, measure
    from bench.manifest import Bench

    bench = Bench(ROOT)
    worst, best = {}, {}
    try:
        for control, seeds in ((False, args.seeds), (True, args.control_seeds)):
            for seed in seeds:
                r = measure(bench, args.workload, seed, args.seconds, False,
                            t_start=time.perf_counter(), control=control,
                            log=lambda s: None)
                readings = {k: c["value"] for k, c in r["checks"].items()}
                print(json.dumps({"seed": seed, "control": control,
                                  "correct": r["correct"],
                                  "attempted": r["attempted"],
                                  "readings": readings}), flush=True)
                for k, v in readings.items():
                    if control:
                        best[k] = min(best.get(k, v), v)
                    else:
                        worst[k] = max(worst.get(k, v), v)
    except NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"program_max": worst, "control_min": best}))
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
