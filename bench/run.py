"""Run one benchmark cell once, from the root of a checkout:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result's JSON object; the numbers
compared with the reference, each with its limit, are the last lines of
standard error. Without a TPU (or with fewer chips than the cell asks for)
it exits with code 2 and prints no result.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    ROOT = Path(__file__).resolve().parents[1]
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]  # not bench/ itself
    from bench.harness import main

    sys.exit(main(t_start=T_START, root=ROOT))
