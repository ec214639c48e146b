"""Chip benchmark: one cell of BENCHMARK.json in one process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for. The last line of standard output is the result as one
JSON object; the numbers the check compared, each with its limit, are
the last lines of standard error. Without a TPU it exits non-zero and
prints no result.
"""
import pathlib
import sys
import time

T_START = time.perf_counter()        # set-up is timed from here
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

if __name__ == "__main__":
    from chipbench import harness
    sys.exit(harness.main(t_start=T_START))
