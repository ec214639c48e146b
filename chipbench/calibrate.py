"""Readings that the limits of a cell's check are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1-12 --control-seeds 1-3

For each seed of ``--seeds``: the program's first rounds through the
timed path, exactly as a run's set-up makes them, then the settlement
and training numbers against the reference (the sound readings; their
largest is a number's lower reading). For each seed of
``--control-seeds`` also: the reference against itself computed in the
nearest precision below the configuration's (the control), and against
the reference with a fault planted (each worker's loss over half of its
rows; worker 0's loss and score altered where they are produced). A
step that returns its state unchanged reads 1 on ``delta_gap`` by that
number's definition and is printed without a run. One JSON line per
reading on standard output. A benchmark run does not run this.
"""
from __future__ import annotations

import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

def seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="chipbench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--bench", default=None,
                    help="BENCHMARK.json to read the cell from (default: "
                         "the checkout's)")
    a = ap.parse_args(argv)
    from chipbench import checks, harness
    cell = harness.load_cell(a.workload, a.bench)
    harness.require_chips(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    ctl = checks.control(cell.config)
    control = set(seeds(a.control_seeds)) if a.control_seeds else set()

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    for seed in seeds(a.seeds):
        t0 = time.perf_counter()
        w = harness.warm_up(cell, seed)
        w.prog.proto.flush()
        numbers = checks.settlement(
            w.prog, w.records, len(w.records) - len(w.prog.seals), w.params,
            len(w.records), checks.expected_masks(cell, seed, len(w.records)))
        w.prog.proto.node.close()
        rows, out = w.rows[:len(w.records)], harness.program_out(w)
        del w
        gc.collect()
        numbers.update(checks.against_reference(cell, seed, rows, out))
        emit(workload=cell.name, seed=seed, kind="program", numbers=numbers,
             seconds=time.perf_counter() - t0)
        if seed not in control:
            continue
        init = checks.init_params(cell, seed)
        masks = checks.expected_masks(cell, seed, len(rows))
        ref = checks.run_reference(cell, seed, rows, masks)
        name = "control:" + ",".join(f"{k}={v}" for k, v in ctl.items())
        for kind, kw in ((name, ctl),
                         ("fault:half_batch", {"fault": "half"}),
                         ("fault:answer_altered", {"fault": "altered"})):
            t0 = time.perf_counter()
            got = checks.run_reference(cell, seed, rows, masks, **kw)
            emit(workload=cell.name, seed=seed, kind=kind,
                 numbers=checks.compare(ref, got, init),
                 seconds=time.perf_counter() - t0)
        emit(workload=cell.name, seed=seed, kind="fault:state_unchanged",
             numbers=checks.compare(ref, ref._replace(params=init), init))
    return 0


if __name__ == "__main__":
    sys.exit(main())
