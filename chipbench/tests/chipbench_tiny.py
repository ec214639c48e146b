"""CPU-sized copies of the benchmark for its tests."""
import json
import pathlib
import shutil
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]

# CPU sizes of each traffic mix and configuration: W = 4, smoke widths
TINY_TRAFFIC = {
    "xdevice-sync-w1024": dict(clusters=2, workers_per_cluster=2,
                               samples_per_worker=8, trace_rounds=2,
                               reference_workers_at_once=2),
    "xdevice-async-w1024": dict(clusters=2, workers_per_cluster=2,
                                samples_per_worker=8, buffer_size=2,
                                trace_rounds=2, reference_workers_at_once=2),
    "xsilo-sync-w4": dict(rows_per_worker=2, seq_len=64, trace_rounds=2),
}
TINY_CONFIG = {
    "smollm-135m": dict(num_hidden_layers=2, hidden_size=288,
                        num_attention_heads=6, num_key_value_heads=2,
                        intermediate_size=512, vocab_size=512),
}


def make_tiny(dest: pathlib.Path) -> pathlib.Path:
    """A checkout's benchmark at CPU sizes under ``dest``; returns the
    path of its BENCHMARK.json."""
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace",
                                                  "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for group, table in (("workloads", TINY_TRAFFIC),
                         ("configs", TINY_CONFIG)):
        for name, sizes in table.items():
            p = dest / "chipbench" / group / f"{name}.json"
            d = json.loads(p.read_text())
            d.update(sizes)
            p.write_text(json.dumps(d))
    return dest / "BENCHMARK.json"


def drive(bench, cell_name, *, seed=4_294_967_311, seconds=1.0,
          trace=False):
    """One run of a cell at CPU size, past the look for a chip; returns
    (result line, log lines)."""
    import jax
    from chipbench import harness
    cell = harness.load_cell(cell_name, bench)
    logs = []
    out = harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                           t_start=time.perf_counter(),
                           devs=jax.devices()[:1], log=logs.append)
    return out, logs


def stage_silo(bench: pathlib.Path) -> str:
    """Add the staged smollm-135m cross-silo cell, which is not in
    BENCHMARK.json yet, to a copy's BENCHMARK.json, with limits on its
    settlement numbers only; returns its name."""
    b = json.loads(bench.read_text())
    name = "smollm135m-silo-w4"
    b["configs"].append({"name": "smollm-135m", "source": "staged",
                         "file": "chipbench/configs/smollm-135m.json",
                         "reduced": [], "why": "staged"})
    b["workloads"].append({"name": name, "config": "smollm-135m",
                           "traffic": "xsilo-sync-w4", "chips": 1,
                           "why": "staged"})
    b["end_to_end"].append({"name": "tokens_per_s", "unit": "tokens/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock", "workloads": [name]})
    for m, unit in (("device_idle.llm", "%"), ("step_mfu.llm", "%"),
                    ("settle_ms.llm", "ms")):
        b["per_layer"].append({"name": m, "unit": unit, "better": "lower",
                               "source": "device_trace", "layer": "staged",
                               "moves": "tokens_per_s", "workloads": [name]})
    bench.write_text(json.dumps(b))
    limits = {k: {"limit": 0} for k in ("unsealed", "chain_invalid",
                                         "penalty_mismatch", "cid_mismatch")}
    (bench.parent / "chipbench" / "limits" / f"{name}.json").write_text(
        json.dumps(limits))
    return name
