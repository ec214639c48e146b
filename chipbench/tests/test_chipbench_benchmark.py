"""BENCHMARK.json against the benchmark's contract, and discovery of a
cell by name from files alone."""
import json
import pathlib
import re

import pytest

from chipbench import harness

ROOT = pathlib.Path(harness.__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.fullmatch(e["name"]), e["name"]
            names.append(e["name"])
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    lines = [e["why"] for e in BENCH["configs"] + BENCH["workloads"]] \
        + [m["layer"] for m in BENCH["per_layer"]] + BENCH["command"]
    assert all(1 <= len(x) <= 200 and not set(x) & {"\t", "\n"}
               for x in lines)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert set(e2e) == {"samples_per_s", "round_latency_p95_s", "setup_s"}
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] == 0.25


def test_every_cell_reports_setup_another_metric_and_a_layer():
    cells = [w["name"] for w in BENCH["workloads"]]
    for name in cells:
        cell = harness.load_cell(name)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer


def test_per_layer_moves_a_metric_each_of_its_cells_reports():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], []).append(m["name"])
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell]), \
                (m["name"], cell)
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist(w):
    cell = harness.load_cell(w["name"])
    assert cell.chips in (1, 4)
    assert set(cell.limits) >= {"unsealed", "penalty_mismatch", "loss_gap"}
    conf = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    assert conf["file"].startswith("chipbench/configs/")
    assert cell.config["reduced"] == conf["reduced"]


def test_a_new_cell_is_found_by_its_files(tmp_path):
    from chipbench_tiny import make_tiny
    bench = make_tiny(tmp_path)
    home = bench.parent / "chipbench"
    mix = json.loads((home / "workloads" / "xdevice-sync-w1024.json")
                     .read_text())
    mix["samples_per_worker"] = 4
    (home / "workloads" / "xdevice-sync-small.json").write_text(
        json.dumps(mix))
    (home / "limits" / "papernet-sync-small.json").write_text(
        (home / "limits" / "papernet-sync-w1024.json").read_text())
    b = json.loads(bench.read_text())
    b["workloads"].append({"name": "papernet-sync-small",
                           "config": "paper-net",
                           "traffic": "xdevice-sync-small", "chips": 1,
                           "why": "a test cell"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "papernet-sync-w1024" in m.get("workloads", []):
            m["workloads"].append("papernet-sync-small")
    bench.write_text(json.dumps(b))
    cell = harness.load_cell("papernet-sync-small", bench)
    assert cell.traffic["samples_per_worker"] == 4
    assert {m["name"] for m in cell.per_layer} >= {"step_mfu.cnn",
                                                   "trust_roofline"}
