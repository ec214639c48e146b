"""Runs one cell of ``BENCHMARK.json``: set-up, the measured window, the
check that decides ``correct``, and the result line.

Everything that belongs to one cell is found by name: the configuration
(``configs/<config>.json`` with its reference ``configs/<config>.py``),
the traffic mix (``workloads/<traffic>.json``), the limits of the check
(``limits/<cell>.json``) and one reader per per-layer metric
(``metrics/<metric>.py``). Adding a cell adds files; no code changes.

A run, in one process:

1. set-up: the program's task is built, its weights replaced by ones made
   on the device from the seed, the rows of every round made on the host,
   and the first ``warmup_rounds`` rounds run through the same entry point
   as the window (they compile the round and fill the settler pipeline);
2. the window: whole rounds back to back, a closed loop, until
   ``seconds`` have passed; then the settler is flushed;
3. the check: settlement of every round against the rounds' scores, and
   the warm-up rounds against the plain reference (``reference.py``), run
   after the program's state is freed.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import pathlib
import shutil
import statistics
import sys
import time
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from chipbench import checks, traffic as gen, tracing

HERE = pathlib.Path(__file__).resolve().parent


def load_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    model: Any            # the configuration's reference module
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    home: pathlib.Path    # the benchmark's directory


def load_cell(name: str, bench_path=None) -> Cell:
    """The cell ``name`` of the ``BENCHMARK.json`` at ``bench_path`` (the
    checkout's, by default), with every file it names."""
    bench_path = pathlib.Path(bench_path or HERE.parent / "BENCHMARK.json")
    root = bench_path.parent
    bench = load_json(bench_path)
    home = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_file = root / conf["file"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=w["chips"], config=load_json(cfg_file),
                model=load_module(cfg_file.with_suffix(".py")),
                traffic=load_json(home / "workloads" / f"{w['traffic']}.json"),
                limits=load_json(home / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, home=home)


def require_chips(chips: int):
    """The devices the cell asks for, or SystemExit: a run measures a TPU
    or nothing."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"chipbench: needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


# -- the program under test ----------------------------------------------------


class Program:
    """The program's one-task protocol, driven through its user entry
    points, with weights from the seed and a seal clock."""

    def __init__(self, cell: Cell, seed: int):
        import jax
        from repro.configs.base import (FederationConfig, ModelConfig,
                                        TrainConfig)
        from repro.core.async_sim import WorkerProfile
        from repro.core.protocol import SDFLBProtocol

        t = cell.traffic
        kw = cell.model.program(cell.config)
        self.sync = t["mode"] == "sync"
        fed = FederationConfig(
            num_clusters=t["clusters"],
            workers_per_cluster=t["workers_per_cluster"],
            async_mode=not self.sync, buffer_size=t.get("buffer_size", 8),
            **t["protocol"])
        profiles = None if self.sync else [
            WorkerProfile(speed=s, jitter=j, failure_prob=f)
            for s, j, f in gen.arrival_profiles(t, seed)]
        self.proto = SDFLBProtocol(
            ModelConfig(**kw["model"]), fed, TrainConfig(**kw["train"]),
            use_blockchain=True, seed=gen.derive(seed, gen.TASK),
            arrival_profiles=profiles)
        self.task = self.proto.task
        key = jax.random.PRNGKey(gen.derive(seed, gen.WEIGHTS))
        weights = jax.jit(lambda k: cell.model.init(cell.config, k))(key)
        have = jax.tree.map(lambda x: (x.shape, x.dtype),
                            self.task.global_params)
        made = jax.tree.map(lambda x: (x.shape, x.dtype), weights)
        if have != made:
            raise ValueError(f"configuration's weights {made} do not match "
                             f"the program's params {have}")
        self.task.global_params = weights
        self.seals: List[float] = []
        self.proto.node.add_seal_listener(
            lambda blk, commit: self.seals.append(time.perf_counter()))
        self.total0 = self.task.contract.total_value()
        # the matmul precision the configuration states, where it states
        # one; every call into the program runs under it
        self.precision = cell.config.get("matmul_precision")

    def round(self, rows: dict):
        """One round through the user entry point: ``run_round`` (a node
        tick) or, event-driven, ``run_events`` for one event."""
        import jax
        with jax.default_matmul_precision(self.precision):
            if self.sync:
                return self.proto.run_round(rows)
            recs = self.proto.run_events(lambda r: rows, events=1)
        return recs[0] if recs else None


def _samples(rec, traffic: dict, per_worker: int) -> int:
    """Samples (or tokens) whose update entered the aggregate."""
    if rec.participation is None:
        return gen.num_workers(traffic) * per_worker
    return int(np.sum(np.asarray(rec.participation) > 0)) * per_worker


def p95(xs: List[float]) -> float:
    """95th percentile, linear between closest ranks."""
    return float(np.percentile(np.asarray(xs, np.float64), 95))


class CompileCounter:
    """Counts the programs XLA builds while active: a compilation, or a
    load from the persistent cache; in the window there should be none."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and name.endswith("backend_compile_duration"):
            self.n += 1


class Run(NamedTuple):
    """What a per-layer metric's reader may read."""
    cell: Cell
    records: list          # the window's RoundRecords
    trace: Optional[tracing.Trace]
    traced_rounds: list    # RoundRecords of the traced rounds
    peaks: dict
    flops_per_sample: int
    samples_per_worker: int
    update_bytes: int      # one round's (W, D) update matrix, in bytes
    dim: int               # D, the model's parameter count

    def samples(self, recs) -> int:
        """Samples (or tokens) whose update entered the aggregate."""
        return sum(_samples(r, self.cell.traffic, self.samples_per_worker)
                   for r in recs)


def read_metric(cell: Cell, name: str, run: Run):
    reader = load_module(cell.home / "metrics" / f"{name}.py")
    return reader.read(run)


def device_record(devs, trace: Optional[tracing.Trace]) -> dict:
    d = devs[0]
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devs)
    out = {"platform": d.platform, "kind": d.device_kind, "count": len(devs),
           "memory_peak_bytes": int(peak)}
    if trace is not None:
        out["busy_s"] = trace.busy_s()
        out["window_s"] = trace.window_s()
    return out


def peaks_for(kind: str, home: pathlib.Path) -> dict:
    table = load_json(home / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class Warm(NamedTuple):
    """A program after its first rounds, with what the check keeps of
    them."""
    prog: Program
    rows: list             # every round's rows; the first are the warm-up's
    records: list          # the warm-up rounds' RoundRecords
    dispatched: list       # their dispatch times
    grad_norms: dict       # first gradient's leaf norms, from the program
    params: Any            # global params after the warm-up rounds


def warm_up(cell: Cell, seed: int) -> Warm:
    """Build the program and run its first ``warmup_rounds`` rounds
    through the window's own entry point, on rows of their own. They
    compile the round and fill the settler pipeline, and are the rounds
    the check compares with the reference."""
    import jax
    t = cell.traffic
    warm = t["warmup_rounds"]
    rows = gen.rounds(cell.config, t, seed, max(t["pool"], warm))
    prog = Program(cell, seed)
    norms = jax.jit(checks.first_grad_norms, static_argnums=1)
    recs, dispatched, grad_norms = [], [], None
    for r in range(warm):
        dispatched.append(time.perf_counter())
        recs.append(prog.round(rows[r]))
        if r == 0:
            grad_norms = jax.device_get(norms(
                prog.task.opt_state, checks.grad_spec(cell.config)))
    return Warm(prog, rows, recs, dispatched, grad_norms,
                prog.task.global_params)


def program_out(w: Warm) -> checks.ProgramOut:
    import jax
    return checks.ProgramOut(
        losses=np.stack([np.asarray(r.losses) for r in w.records]),
        scores=np.stack([np.asarray(r.scores) for r in w.records]),
        grad_norms={k: float(v) for k, v in w.grad_norms.items()},
        params=jax.device_get(w.params))


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, devs, log=print) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax

    t = cell.traffic
    W = gen.num_workers(t)
    per_worker = cell.model.samples_per_worker(t)
    w = warm_up(cell, seed)
    prog, task, rows = w.prog, w.prog.task, w.rows
    warm = len(w.records)
    if trace:
        with jax.default_matmul_precision(prog.precision):
            lowered = task.lower_round(rows[0],
                                       None if prog.sync else np.ones(W))
        log("compiled round memory_analysis: "
            + str(lowered.compile().memory_analysis()))
    counter = CompileCounter()

    # the window: whole rounds back to back; with ``trace`` the first
    # ``trace_rounds`` of them run under the profiler
    trace_dir = cell.home / ".trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    traced_n = t["trace_rounds"] if trace else 0
    records, dispatched = [], list(w.dispatched)
    raised = 0
    counter.on = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    tracing_on = traced_n > 0
    if tracing_on:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        span = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
        span.__enter__()

    def stop_tracing():
        jax.block_until_ready(task.global_params)
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    while True:
        i = len(records)
        if tracing_on and i == traced_n:
            stop_tracing()
            tracing_on = False
        if time.perf_counter() - t0 >= seconds and i > traced_n:
            break
        dispatched.append(time.perf_counter())
        try:
            with jax.profiler.TraceAnnotation(tracing.ROUND_SPAN):
                rec = prog.round(rows[(warm + i) % len(rows)])
        except Exception as e:               # TaskSettlementError and kin
            log(f"round {i} of the window failed: {e!r}")
            raised = 1
            dispatched.pop()
            break
        if rec is None:                      # an event with no arrivals
            dispatched.pop()
            continue
        records.append(rec)
    if tracing_on:
        stop_tracing()
    try:
        prog.proto.flush()
    except Exception as e:
        log(f"flush failed: {e!r}")
        raised = 1
    counter.on = False
    sealed = prog.seals
    unsealed = warm + len(records) - len(sealed)
    tr = None
    if traced_n:
        tr = tracing.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics: Dict[str, dict] = {}
    win_disp = dispatched[warm:]
    win_seal = sealed[warm:warm + len(records)]
    run = Run(cell=cell, records=records, trace=tr,
              traced_rounds=records[:traced_n],
              peaks=peaks_for(devs[0].device_kind, cell.home)
              if devs[0].platform == "tpu" else {},
              flops_per_sample=cell.model.flops_per_sample(cell.config, t),
              samples_per_worker=per_worker,
              update_bytes=checks.update_bytes(w.params, W),
              dim=checks.dim(w.params))
    if trace:
        log(f"compilations in the window: {counter.n}")
        for m in cell.per_layer:
            v = read_metric(cell, m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        latencies = [b - a for a, b in zip(win_disp, win_seal)]
        span_s = win_seal[-1] - win_disp[0] if win_seal else float("nan")
        done = run.samples(records)
        values = {"setup_s": setup_s, "samples_per_s": done / span_s,
                  "tokens_per_s": done / span_s,
                  "round_latency_p95_s": p95(latencies) if latencies
                  else float("nan")}
        log(f"window: {len(records)} rounds, {done} samples or tokens in "
            f"{span_s:.4f} s from the first dispatch to the last seal; "
            f"{len(latencies)} round latencies, median "
            f"{statistics.median(latencies) if latencies else 0:.4f} s; "
            f"compilations in the window: {counter.n}")
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    dev = device_record(devs, tr)

    # the check: settlement of every round, then the warm-up rounds
    # against the reference once the program's state is freed
    numbers = checks.settlement(
        prog, w.records + records, unsealed, w.params, warm,
        checks.expected_masks(cell, seed, warm + len(records)))
    prog.proto.node.close()
    out = program_out(w)
    del prog, task, w, run
    gc.collect()
    numbers.update(checks.against_reference(cell, seed, rows[:warm], out))
    verdict = checks.judge(numbers, cell.limits)
    failed = raised + max(0, len(records) - len(win_seal))
    result = {"correct": verdict["correct"] and failed == 0,
              "attempted": len(records) + raised, "failed": failed,
              "metrics": metrics, "device": dev}
    if tr is not None:
        result["breakdown"] = tr.breakdown()
    result["checks"] = verdict["checks"]
    return result


def main(argv=None, t_start=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(a.workload)
    devs = require_chips(cell.chips)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, is kept: the second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    out = run_cell(cell, seed=a.seed, seconds=a.seconds, trace=bool(a.trace),
                   t_start=t_start, devs=devs, log=log)
    for name, (value, limit) in out["checks"].items():
        log(f"check {name}: {value!r} limit {limit!r}")
    print(json.dumps(out), flush=True)
    return 0
