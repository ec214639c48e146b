"""Reduction of a profiler trace to intervals, and of intervals to the
numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``; ``jax.profiler.ProfileData`` reads it.
As seen on a TPU v5 lite with JAX 0.9: a chip's plane is
``/device:TPU:<n>``. On it the line ``XLA Ops`` holds one event per
operation the chip ran, named by its HLO text (``%fusion.134 = f32[...]
fusion(...)``; a Pallas kernel as ``%trust_agg.1 = ... custom-call(...)``,
after the jitted function that wraps its ``pallas_call``), and ``XLA
Modules`` one event per program execution (``jit_fl_round(<hash>)`` for
the round). ``Async XLA Ops`` holds DMA copies in flight, which overlap
the operations and are not counted as busy. Host threads are lines of
``/host:CPU``; the benchmark's own spans (``WINDOW_SPAN`` around the
traced rounds, ``ROUND_SPAN`` around each call into the program) are
events on the driving thread's line, on the same clock as the device's.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple, Tuple

WINDOW_SPAN = "chipbench.traced_rounds"
ROUND_SPAN = "chipbench.round"
OPS_LINE = "XLA Ops"
# the round program's executions on the ``XLA Modules`` line
ROUND_PROGRAM = r"^jit_fl_round\("
# the trust kernels on the ``XLA Ops`` line: ``trust_score_stats`` and
# ``trust_agg`` (sync), ``fused_async_agg_kernel`` (async)
TRUST_KERNELS = r"^%(trust_score_stats|trust_agg|fused_async_agg_kernel)\b"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    name: str
    start: float      # seconds
    end: float


class Trace(NamedTuple):
    """Events of one traced window; times in seconds on one clock."""
    ops: Dict[int, List[Event]]        # device index -> operations
    modules: Dict[int, List[Event]]    # device index -> program executions
    host: List[Event]                  # host spans of the driving thread
    window: Tuple[float, float]

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(union(evs, *self.window) for evs in self.ops.values()) \
            / len(self.ops)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def module_s(self, pattern: str) -> float:
        """Summed device time of program executions whose name matches
        ``pattern``, averaged over devices."""
        rx = re.compile(pattern)
        if not self.modules:
            return 0.0
        return sum(sum(clip(e, *self.window) for e in evs if rx.search(e.name))
                   for evs in self.modules.values()) / len(self.modules)

    def op_s(self, pattern: str) -> float:
        """Summed device time of operations whose name matches
        ``pattern``, averaged over devices."""
        rx = re.compile(pattern)
        if not self.ops:
            return 0.0
        return sum(sum(clip(e, *self.window) for e in evs if rx.search(e.name))
                   for evs in self.ops.values()) / len(self.ops)

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time, and the longest
        idle gaps named by the innermost host span around them."""
        per: Dict[str, float] = {}
        for evs in self.ops.values():
            for e in evs:
                per[e.name] = per.get(e.name, 0.0) + clip(e, *self.window)
        n = max(len(self.ops), 1)
        ops = sorted(((k, v / n) for k, v in per.items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = []
        for evs in self.ops.values():
            for lo, hi in idle_gaps(evs, *self.window):
                gaps.append((self.host_at((lo + hi) / 2), hi - lo))
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[short(k), v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}

    def host_at(self, t: float) -> str:
        inside = [e for e in self.host if e.start <= t <= e.end]
        if not inside:
            return "host: outside any span"
        return "host: " + min(inside, key=lambda e: e.end - e.start).name


def short(op: str, width: int = 120) -> str:
    """An operation's HLO text without its layouts, cut to ``width``."""
    return re.sub(r"\{[^{}]*\}", "", op)[:width]


def clip(e: Event, lo: float, hi: float) -> float:
    return max(0.0, min(e.end, hi) - max(e.start, lo))


def merged(evs: List[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside [lo, hi], as disjoint
    sorted intervals."""
    out: List[Tuple[float, float]] = []
    for e in sorted(evs, key=lambda e: e.start):
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def union(evs: List[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(evs, lo, hi))


def idle_gaps(evs: List[Event], lo: float, hi: float):
    edges = [lo]
    for a, b in merged(evs, lo, hi):
        edges += [a, b]
    edges.append(hi)
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def load(trace_dir) -> Trace:
    """Read the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    window = None
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            evs = [Event(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                   for e in line.events]
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))] = evs
            elif m and line.name == MODULES_LINE:
                modules[int(m.group(1))] = evs
            elif plane.name.startswith("/host:"):
                for e in evs:
                    if e.name == WINDOW_SPAN:
                        window = (e.start, e.end)
                if any(e.name == ROUND_SPAN for e in evs):
                    host = evs
    if window is None:
        raise ValueError(f"trace under {trace_dir} has no {WINDOW_SPAN} span")
    return Trace(ops, modules, host, window)
