"""The program's own host spans in a traced run: the device idle time
that falls inside them, and the durations the rounds' records keep.

The node marks each phase of a round with a ``TraceAnnotation`` named
``sdflb.<phase>`` and keeps the phase's seconds in
``RoundRecord.spans[<name>]``. Spans of the driving thread are events of
``Trace.host``. A program without them (an older one) leaves every reader
built on this module silent, as does a run whose trace saw no device
operation.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from chipbench.tracing import idle_gaps, merged

PREFIX = "sdflb."


def overlap(a: List[Tuple[float, float]],
            b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_inside(trace, names: Iterable[str]) -> Optional[float]:
    """Seconds of the window in which no operation ran on a device while
    the driving thread was inside a span named in ``names``, averaged over
    devices; None where the trace has no device operation or no program
    span."""
    if trace is None or not trace.ops \
            or not any(e.name.startswith(PREFIX) for e in trace.host):
        return None
    names = set(names)
    lo, hi = trace.window
    inside = merged([e for e in trace.host if e.name in names], lo, hi)
    return sum(overlap(idle_gaps(evs, lo, hi), inside)
               for evs in trace.ops.values()) / len(trace.ops)


def record_mean(run, name: str, skip=()) -> Optional[float]:
    """Mean of ``spans[name]`` over the window's settled rounds other
    than those in ``skip``, in seconds; None unless the run's trace saw
    the device and the records carry the span."""
    if run.trace is None or not run.trace.ops:
        return None
    values = [r.spans[name] for r in run.records
              if r.settled and name in (getattr(r, "spans", None) or {})
              and not any(r is s for s in skip)]
    if not values:
        return None
    return sum(values) / len(values)
