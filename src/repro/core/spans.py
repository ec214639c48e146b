"""Host spans of the node's hot path, on the profiler's clock.

``span(name, into)`` marks one phase of a tick or of a settlement: it
enters a ``jax.profiler.TraceAnnotation`` (so a profiler trace shows the
phase on the calling thread's line, on the same clock as the device's
operations) and adds the phase's host seconds to ``into[name]`` when a
dict is given. With no profiler running the annotation does nothing, so
spans are always on. ``RoundRecord.spans`` holds a round's durations;
``settle_time`` and ``chain_time`` are read off the same spans.

Names, in order of a round's life:

- ``sdflb.base_init``: building a model's frozen base on the device, once,
  when its task is created (set-up, before any round)
- ``sdflb.batch_h2d``: the batch leaves' hand-over to the device (driving
  thread; the copy itself completes asynchronously, after the span)
- ``sdflb.handoff``: the tick's queue hand-off to the settler (driving)
- ``sdflb.head_wait``: the wait for the block that settled round r-1
- ``sdflb.score_sync``: the device-to-host sync of scores, weights, losses
- ``sdflb.settle``: one round's settlement, IPFS put to record (settler)
- ``sdflb.ipfs_put``: the IPFS put of the round's params (settler)
- ``sdflb.gc``: a garbage collection, on whichever thread triggered it

``sdflb.settle_queue`` is a counter, not a span: the seconds a finished
round waited before the settler started on it. The model's own device
counters (``moe.pairs_held``, ``moe.max_expert_load``) are in
``RoundRecord.counters``; named scopes inside the jitted round (``mla``,
``moe_route``, ``moe_experts``, ``shared_experts``, ``lora``) mark its
operations' metadata.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, Optional

import jax


class span:
    """Context manager marking one host phase; see the module docstring.
    ``t0`` (``time.perf_counter``) is the phase's start once entered.
    ``close()`` ends a span entered by hand, for a phase that does not
    sit in one block."""

    __slots__ = ("name", "into", "t0", "_annotation")

    def __init__(self, name: str,
                 into: Optional[Dict[str, float]] = None) -> None:
        self.name = name
        self.into = into
        self.t0 = 0.0
        self._annotation = None

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        self._annotation.__exit__(*exc)
        if self.into is not None:
            self.into[self.name] = self.into.get(self.name, 0.0) + dt

    def close(self) -> None:
        self.__exit__(None, None, None)


_gc_annotation = None


def _gc_hook(phase: str, info: dict) -> None:
    # CPython runs one collection at a time, so one slot suffices
    global _gc_annotation
    if phase == "start":
        _gc_annotation = jax.profiler.TraceAnnotation("sdflb.gc")
        _gc_annotation.__enter__()
    elif _gc_annotation is not None:
        _gc_annotation.__exit__(None, None, None)
        _gc_annotation = None


def trace_gc() -> None:
    """Mark every garbage collection as a ``sdflb.gc`` span; idempotent,
    one hook per process."""
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)
