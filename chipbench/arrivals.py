"""Arrival cohorts of an event-driven job, simulated from the arrival
profiles and the task's seed, independently of the program.

The semantics (and the order in which random numbers are drawn) follow
the job's arrival model: every worker starts a local round at time 0
that lasts ``speed × lognormal(0, jitter)``; when it ends, its update is
lost with probability ``failure_prob`` and otherwise joins the pending
buffer (once per event), and the worker starts its next round at once.
An aggregation event waits until ``buffer_size`` distinct workers are
pending (or ``max_wait`` simulated seconds pass) and takes them as its
cohort. Ties in time break on the round, then the worker. The random
stream is seeded from ``(seed, first 8 bytes of sha256(task_id))``.
"""
from __future__ import annotations

import hashlib
import heapq

import numpy as np


def cohorts(profiles: list, *, seed: int, task_id: str, buffer_size: int,
            events: int, max_wait: float = float("inf")) -> list:
    """The (W,) 0/1 arrival mask of each of the first ``events`` events.
    ``profiles``: (speed, jitter, failure_prob) per worker."""
    key = int.from_bytes(hashlib.sha256(task_id.encode()).digest()[:8], "big")
    rng = np.random.default_rng((seed, key))
    W = len(profiles)
    now = 0.0
    heap: list = []

    def start(w: int, rnd: int):
        speed, jitter, _ = profiles[w]
        heapq.heappush(heap, (now + speed * float(rng.lognormal(0.0, jitter)),
                              rnd, w))

    for w in range(W):
        start(w, 0)
    out = []
    need = min(buffer_size, W)
    for _ in range(events):
        pending = np.zeros(W, bool)
        count = 0
        deadline = now + max_wait
        while count < need and heap and heap[0][0] <= deadline:
            t, rnd, w = heapq.heappop(heap)
            now = t
            lost = rng.random() < profiles[w][2]
            if not lost and not pending[w]:
                pending[w] = True
                count += 1
            start(w, rnd + 1)
        if count < need and np.isfinite(deadline):
            now = max(now, deadline)
        out.append(pending.astype(np.int64))
    return out
