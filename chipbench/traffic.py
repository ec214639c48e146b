"""The one generator of federated traffic: rows for every worker of every
round, and arrival profiles, from a traffic file's parameters and the
run's seed. The program receives only what this makes.

Traffic file keys (``workloads/<traffic>.json``):

- ``clusters``, ``workers_per_cluster``: the cohort, W = their product.
- ``samples_per_worker`` (images) or ``rows_per_worker`` × ``seq_len``
  (tokens): what one worker trains on in a round.
- ``noise``: image noise level, or the share of random tokens.
- ``pool``: distinct rounds of rows made in set-up; the window cycles
  through them, and the first ``warmup_rounds`` (the rounds the check
  compares) each get rows of their own.
- ``mode``: ``sync`` (one round per dispatch) or ``async`` (arrival
  events, with ``buffer_size`` and an ``arrivals`` group:
  ``straggler_frac``, ``straggler_slowdown``, ``base_speed``,
  ``speed_spread``, ``jitter``, ``failure_prob``).
- ``protocol``: the job's trust and economics settings.
"""
from __future__ import annotations

import numpy as np


def derive(seed: int, tag: int) -> int:
    """A 31-bit seed for one use (``tag``) of the run's seed, which may
    be wider than 32 bits."""
    ss = np.random.SeedSequence(entropy=int(seed), spawn_key=(tag,))
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


WEIGHTS, DATA, TASK, ARRIVALS = range(4)


def num_workers(traffic: dict) -> int:
    return traffic["clusters"] * traffic["workers_per_cluster"]


def _images(config: dict, traffic: dict, rng, templates, W: int) -> dict:
    B = traffic["samples_per_worker"]
    labels = rng.integers(0, config["num_classes"], size=(W, B),
                          dtype=np.int32)
    noise = rng.standard_normal(
        (W, B) + templates.shape[1:], dtype=np.float32)
    noise *= np.float32(traffic["noise"])
    noise += templates[labels]
    return {"images": noise, "labels": labels}


def _tokens(config: dict, traffic: dict, rng, W: int) -> dict:
    """Each silo draws periodic streams (period 3 + w mod 5) over the
    vocabulary, with a ``noise`` share of positions replaced at random."""
    B, S, V = traffic["rows_per_worker"], traffic["seq_len"], \
        config["vocab_size"]
    toks = np.empty((W, B, S), np.int32)
    for w in range(W):
        period = 3 + w % 5
        base = rng.integers(0, V, size=(B, period))
        stream = np.tile(base, (1, -(-S // period)))[:, :S]
        flip = rng.random((B, S)) < traffic["noise"]
        toks[w] = np.where(flip, rng.integers(0, V, size=(B, S)), stream)
    return {"tokens": toks, "labels": toks.copy()}


def rounds(config: dict, traffic: dict, seed: int, n: int) -> list:
    """``n`` rounds of rows, each a dict of (W, rows, ...) host arrays;
    every round's rows are drawn afresh."""
    rng = np.random.default_rng(derive(seed, DATA))
    W = num_workers(traffic)
    if config["inputs"] == "images":
        side, ch = config["image_size"], config["in_channels"]
        templates = rng.standard_normal(
            (config["num_classes"], side, side, ch), dtype=np.float32)
        return [_images(config, traffic, rng, templates, W) for _ in range(n)]
    if config["inputs"] == "tokens":
        return [_tokens(config, traffic, rng, W) for _ in range(n)]
    raise ValueError(f"unknown inputs {config['inputs']!r}")


def arrival_profiles(traffic: dict, seed: int) -> list:
    """(speed, jitter, failure_prob) per worker: a ``straggler_frac``
    share of workers is ``straggler_slowdown`` times slower, and every
    speed is spread uniformly by ``speed_spread``."""
    a = traffic["arrivals"]
    W = num_workers(traffic)
    rng = np.random.default_rng(derive(seed, ARRIVALS))
    slow = np.zeros(W, bool)
    slow[rng.choice(W, size=int(round(W * a["straggler_frac"])),
                    replace=False)] = True
    spread = rng.uniform(1 - a["speed_spread"], 1 + a["speed_spread"], W)
    speed = a["base_speed"] * np.where(slow, a["straggler_slowdown"], 1.0) \
        * spread
    return [(float(s), a["jitter"], a["failure_prob"]) for s in speed]
