"""End-to-end SDFL-B training driver.

Two modes:
  * ``--arch paper-net`` — the paper's own experiment: MNIST-surrogate CNN,
    SGD(lr=0.01, momentum=0.5), N workers in clusters, blockchain on/off.
  * any assigned LLM arch — federated LM training on synthetic token
    streams using the *smoke-size* variant by default (CPU container), or
    the full config with ``--full`` (expects a real TPU mesh).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch paper-net \
      --workers 8 --clusters 2 --rounds 50 [--no-blockchain] [--async]
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --rounds 5
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.configs.base import FederationConfig, TrainConfig
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.core import async_sim
from repro.core.protocol import SDFLBProtocol
from repro.data.datasets import make_federated_mnist, synthetic_tokens
from repro.launch.compile_cache import enable_compile_cache


def build_protocol(args):
    fed = FederationConfig(
        num_clusters=args.clusters,
        workers_per_cluster=args.workers // args.clusters,
        async_mode=args.async_mode,
        trust_threshold=args.trust_threshold,
        mode="head_gather" if args.head_gather else "allreduce")
    if args.arch == "paper-net":
        cfg = get_config("paper-net")
        tc = TrainConfig(optimizer="sgd", lr=0.01, momentum=0.5, remat=False)
    else:
        cfg = (get_config(args.arch) if args.full
               else get_smoke_config(args.arch))
        tc = TrainConfig(optimizer="adamw", lr=3e-4, remat=args.full,
                         grad_clip=1.0)
    proto = SDFLBProtocol(cfg, fed, tc, use_blockchain=not args.no_blockchain,
                          seed=args.seed)
    return proto, cfg, fed, tc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-net",
                    choices=ARCH_IDS + ["paper-net"])
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--clusters", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--samples", type=int, default=4096)
    ap.add_argument("--no-blockchain", action="store_true")
    ap.add_argument("--async", dest="async_mode", action="store_true")
    ap.add_argument("--head-gather", action="store_true")
    ap.add_argument("--trust-threshold", type=float, default=0.3)
    ap.add_argument("--non-iid", type=float, default=0.0)
    ap.add_argument("--full", action="store_true",
                    help="full-size arch config (TPU mesh expected)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="")
    args = ap.parse_args()
    assert args.workers % args.clusters == 0
    enable_compile_cache()

    proto, cfg, fed, tc = build_protocol(args)
    W = args.workers

    scheduler = None
    if args.async_mode:
        scheduler = async_sim.AsyncScheduler(
            async_sim.heterogeneous_profiles(W, seed=args.seed),
            seed=args.seed, buffer_size=max(2, W // 2))

    if args.arch == "paper-net":
        ds = make_federated_mnist(W, samples=args.samples,
                                  non_iid_alpha=args.non_iid, seed=args.seed)
        eval_batch = ds.eval_batch(512)
        get_batch = lambda: ds.round_batches(args.batch)
    else:
        data = synthetic_tokens(W, args.batch, args.seq, cfg.vocab_size,
                                seed=args.seed)
        eval_batch = {k: v[0] for k, v in data.items()}
        get_batch = lambda: synthetic_tokens(W, args.batch, args.seq,
                                             cfg.vocab_size,
                                             seed=args.seed + len(proto.history))

    log = []
    t_start = time.monotonic()
    for r in range(args.rounds):
        part = None
        if scheduler is not None:
            _, mask, _ = scheduler.next_aggregation()
            part = mask
        rec = proto.run_round(get_batch(), participation=part)
        if (r + 1) % max(1, args.rounds // 10) == 0 or r == args.rounds - 1:
            ev = proto.evaluate(eval_batch)
            entry = {"round": r + 1, **ev,
                     "mean_score": float(np.mean(rec.scores)),
                     "chain_time": rec.chain_time,
                     "wall": time.monotonic() - t_start}
            log.append(entry)
            print(json.dumps(entry))
    payouts = proto.finalize()
    if proto.ledger is not None:
        print(f"ledger: {len(proto.ledger.blocks)} blocks, "
              f"verified={proto.ledger.verify_chain()}, "
              f"ipfs objects={proto.ipfs.puts}")
        print(f"value conservation: {proto.contract.total_value():.2f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"log": log, "payouts": payouts}, f, indent=1)


if __name__ == "__main__":
    main()
