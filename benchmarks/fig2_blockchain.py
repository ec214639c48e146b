"""Paper Fig. 2 — accuracy AND wall-time, 3 workers, with/without blockchain.

Paper claim: accuracy identical with/without blockchain; the blockchain
variant costs more time per round. Our reproduction runs the SAME seeds so
learning dynamics are bit-identical; the chain adds hashing/contract/IPFS
work measured separately.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import csv_row, paper_protocol, run_rounds
from repro.data.datasets import make_federated_mnist


def run(rounds: int = 60, samples: int = 2048, seed: int = 0):
    results, settle = {}, {}
    for chain in (True, False):
        ds = make_federated_mnist(3, samples=samples, seed=seed)
        proto = paper_protocol(3, blockchain=chain, seed=seed)
        log = run_rounds(proto, ds, rounds, eval_every=max(rounds // 10, 1))
        proto.finalize()            # drains the settler: settle_time final
        key = "with" if chain else "without"
        results[key] = log
        settle[key] = float(np.mean([r.settle_time for r in proto.history]))
    on, off = results["with"], results["without"]
    acc_gap = max(abs(a["accuracy"] - b["accuracy"]) for a, b in zip(on, off))
    t_on = float(np.mean([r["round_time"] for r in on]))
    t_off = float(np.mean([r["round_time"] for r in off]))
    # training-thread chain cost is the settler queue handoff only; the real
    # per-round chain work (IPFS + contract + Merkle) is the settler-thread
    # settle_time
    handoff_on = float(np.mean([r["chain_time"] for r in on]))
    chain_on, chain_off = settle["with"], settle["without"]
    csv_row("fig2_round_time_with_chain", t_on * 1e6,
            f"acc={on[-1]['accuracy']:.3f} settle_us={chain_on * 1e6:.0f} "
            f"handoff_us={handoff_on * 1e6:.1f}")
    csv_row("fig2_round_time_without_chain", t_off * 1e6,
            f"acc={off[-1]['accuracy']:.3f}")
    csv_row("fig2_accuracy_gap", 0.0, f"max_gap={acc_gap:.6f}")
    csv_row("fig2_chain_overhead_pct", chain_on * 1e6,
            f"{chain_on / max(t_on, 1e-9) * 100:.2f}% of round, "
            f"off the training thread")
    assert acc_gap < 1e-6, "learning dynamics must be chain-independent"
    # the chain's extra work is measured directly on the settler thread
    # (hashing + contract + IPFS); comparing total wall-time is
    # noise-dominated on CPU at this model size, the paper's "with chain is
    # slower" trend is the positive per-round settle_time
    assert chain_on > 10 * chain_off   # chain work is real, off-path ~0
    return {"with": on, "without": off, "acc_gap": acc_gap,
            "settle_s": settle,
            "overhead_pct": chain_on / max(t_on, 1e-9) * 100}


def run_pipeline_depths(depths=(0, 1, 2, 4), rounds: int = 20,
                        samples: int = 1024, seed: int = 0):
    """Pipeline-depth sweep: identical chains at every depth (the settler
    preserves decision sequences), while the chain cost charged to the
    training thread collapses from the full settlement (depth 0, inline)
    to the queue handoff (depth > 0, background settler)."""
    from repro.configs.base import FederationConfig
    from repro.configs.registry import get_config
    from repro.core.protocol import SDFLBProtocol

    from benchmarks.common import PAPER_TC

    out = {}
    chains = {}
    for depth in depths:
        ds = make_federated_mnist(3, samples=samples, seed=seed)
        fed = FederationConfig(num_clusters=1, workers_per_cluster=3,
                               trust_threshold=0.2, pipeline_depth=depth)
        proto = SDFLBProtocol(get_config("paper-net"), fed, PAPER_TC,
                              use_blockchain=True, seed=seed)
        for _ in range(rounds):
            proto.run_round(ds.round_batches(32))
        proto.finalize()
        train_chain = float(np.mean([r.chain_time for r in proto.history]))
        settle_t = float(np.mean([r.settle_time for r in proto.history]))
        out[depth] = {"train_thread_chain_s": train_chain,
                      "settler_thread_s": settle_t}
        chains[depth] = [b.hash for b in proto.ledger.blocks]
        csv_row(f"fig2_pipeline_depth{depth}", train_chain * 1e6,
                f"settler_us={settle_t * 1e6:.0f} "
                f"{'inline' if depth == 0 else 'threaded'}")
    # decisions are depth-independent (byte-identical chains) ...
    assert all(c == chains[depths[0]] for c in chains.values())
    # ... and the threaded settler hides the chain work: the training
    # thread pays the queue handoff, a fraction of the inline settlement
    threaded = min(out[d]["train_thread_chain_s"] for d in depths if d > 0)
    assert threaded < 0.5 * out[0]["train_thread_chain_s"], \
        f"threaded handoff must beat inline settlement: {out}"
    return out


def run_settler_pipeline(depths=(1, 2, 4), rounds: int = 12,
                         samples: int = 768, seed: int = 0):
    """End-to-end settler sweep on the paper protocol at 2 clusters × 3
    workers: every pipelined depth seals the byte-identical chain as the
    serial (inline, depth 0) driver — the settler thread changes when the
    chain work runs, never what is decided — while the training thread
    pays only the queue handoff."""
    import dataclasses

    from repro.configs.base import FederationConfig
    from repro.configs.registry import get_config
    from repro.core.protocol import SDFLBProtocol

    from benchmarks.common import PAPER_TC

    base = FederationConfig(num_clusters=2, workers_per_cluster=3,
                            trust_threshold=0.2, merkle_chunk_size=1)
    chains, out = {}, {}
    for depth in (0, *depths):
        ds = make_federated_mnist(6, samples=samples, seed=seed)
        fed = dataclasses.replace(base, pipeline_depth=depth)
        proto = SDFLBProtocol(get_config("paper-net"), fed, PAPER_TC,
                              use_blockchain=True, seed=seed)
        for _ in range(rounds):
            proto.run_round(ds.round_batches(32))
        proto.finalize()
        chains[depth] = [b.hash for b in proto.ledger.blocks]
        handoff = float(np.mean([r.chain_time for r in proto.history]))
        out[depth] = handoff
        csv_row(f"fig2_settler_pipeline_depth{depth}", handoff * 1e6,
                "serial" if depth == 0 else "pipelined")
    assert all(c == chains[0] for c in chains.values()), \
        "pipelined chains must be byte-identical to the serial driver"
    return out


def run_settlement_paths(W: int = 5_000, rounds: int = 5, seed: int = 0):
    """Batch vs legacy-scalar settlement cost on identical score streams:
    the scalar dict API (kept as a wrapper for Algorithm 1 equivalence)
    pays O(W) Python dict work per round; the array path pays O(1) Python
    + vectorized numpy. Reported as fig2 rows since this is exactly the
    chain-side wall-time the with-blockchain variant adds per round."""
    import time

    from repro.chain.contract import TrustContract
    from repro.chain.ledger import Ledger

    rng = np.random.default_rng(seed)
    score_mat = rng.random((rounds, W))

    def make():
        c = TrustContract(Ledger(), requester_deposit=1e5, worker_stake=10.0,
                          penalty_pct=50.0, trust_threshold=0.5, top_k=10)
        c.join_batch(W)
        return c

    c_scalar, c_batch = make(), make()
    t0 = time.monotonic()
    for r in range(rounds):
        c_scalar.settle_round(
            r, {f"worker-{w}": float(score_mat[r, w]) for w in range(W)})
    t_scalar = (time.monotonic() - t0) / rounds
    t0 = time.monotonic()
    for r in range(rounds):
        c_batch.settle_round_batch(r, score_mat[r])
    t_batch = (time.monotonic() - t0) / rounds
    # both paths settle identically (the equivalence property the tests pin)
    np.testing.assert_allclose(c_scalar.stake, c_batch.stake)
    assert abs(c_scalar.total_value() - c_batch.total_value()) < 1e-6
    csv_row("fig2_settle_scalar_path", t_scalar * 1e6, f"W={W}")
    csv_row("fig2_settle_batch_path", t_batch * 1e6,
            f"W={W} speedup={t_scalar / t_batch:.1f}x")
    assert t_batch < t_scalar, "array path must beat per-worker dict loops"
    return {"scalar_s": t_scalar, "batch_s": t_batch}


if __name__ == "__main__":
    import json
    run_settlement_paths()
    run_pipeline_depths()
    run_settler_pipeline()
    print(json.dumps(run()["with"][-1], indent=1))
