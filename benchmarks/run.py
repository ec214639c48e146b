"""Benchmark harness — one entry per paper figure/table + framework-level
benches. Prints ``name,us_per_call,derived`` CSV rows per experiment.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig2,...]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced rounds/samples (CI-speed)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset (fig2,fig3,fig4,fig56,"
                         "trust,async,async_node,serve,network,cfl,chain,"
                         "kernels,fused_round,roofline)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    q = args.quick

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (async_ablation, async_node, cfl_baseline,
                            fig2_blockchain, fig3_scalability,
                            fig4_reliability, fig56_convergence,
                            kernel_bench, network_reliability,
                            proof_serving, roofline, trust_ablation)

    suite = {
        "fig2": lambda: fig2_blockchain.run(
            rounds=20 if q else 60, samples=1024 if q else 2048),
        "fig3": lambda: fig3_scalability.run(
            rounds=20 if q else 60, samples=2048 if q else 4096),
        "fig4": lambda: (
            fig4_reliability.run(
                rounds=16 if q else 40, samples=2048 if q else 4096),
            fig4_reliability.run_churn(
                rounds=12 if q else 24, samples=2048)),
        "fig56": lambda: fig56_convergence.run(
            rounds=60 if q else 100, samples=2048 if q else 4096),
        "trust": lambda: trust_ablation.run(
            rounds=20 if q else 50, samples=2048 if q else 4096),
        "async": lambda: async_ablation.run(
            rounds=16 if q else 40, samples=2048 if q else 4096),
        # event-driven node headline: simulated-time settlement tail latency
        # under a heavy-tailed straggler profile + chain-only cohort seal
        # cost (writes the CI-gated BENCH_async_node.json)
        "async_node": lambda: async_node.run(
            W=10_000 if q else 100_000,
            sync_rounds=3 if q else 4,
            async_events=120 if q else 400,
            chain_events=6 if q else 8),
        # chain read path: batched multiproof speedup vs independent proofs
        # + light-client QPS under live settlement (writes the CI-gated
        # BENCH_proof_serving.json)
        "serve": lambda: proof_serving.run(
            W=10_000 if q else 100_000,
            rounds=3 if q else 4,
            duration_s=1.0 if q else 1.5),
        # multi-node settlement reliability: fault-free/partition/byzantine
        # seed sweep (writes the CI-gated BENCH_network_reliability.json:
        # rejoin within budget, byzantine containment == 1.0)
        "network": lambda: network_reliability.run(
            seeds=8 if q else 20),
        "cfl": lambda: cfl_baseline.run(
            rounds=25 if q else 50, samples=2048 if q else 4096),
        "kernels": kernel_bench.run,
        # fused flat-pack trust round vs per-leaf reference on paper-CNN
        # shapes up to the 10k cohort (writes the CI-gated
        # BENCH_fused_round.json: fused HBM passes <= 2, no wall regression
        # of the default path)
        "fused_round": lambda: kernel_bench.run_fused_round(
            worker_counts=(256, 1024, 4096) if q
            else (256, 1024, 4096, 10240),
            e2e=not q),
        "roofline": roofline.run,
        # chain-layer scaling: dense batch settlement vs the legacy scalar
        # path, then the sparse delta path (W=1M at full scale — the
        # million-worker headline gates on the cohort pattern)
        "chain": lambda: (
            fig3_scalability.run_chain_scaling(
                worker_counts=(1_000, 10_000) if q
                else (1_000, 10_000, 100_000),
                rounds=2 if q else 3),
            fig3_scalability.run_sparse_settlement(
                worker_count=100_000 if q else 1_000_000,
                rounds=3 if q else 6,
                headline_budget_s=None if q else 0.1)),
    }
    failures = []
    for name, fn in suite.items():
        if only and name not in only:
            continue
        print(f"\n===== {name} =====")
        t0 = time.monotonic()
        try:
            fn()
            print(f"[{name}] done in {time.monotonic() - t0:.1f}s")
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED: {failures}")
        sys.exit(1)
    print("\nALL BENCHMARKS PASSED")


if __name__ == "__main__":
    main()
