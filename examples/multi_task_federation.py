"""Multi-tenant chain node: three federated tasks sharing one ledger.

The paper's blockchain layer is shared infrastructure — many collaborative
learning tasks settle on the same chain. Here one ``ChainNode`` serves
three heterogeneous MNIST federations (different worker counts, Merkle
chunk sizes, and round cadences). Ticks where several tasks fire seal ONE
multi-task block committing the canonical ``task_id → root`` map; solo
ticks seal the classic single-task layout. Settlement proofs are
two-level (chunk-in-task, task-in-block) and a failing task would abort
only its own round.

    PYTHONPATH=src python examples/multi_task_federation.py
"""
from repro.configs.base import FederationConfig, TrainConfig
from repro.configs.registry import get_config
from repro.core.node import ChainNode
from repro.data.datasets import make_federated_mnist


def main() -> None:
    tc = TrainConfig(lr=0.01, momentum=0.5, optimizer="sgd")  # paper §IV
    cfg = get_config("paper-net")
    node = ChainNode(pipeline_depth=2)

    # three tenants: W=6 task, W=4 two-cluster task, W=2 small task
    feds = {
        "hospital-fl": FederationConfig(
            num_clusters=2, workers_per_cluster=3, trust_threshold=0.3,
            top_k_rewarded=3, merkle_chunk_size=2),
        "bank-fl": FederationConfig(
            num_clusters=2, workers_per_cluster=2, trust_threshold=0.4,
            top_k_rewarded=2, merkle_chunk_size=1),
        "iot-fl": FederationConfig(
            num_clusters=1, workers_per_cluster=2, trust_threshold=0.2,
            top_k_rewarded=1, merkle_chunk_size=4),
    }
    cadence = {"hospital-fl": 1, "bank-fl": 2, "iot-fl": 3}  # rounds/tick
    tasks = {tid: node.create_task(tid, cfg, fed, tc, seed=i)
             for i, (tid, fed) in enumerate(feds.items())}
    data = {tid: make_federated_mnist(t.W, samples=1024, seed=i)
            for i, (tid, t) in enumerate(tasks.items())}
    evals = {tid: data[tid].eval_batch(256) for tid in tasks}

    ticks = 12
    for t in range(ticks):
        firing = {tid: data[tid].round_batches(32)
                  for tid in tasks if t % cadence[tid] == 0}
        node.run_tick(firing)
        print(f"tick {t:2d}  tasks={sorted(firing)}")
    node.flush()

    print(f"\nchain: {len(node.ledger.blocks)} blocks, "
          f"deep-verified={node.ledger.verify_chain(deep=True)}")
    multi = [b for b in node.ledger.blocks if b.task_roots]
    print(f"multi-task blocks: {len(multi)} "
          f"(e.g. block {multi[0].index} commits "
          f"{sorted(multi[0].task_roots)})")

    # a light client audits a co-tenant block's two-level proof without
    # trusting the node: synced headers + a batched proof fetch
    from repro.serve import LightClient
    auditor = LightClient(node.read_server())
    auditor.sync()
    batch = auditor.fetch_proofs("hospital-fl", list(range(6)),
                                 round_index=0)
    print(f"2-level proofs for all 6 hospital-fl workers, round 0: "
          f"{batch.num_digests} shared siblings, "
          f"verifies={auditor.verify_batch(batch)}, "
          f"worker 0 record={batch.decoded(0)}")

    payouts = node.finalize()
    for tid, task in tasks.items():
        rounds = len(task.history)
        pen_total = sum(float(r.penalties.sum()) for r in task.history)
        trust = task.reputation.scores.round(2).tolist()
        print(f"\n[{tid}] rounds={rounds}  "
              f"final_acc={task.evaluate(evals[tid])['accuracy']:.3f}")
        print(f"  trust (reputation EMA): {trust}")
        print(f"  penalties collected: {pen_total:.1f}  "
              f"requester balance: {task.contract.requester_balance:.1f}")
        print(f"  payouts: {({k: round(v, 1) for k, v in payouts[tid].items()})}")
        print(f"  ipfs puts: {node.ipfs.puts_by_owner[tid]}")
    print(f"\nshared store: {node.ipfs.puts} puts, "
          f"{node.ipfs.bytes_stored / 1e6:.1f} MB stored, "
          f"{node.ipfs.dedup_hits} deduped")


if __name__ == "__main__":
    main()
