"""Paper Fig. 3 — scalability: average accuracy vs epoch for 8/16/20
workers. Claim: consistent accuracy trends across worker counts.

Extended with a chain-only settlement scaling sweep (``run_chain_scaling``)
to W ≥ 100k workers: the array-native contract settles a round in O(1)
Python ops + O(W) vectorized numpy/hashing, so per-worker settlement cost
*falls* with W (sub-linear total Python overhead) and a 100k-worker round
stays under 1s on CPU — the regime the ROADMAP's millions-of-users
north-star needs, far beyond the paper's W=20. ``run_merkle_chunk_sweep``
isolates the commit itself: chunked leaves (k records per leaf) hash
~2·W/k nodes instead of ~2·W, which removed the last O(W)·SHA-256 host
cost on the settlement path. ``run_sparse_settlement`` takes the last
step to W=1M: with ≤10% of workers active per tick, sparse delta commits
re-hash only the dirty chunk paths (O(C·log(W/k)) instead of O(W/k)), so
a million-worker round settles in delta time proportional to *activity*,
not population — reported per changed record alongside per worker."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import bench_json, csv_row, paper_protocol, run_rounds
from repro.data.datasets import make_federated_mnist


def run(rounds: int = 60, samples: int = 4096, seed: int = 0,
        worker_counts=(8, 16, 20)):
    curves = {}
    for W in worker_counts:
        ds = make_federated_mnist(W, samples=samples, seed=seed)
        clusters = 2 if W % 2 == 0 else 1
        proto = paper_protocol(W, clusters=clusters, seed=seed)
        log = run_rounds(proto, ds, rounds, eval_every=max(rounds // 10, 1))
        proto.finalize()
        curves[W] = log
        csv_row(f"fig3_final_accuracy_w{W}", 0.0,
                f"acc={log[-1]['accuracy']:.3f}")
    finals = [curves[W][-1]["accuracy"] for W in worker_counts]
    spread = max(finals) - min(finals)
    csv_row("fig3_accuracy_spread_across_W", 0.0, f"spread={spread:.4f}")
    # scalability claim: all configs converge to a similar band
    assert spread < 0.15, f"accuracy should be consistent across W: {finals}"
    return curves


def run_merkle_chunk_sweep(worker_count: int = 100_000,
                           chunk_sizes=(1, 8, 64, 256), repeats: int = 3,
                           seed: int = 0):
    """Merkle-commit cost vs chunk size at fixed W: building the commit
    tree over one round's settlement records with k records per leaf. Pins
    the chunked-leaves claim — the k=64 default must cut commit time ≥5×
    versus the per-record (k=1, PR-1) commit at W=100k — and checks every
    chunking still proves and verifies an arbitrary record."""
    from repro.chain.contract import encode_settlement_records
    from repro.chain.ledger import MerkleTree

    rng = np.random.default_rng(seed)
    W = worker_count
    scores = rng.random(W)
    records = encode_settlement_records(0, np.arange(W), scores,
                                        np.zeros(W), np.full(W, 10.0))
    t_commit = {}
    for k in chunk_sizes:
        times, tree = [], None
        for _ in range(repeats):
            t0 = time.monotonic()
            tree = MerkleTree(records, chunk_size=k)
            times.append(time.monotonic() - t0)
        t_commit[k] = float(np.median(times))
        # an arbitrary record stays auditable: chunk + node path
        widx = W // 3
        start = (widx // k) * k
        chunk = records.chunk_bytes(start, min(start + k, W))
        assert MerkleTree.verify(chunk, tree.record_proof(widx), tree.root)
        csv_row(f"fig3_merkle_commit_w{W}_k{k}", t_commit[k] * 1e6,
                f"leaves={tree.num_leaves} hash_ops={tree.hash_ops}")
    bench_json("merkle_chunk_sweep",
               {"worker_count": W,
                "commit_s": {str(k): t for k, t in t_commit.items()}})
    if 1 in t_commit and 64 in t_commit:
        speedup = t_commit[1] / t_commit[64]
        csv_row(f"fig3_merkle_chunk_speedup_w{W}", 0.0,
                f"k64_vs_k1={speedup:.1f}x")
        assert speedup >= 5.0, \
            f"chunked commit must be >=5x faster than per-record: {t_commit}"
    return t_commit


def run_sparse_settlement(worker_count: int = 1_000_000,
                          active_frac: float = 0.10, rounds: int = 6,
                          chunk_size: int = 64,
                          patterns=("cohort", "random"), seed: int = 0,
                          deep_verify: bool = True,
                          measure_dense_full: bool = True,
                          headline_budget_s=0.1, delta_gate_ratio=3.0,
                          json_name: str = "sparse_settlement"):
    """Million-worker sparse settlement sweep: W workers enrolled, only
    C = ``active_frac``·W settle per tick, each tick sealing a
    ``DeltaCommit`` block that still commits (and proves) the full
    population.

    Two activity patterns bound the delta cost:

    * ``cohort`` — contiguous disjoint cohorts rotate through the rounds
      (the paper's cluster-scheduled regime). Dirty chunk leaves = C/k, so
      delta hashing matches a dense commit over C records and the
      W=1M/10%-active tick lands under ``headline_budget_s`` (~100 ms on
      the 2-core CI class of box).
    * ``random`` — C uniform-random workers. At k=64 and 10% activity
      nearly *every* chunk is dirtied (E[dirty leaves] ≈ W/k), so the
      delta degenerates toward a full re-commit; reported honestly as the
      adversarial bound — the headline gates on ``cohort`` only.

    Costs are reported per *changed* record (delta_s/C — the number that
    must stay flat as W grows) alongside per enrolled worker (delta_s/W).
    The regression gate is *relative*: a cohort delta round touching C
    records must cost < ``delta_gate_ratio``× a dense round of a
    C-worker contract per record (pop-buffer scatter + overlay clone +
    O(C·log(W/k)) interior re-hash are the only extras). Extends
    ``BENCH_chain_scaling.json`` with the W row and writes
    ``BENCH_<json_name>.json``."""
    import os

    from repro.chain.contract import TrustContract
    from repro.chain.ledger import Ledger

    W = worker_count
    C = max(1, int(W * active_frac))
    k = chunk_size
    rng = np.random.default_rng(seed)

    def make(w, sparse):
        c = TrustContract(Ledger(), requester_deposit=1e6,
                          worker_stake=10.0, penalty_pct=50.0,
                          trust_threshold=0.5, top_k=max(w // 100, 1),
                          merkle_chunk_size=k, sparse_settlement=sparse)
        c.join_batch(w)
        return c

    # dense reference: a C-worker contract settling all C per round — the
    # per-record baseline the delta gate compares against
    dense_c = make(C, sparse=False)
    times = []
    for r in range(max(rounds, 2)):
        s = rng.random(C)
        t0 = time.monotonic()
        dense_c.settle_round_batch(r, s, timestamp=float(r + 1))
        times.append(time.monotonic() - t0)
    dense_at_active_s = float(np.median(times[1:]))
    csv_row(f"fig3_sparse_dense_ref_c{C}", dense_at_active_s * 1e6,
            f"per_record_us={dense_at_active_s / C * 1e6:.3f}")

    dense_at_full_s = None
    if measure_dense_full:
        # one dense full-population round at W — what every tick would
        # cost without the sparse path
        dense_w = make(W, sparse=False)
        times = []
        for r in range(2):
            s = rng.random(W)
            t0 = time.monotonic()
            dense_w.settle_round_batch(r, s, timestamp=float(r + 1))
            times.append(time.monotonic() - t0)
        dense_at_full_s = float(min(times))
        csv_row(f"fig3_sparse_dense_full_w{W}", dense_at_full_s * 1e6,
                f"per_worker_us={dense_at_full_s / W * 1e6:.3f}")

    anchor_s, delta_s, dirty = {}, {}, {}
    for pattern in patterns:
        c = make(W, sparse=True)
        times = []
        for r in range(rounds):
            if pattern == "cohort":
                start = (r % max(W // C, 1)) * C
                ids = np.arange(start, start + C, dtype=np.int64)
            else:
                ids = np.sort(rng.permutation(W)[:C]).astype(np.int64)
            s = rng.random(C)
            t0 = time.monotonic()
            c.settle_round_batch(r, s, worker_ids=ids, timestamp=float(r + 1))
            times.append(time.monotonic() - t0)
        # round 0 pays the dense anchor (the base commit over all W);
        # steady state is the delta rounds
        anchor_s[pattern] = times[0]
        delta_s[pattern] = float(np.median(times[1:] or times))
        dirty[pattern] = len(np.unique(ids // k))
        csv_row(f"fig3_sparse_settle_w{W}_{pattern}",
                delta_s[pattern] * 1e6,
                f"active={C} per_changed_us="
                f"{delta_s[pattern] / C * 1e6:.3f} per_worker_us="
                f"{delta_s[pattern] / W * 1e6:.4f} "
                f"dirty_leaves={dirty[pattern]}/{-(-W // k)} "
                f"anchor_s={anchor_s[pattern]:.3f}")
        # the full population stays proof-covered every delta round:
        # an active and an idle worker both verify against the last block
        last = rounds - 1
        active_w = int(ids[0])
        idle_w = int(np.setdiff1d(np.arange(C + 1, dtype=np.int64),
                                  ids[:C + 1])[0])
        for wid in (active_w, idle_w):
            assert c.verify_settlement(c.settlement_proof(last, wid)), \
                f"worker {wid} proof must verify ({pattern})"
        if deep_verify:
            assert c.ledger.verify_chain(deep=True), \
                f"sparse chain must deep-verify ({pattern})"

    if delta_gate_ratio and "cohort" in delta_s:
        per_changed = delta_s["cohort"] / C
        per_dense = dense_at_active_s / C
        csv_row(f"fig3_sparse_delta_gate_w{W}", 0.0,
                f"cohort_vs_dense_ref={per_changed / per_dense:.2f}x "
                f"(gate {delta_gate_ratio}x)")
        assert per_changed < delta_gate_ratio * per_dense, \
            f"cohort delta per-changed-record cost must stay within " \
            f"{delta_gate_ratio}x of a dense C-record round: " \
            f"{per_changed * 1e6:.3f}us vs {per_dense * 1e6:.3f}us"
    if headline_budget_s and "cohort" in delta_s:
        assert delta_s["cohort"] < headline_budget_s, \
            f"W={W} cohort delta tick must settle under " \
            f"{headline_budget_s}s: {delta_s['cohort']:.3f}s"

    payload = {"worker_count": W, "active": C, "active_frac": active_frac,
               "chunk_size": k, "rounds": rounds,
               "anchor_s": anchor_s, "delta_s": delta_s,
               "dirty_leaves": dirty,
               "per_changed_us": {p: t / C * 1e6
                                  for p, t in delta_s.items()},
               "per_worker_us": {p: t / W * 1e6
                                 for p, t in delta_s.items()},
               "dense_at_active_s": dense_at_active_s,
               "dense_at_full_s": dense_at_full_s,
               "cpu_count": os.cpu_count()}
    bench_json(json_name, payload)
    # extend the chain-scaling artifact with this W's sparse row (and the
    # dense full-round time when measured) — merge, don't overwrite: the
    # dense sweep owns the other rows
    import json
    import pathlib
    p = pathlib.Path("BENCH_chain_scaling.json")
    data = json.loads(p.read_text()) if p.exists() else {}
    if dense_at_full_s is not None:
        data.setdefault("batch_s", {})[str(W)] = dense_at_full_s
    data.setdefault("sparse_delta_s", {})[str(W)] = delta_s
    data["sparse_active_frac"] = active_frac
    p.write_text(json.dumps(data, indent=1, sort_keys=True) + "\n")
    print(f"merged sparse row into {p}")
    return payload


def run_multi_task_node(worker_count: int = 100_000,
                        task_counts=(1, 2, 4), chunk_size: int = 4096,
                        rounds: int = 7, seed: int = 0):
    """Multi-tenant settlement sweep at fixed *total* W: N co-tenant tasks
    (worker_count // N workers each) settle every round into ONE
    multi-task block, timed per settled record.

    Claims pinned: (1) determinism — re-driving the same score stream
    seals byte-identical chains; (2) per-task roots are co-tenancy
    independent — bit-identical to each task settling alone on its own
    ledger."""
    from repro.chain.contract import TrustContract
    from repro.chain.ledger import Ledger
    from repro.core.node import TaskRoundWork, settle_tasks_block

    def make_contract(led, tid, Wt):
        c = TrustContract(led, requester_deposit=1e6, worker_stake=10.0,
                          penalty_pct=50.0, trust_threshold=0.5,
                          top_k=max(Wt // 100, 1),
                          merkle_chunk_size=chunk_size, task_id=tid)
        c.join_batch(Wt)
        return c

    t_settle, tput = {}, {}
    for N in task_counts:
        # N*Wt records actually settle per tick (exact, not W, when N does
        # not divide W)
        Wt = worker_count // N
        tids = [f"task-{i:02d}" for i in range(N)]
        scores = np.random.default_rng(seed).random((rounds, N, Wt))

        def drive():
            led = Ledger()
            cs = {tid: make_contract(led, tid, Wt) for tid in tids}
            times, roots = [], []
            for r in range(rounds):
                work = [TaskRoundWork(tid, cs[tid], r, scores[r, i])
                        for i, tid in enumerate(tids)]
                t0 = time.monotonic()
                blk, _, errors = settle_tasks_block(led, work,
                                                    timestamp=float(r + 1))
                times.append(time.monotonic() - t0)
                assert not errors
                roots.append(led.task_roots(blk.index))
            assert led.verify_chain(deep=True)
            return led, times, roots

        led, times, roots = drive()
        # determinism: the same stream seals byte-identical chains
        led2, times2, _ = drive()
        assert [b.hash for b in led.blocks] \
            == [b.hash for b in led2.blocks], \
            f"multi-task chains must be reproducible (N={N})"
        samples = (times[1:] or times) + (times2[1:] or times2)
        t_settle[N] = float(min(samples))
        tput[N] = N * Wt / t_settle[N]
        # per-task commits are co-tenancy independent: spot-check two
        # tasks against standalone single-tenant runs
        for i, tid in enumerate(tids[:2]):
            solo_led = Ledger()
            solo = make_contract(solo_led, tid, Wt)
            for r in range(rounds):
                solo.settle_round_batch(r, scores[r, i],
                                        timestamp=float(r + 1))
            assert [roots[r][tid] for r in range(rounds)] \
                == [b.records_root for b in solo_led.blocks[1:]], \
                f"task {tid} roots must be co-tenancy independent"
        csv_row(f"fig3_multi_task_node_w{worker_count}_n{N}",
                t_settle[N] * 1e6,
                f"tasks={N} k={chunk_size} "
                f"records_per_s={tput[N] / 1e6:.2f}M")
    return {"settle_s": t_settle, "records_per_s": tput}


def run_chain_scaling(worker_counts=(1_000, 10_000, 100_000), rounds: int = 3,
                      seed: int = 0):
    """Chain-only settlement sweep: full Algorithm 1 round (vectorized
    BadWorkers/penalties/transfer + Merkle commit + block seal) per W,
    batch path vs the legacy per-worker scalar path.

    The claim pinned here: settlement wall-time grows sub-linearly in
    *Python overhead* — the batch path's interpreter work is O(1) per round
    (the O(W) remainder is vectorized numpy + C hashing), whereas the
    seed's per-worker loop (tx dicts, min(), list appends, W dicts
    canonically hashed into each block — emulated by ``_legacy_settle``)
    pays *rising* interpreter cost per worker. So the batch advantage must
    widen with W, batch per-worker cost must stay in a flat band, and a
    100k-worker round must settle in < 1s on CPU (the legacy path crosses
    1s right around W=100k)."""
    from repro.chain.contract import TrustContract
    from repro.chain.ledger import Ledger

    def _legacy_settle(ledger, r, names, scores, state, F, P, T):
        """Seed-faithful scalar settlement: per-worker score/penalty tx
        dicts appended into the round block."""
        pending = []
        for wid, s in sorted(zip(names, scores.tolist())):
            acct = state[wid]
            acct[2].append(s)
            pending.append({"type": "score", "round": r, "worker": wid,
                            "score": s})
            if s < T:
                pen = min(F * P / 100.0, acct[0])
                acct[0] -= pen
                acct[1] += 1
                pending.append({"type": "penalty", "round": r, "worker": wid,
                                "amount": pen})
        ledger.append_block(pending)

    rng = np.random.default_rng(seed)
    F, P, T = 10.0, 50.0, 0.5
    t_batch, t_legacy, speedup = {}, {}, {}
    for W in worker_counts:
        score_mat = rng.random((rounds, W))
        cb = TrustContract(Ledger(), requester_deposit=1e6, worker_stake=F,
                           penalty_pct=P, trust_threshold=T,
                           top_k=max(W // 100, 1))
        cb.join_batch(W)
        times = []
        for r in range(rounds):
            t0 = time.monotonic()
            cb.settle_round_batch(r, score_mat[r])
            times.append(time.monotonic() - t0)
        t_batch[W] = float(np.median(times))
        assert cb.ledger.verify_chain(deep=True)

        names = [cb.worker_name(i) for i in range(W)]
        state = {n: [F, 0, []] for n in names}
        legacy_ledger = Ledger()
        times = []
        for r in range(rounds):
            t0 = time.monotonic()
            _legacy_settle(legacy_ledger, r, names, score_mat[r], state, F, P,
                           T)
            times.append(time.monotonic() - t0)
        t_legacy[W] = float(np.median(times))
        speedup[W] = t_legacy[W] / t_batch[W]
        # identical Algorithm 1 outcome, loop or vectorized
        np.testing.assert_allclose(
            cb.stake, np.array([state[n][0] for n in names]))
        csv_row(f"fig3_chain_settle_w{W}", t_batch[W] * 1e6,
                f"per_worker_us={t_batch[W] / W * 1e6:.3f} "
                f"vs_legacy={speedup[W]:.1f}x")
    counts = sorted(t_batch)
    lo, hi = counts[0], counts[-1]
    # Python overhead is sub-linear: the gap to the Python-loop legacy path
    # widens with W, and per-worker batch cost stays in a flat band
    assert speedup[hi] > speedup[lo], \
        f"batch advantage must widen with W: {speedup}"
    assert t_batch[hi] / hi < 2.0 * t_batch[lo] / lo, \
        f"per-worker batch cost must stay flat: {t_batch}"
    if hi >= 100_000:
        assert t_batch[hi] < 1.0, \
            f"100k-worker settlement must stay under 1s: {t_batch[hi]:.2f}s"
    csv_row("fig3_chain_settle_scaling", 0.0,
            f"x{hi // lo} workers -> x{t_batch[hi] / t_batch[lo]:.1f} time, "
            f"legacy-path speedup {speedup[lo]:.1f}x -> {speedup[hi]:.1f}x")
    bench_json("chain_scaling",
               {"batch_s": {str(w): t for w, t in t_batch.items()},
                "legacy_s": {str(w): t for w, t in t_legacy.items()},
                "speedup": {str(w): s for w, s in speedup.items()}})
    return {"batch": t_batch, "legacy": t_legacy, "speedup": speedup}


if __name__ == "__main__":
    run_merkle_chunk_sweep()
    run_chain_scaling()
    run_sparse_settlement()
    run_multi_task_node()
    run(rounds=30, samples=2048)
