"""Multi-tenant chain node: N concurrent federated tasks on one ledger.

Pins the multi-task block layout (canonical task_id → root map over
per-task DenseCommits), N ∈ {1, 2, 5} bit-identity of per-task commits
vs the single-tenant driver, task-isolation under tampering (corrupting
task A's records never invalidates task B's proofs), two-level
settlement-proof round-trips with malformed-proof rejection, run-to-run
determinism, pipelined settlement byte-identical to the inline driver, and
failure isolation: a failing round computation commits nothing, is sticky,
and names its task and round in the raised error."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.chain.contract import TrustContract
from repro.chain.ledger import (DenseCommit, Ledger, MerkleTree,
                                MultiTaskCommit)
from repro.core.node import (ChainNode, TaskRoundWork, TaskSettlementError,
                             settle_tasks_block)
from repro.core.protocol import SDFLBProtocol


def _records(n, seed=0, size=40):
    rng = np.random.default_rng(seed)
    return [bytes(rng.bytes(size)) for _ in range(n)]


def _contract(led, tid, W, chunk=3, deposit=1e4):
    c = TrustContract(led, requester_deposit=deposit, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=5,
                      merkle_chunk_size=chunk, task_id=tid)
    c.join_batch(W)
    return c


# -- commit layer -------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(n_tasks=st.integers(1, 5), base=st.integers(1, 40),
       k=st.integers(1, 6), seed=st.integers(0, 1000))
def test_multi_task_commit_layers_over_sharded_commits(n_tasks, base, k,
                                                       seed):
    """Property: a MultiTaskCommit over per-task DenseCommits has (a) a
    single-task root bit-equal to the task's own root with an empty task
    path, and (b) for any N, per-record two-level proofs (the task's own
    proof + the task path) that verify against the combined root via the
    unchanged MerkleTree.verify."""
    recs = {f"t{i}": _records(base + 3 * i, seed + i)
            for i in range(n_tasks)}
    commits = {t: DenseCommit(r, k) for t, r in recs.items()}
    mtc = MultiTaskCommit(commits)
    assert mtc.task_ids == sorted(recs)
    if n_tasks == 1:
        only = next(iter(commits.values()))
        assert mtc.root == only.root             # bit-identical to one task
        assert mtc.task_path("t0") == []
    for t, r in recs.items():
        assert mtc.task_roots()[t] == commits[t].root
        for ri in {0, len(r) - 1, len(r) // 2}:
            proof = mtc.record_proof(ri, t)
            assert proof == commits[t].record_proof(ri) + mtc.task_path(t)
            chunk, off = mtc.record_chunk(ri, t)
            assert chunk[off] == r[ri]
            assert MerkleTree.verify(b"".join(chunk), proof, mtc.root)
    assert mtc.recompute_root() == mtc.root


def test_multi_task_commit_rejects_bad_shapes():
    recs = _records(6)
    sc = DenseCommit(recs, 2)
    with pytest.raises(ValueError):
        MultiTaskCommit({})
    with pytest.raises(ValueError):              # anonymous only when alone
        MultiTaskCommit({None: sc, "a": sc})
    mtc = MultiTaskCommit({"a": sc, "b": DenseCommit(_records(4, 1), 2)})
    with pytest.raises(KeyError):                # multi-task needs a task_id
        mtc.commit_for(None)
    with pytest.raises(KeyError):
        mtc.commit_for("ghost")


# -- N-task bit-identity vs the single-tenant driver --------------------------


@pytest.mark.parametrize("N", [1, 2, 5])
def test_cotenant_commits_bit_identical_to_standalone(N):
    """N ∈ {1, 2, 5} heterogeneous tasks (different W and chunk sizes)
    co-committed per round through settle_tasks_block produce, for every
    task, the byte-identical root, penalties, and stakes it
    would commit running alone through settle_round_batch — and with N=1
    the whole block (hash included) is bit-identical to the single-tenant
    driver, regardless of task_id."""
    rng = np.random.default_rng(7)
    tids = [f"task-{i:02d}" for i in range(N)]
    Ws = [20 + 7 * i for i in range(N)]
    chunks = [1, 3, 4, 2, 8][:N]
    rounds = 3
    scores = {tid: rng.random((rounds, W)) for tid, W in zip(tids, Ws)}

    solo = {}
    for i, tid in enumerate(tids):
        led = Ledger()
        c = _contract(led, tid, Ws[i], chunks[i])
        for r in range(rounds):
            c.settle_round_batch(r, scores[tid][r], timestamp=float(r + 1))
        solo[tid] = {"roots": [b.records_root for b in led.blocks[1:]],
                     "hashes": [b.hash for b in led.blocks],
                     "stake": c.stake.copy(),
                     "requester": c.requester_balance}

    led = Ledger()
    cs = {tid: _contract(led, tid, Ws[i], chunks[i])
          for i, tid in enumerate(tids)}
    blocks = []
    for r in range(rounds):
        work = [TaskRoundWork(tid, cs[tid], r, scores[tid][r])
                for tid in tids]
        blk, pens, errors = settle_tasks_block(led, work,
                                               timestamp=float(r + 1))
        assert not errors and set(pens) == set(tids)
        blocks.append(blk)
    assert led.verify_chain(deep=True)

    for tid in tids:
        # per-task roots are co-tenancy independent
        assert [led.task_roots(b.index)[tid] for b in blocks] \
            == solo[tid]["roots"]
        np.testing.assert_array_equal(cs[tid].stake, solo[tid]["stake"])
        assert cs[tid].requester_balance == solo[tid]["requester"]
    if N == 1:
        # the whole chain is bit-identical to the single-tenant driver
        assert [b.hash for b in led.blocks] == solo[tids[0]]["hashes"]
        assert all(b.task_roots is None for b in led.blocks)
    else:
        assert all(set(b.task_roots) == set(tids) for b in blocks)
        task_path_len = (N - 1).bit_length()
        for tid in tids:
            # two-level proof = the task's own chunk path + the
            # cross-task path to the block root
            proof = cs[tid].settlement_proof(1, 0)
            assert cs[tid].verify_settlement(proof)
            assert len(proof["proof"]) >= task_path_len


def test_task_isolation_under_tampering():
    """Corrupting task A's stored records breaks A's proofs and deep chain
    verification but never invalidates task B's proofs — B's sibling
    digests are the stored task roots, not A's bytes."""
    rng = np.random.default_rng(3)
    led = Ledger()
    a = _contract(led, "task-a", 24, chunk=2)
    b = _contract(led, "task-b", 16, chunk=4)
    sa, sb = rng.random((2, 24)), rng.random((2, 16))
    for r in range(2):
        blk, _, errors = settle_tasks_block(
            led, [TaskRoundWork("task-a", a, r, sa[r]),
                  TaskRoundWork("task-b", b, r, sb[r])],
            timestamp=float(r + 1))
        assert not errors
    assert led.verify_chain(deep=True)
    proofs_b = [b.settlement_proof(1, w) for w in range(16)]
    led.tamper_record(blk.index, 5, b"x" * 40, task_id="task-a")
    # A is broken at the chunk level and at deep verification …
    assert not led.verify_record(blk.index, 5, task_id="task-a")
    assert led.verify_chain() and not led.verify_chain(deep=True)
    # … while every one of B's settlements still proves and verifies
    for w, proof in enumerate(proofs_b):
        assert b.verify_settlement(proof)
        assert led.verify_record(blk.index, w, task_id="task-b")
    assert b.verify_settlement(b.settlement_proof(1, 3))


def test_three_level_proofs_roundtrip_and_malformed_rejection():
    """Settlement proofs (chunk-in-task, then task-in-block) verify for
    every worker of every task; forgeries at each level (chunk record,
    in-task sibling, task sibling) and malformed attacker-supplied shapes
    are rejected, never raised on."""
    rng = np.random.default_rng(11)
    led = Ledger()
    cs = {f"t{i}": _contract(led, f"t{i}", 12 + 4 * i, chunk=2)
          for i in range(3)}
    work = [TaskRoundWork(tid, c, 0, rng.random(c.num_workers))
            for tid, c in cs.items()]
    blk, _, errors = settle_tasks_block(led, work, timestamp=1.0)
    assert not errors
    task_path_len = (len(cs) - 1).bit_length()
    for tid, c in cs.items():
        for w in range(0, c.num_workers, 5):
            proof = c.settlement_proof(0, w)
            assert c.verify_settlement(proof)
            assert proof["root"] == blk.records_root
            assert len(proof["proof"]) >= task_path_len
            # chunk-level forgery
            assert not c.verify_settlement(dict(proof, leaf=b"\x01" * 40))
            # in-task forgery: the proof's head sits inside the task's tree
            doctored = list(proof["proof"])
            side, _ = doctored[0]
            doctored[0] = (side, "00" * 32)
            assert not c.verify_settlement(dict(proof, proof=doctored))
            # task-level forgery: the proof's tail crosses tasks
            doctored = list(proof["proof"])
            side, _ = doctored[-1]
            doctored[-1] = (side, "00" * 32)
            assert not c.verify_settlement(dict(proof, proof=doctored))
            # malformed shapes are rejected, never raised on
            assert not c.verify_settlement(dict(proof, proof=[("L", "zz")]))
            assert not c.verify_settlement(dict(proof, chunk=5))
            assert not c.verify_settlement(dict(proof, offset=-1))
            assert not c.verify_settlement({})
        # a worker of task A cannot replay its proof against task B's
        # record indices
        other = cs["t0"] if tid != "t0" else cs["t1"]
        p = c.settlement_proof(0, 1)
        assert not other.verify_settlement(
            dict(p, record=dict(p["record"], worker=99)))


def test_settle_tasks_block_rejects_duplicate_task_ids():
    led = Ledger()
    c = _contract(led, "t", 4)
    w = TaskRoundWork("t", c, 0, np.zeros(4))
    with pytest.raises(ValueError):
        settle_tasks_block(led, [w, w], timestamp=1.0)


# -- determinism ----------------------------------------------------------------


def test_cotenant_settlement_deterministic_across_runs_and_pools():
    """The same 2-task score stream seals byte-identical chains run to
    run (seed-reproducible ordering)."""
    def drive():
        rng = np.random.default_rng(5)
        led = Ledger()
        a = _contract(led, "a", 40, chunk=2)
        b = _contract(led, "b", 24, chunk=2)
        for r in range(4):
            _, _, errors = settle_tasks_block(
                led, [TaskRoundWork("a", a, r, rng.random(40)),
                      TaskRoundWork("b", b, r, rng.random(24))],
                timestamp=float(r + 1))
            assert not errors
        return [blk.hash for blk in led.blocks]

    assert drive() == drive()


# -- protocol-level: the ChainNode driver --------------------------------------


def _paper_setup():
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config

    cfg = get_config("paper-net")
    tc = TrainConfig(lr=0.01, momentum=0.5, optimizer="sgd", remat=False)
    fed = FederationConfig(num_clusters=2, workers_per_cluster=3,
                           trust_threshold=0.45, top_k_rewarded=3,
                           merkle_chunk_size=1)
    return cfg, tc, fed


def test_single_task_node_bit_identical_to_serial_wrapper():
    """An N=1 node driven through the raw multi-task API (threaded,
    arbitrary task_id) seals the byte-identical chain — blocks, heads,
    penalties, payouts — as the serial single-task wrapper: multi-tenancy
    is invisible until a second task actually shares a block."""
    from repro.data.datasets import make_federated_mnist

    cfg, tc, fed = _paper_setup()
    ds = make_federated_mnist(6, samples=768, seed=5)
    serial = SDFLBProtocol(
        cfg, dataclasses.replace(fed, pipeline_depth=0), tc,
        use_blockchain=True, seed=11)
    for _ in range(6):
        serial.run_round(ds.round_batches(32))
    serial_pay = serial.finalize()

    ds = make_federated_mnist(6, samples=768, seed=5)
    node = ChainNode(pipeline_depth=3)
    task = node.create_task("an-arbitrary-name", cfg, fed, tc, seed=11)
    for _ in range(6):
        node.run_tick({"an-arbitrary-name": ds.round_batches(32)})
    node.flush()
    payouts = node.finalize()

    assert [b.hash for b in node.ledger.blocks[:-1]] \
        == [b.hash for b in serial.ledger.blocks[:-1]]
    assert [tuple(r.heads) for r in task.history] \
        == [tuple(r.heads) for r in serial.history]
    np.testing.assert_array_equal(
        np.stack([r.penalties for r in task.history]),
        np.stack([r.penalties for r in serial.history]))
    assert payouts["an-arbitrary-name"] == serial_pay
    assert node.ledger.verify_chain(deep=True)


def test_multi_task_node_end_to_end_heterogeneous_cadences():
    """Three heterogeneous tasks (different W, chunk sizes, cadences) on
    one node: all progress (starvation-free), co-tenant ticks seal
    multi-task blocks and solo ticks the single-task layout, the chain
    deep-verifies through every task, per-task value is conserved, and
    the shared IPFS store attributes per-owner usage."""
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.data.datasets import make_federated_mnist

    cfg = get_config("paper-net")
    tc = TrainConfig(lr=0.01, momentum=0.5, optimizer="sgd", remat=False)
    node = ChainNode(pipeline_depth=2)
    feds = {
        "mnist-a": FederationConfig(num_clusters=1, workers_per_cluster=3,
                                    trust_threshold=0.3, top_k_rewarded=2,
                                    merkle_chunk_size=2),
        "mnist-b": FederationConfig(num_clusters=2, workers_per_cluster=2,
                                    trust_threshold=0.4, top_k_rewarded=3,
                                    merkle_chunk_size=1),
        "mnist-c": FederationConfig(num_clusters=1, workers_per_cluster=2,
                                    trust_threshold=0.2, top_k_rewarded=1,
                                    merkle_chunk_size=4),
    }
    cadence = {"mnist-a": 1, "mnist-b": 2, "mnist-c": 3}
    tasks = {tid: node.create_task(tid, cfg, fed, tc, seed=i)
             for i, (tid, fed) in enumerate(feds.items())}
    data = {tid: make_federated_mnist(t.W, samples=512, seed=i)
            for i, (tid, t) in enumerate(tasks.items())}
    ticks = 6
    for t in range(ticks):
        node.run_tick({tid: data[tid].round_batches(16)
                       for tid in tasks if t % cadence[tid] == 0})
    node.flush()
    for tid, task in tasks.items():
        assert len(task.history) == sum(
            1 for t in range(ticks) if t % cadence[tid] == 0)
        assert all(r.settled for r in task.history)
    blocks = node.ledger.blocks[1:]
    multi = [b for b in blocks if b.task_roots]
    solo = [b for b in blocks if b.task_roots is None]
    assert multi and solo                      # both layouts exercised
    assert set(multi[0].task_roots) == set(feds)   # tick 0: all three fire
    assert node.ledger.verify_chain(deep=True)
    # two-level proof out of a genuinely multi-task block
    a = tasks["mnist-a"].contract
    proof = a.settlement_proof(0, 1)
    assert proof["block_index"] == multi[0].index
    assert a.verify_settlement(proof)
    doctored = list(proof["proof"])
    doctored[-1] = (doctored[-1][0], "00" * 32)
    assert not a.verify_settlement(dict(proof, proof=doctored))
    # shared store attributes per-task usage
    assert node.ipfs.puts_by_owner == {
        tid: len(tasks[tid].history) for tid in tasks}
    payouts = node.finalize()
    assert set(payouts) == set(feds)
    for tid, task in tasks.items():
        expect = feds[tid].requester_deposit \
            + task.W * feds[tid].worker_stake
        assert abs(task.contract.total_value() - expect) < 1e-6


def test_task_joining_running_node_is_deterministic():
    """create_task on a running node drains in-flight ticks first, so the
    joining task's round-0 randomness derives from a deterministic chain
    head: re-driving the same program seals byte-identical chains."""
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.data.datasets import make_federated_mnist

    cfg = get_config("paper-net")
    tc = TrainConfig(lr=0.01, momentum=0.5, optimizer="sgd", remat=False)
    fed = FederationConfig(num_clusters=1, workers_per_cluster=2,
                           trust_threshold=0.2)

    def drive():
        node = ChainNode(pipeline_depth=2)
        a = node.create_task("early", cfg, fed, tc, seed=0)
        ds = make_federated_mnist(2, samples=256, seed=0)
        for _ in range(3):
            node.run_tick({"early": ds.round_batches(16)})
        b = node.create_task("late", cfg, fed, tc, seed=1)
        # registration drained the pipeline: every prior round is settled
        assert all(r.settled for r in a.history)
        ds2 = make_federated_mnist(2, samples=256, seed=1)
        for _ in range(3):
            node.run_tick({"early": ds.round_batches(16),
                           "late": ds2.round_batches(16)})
        node.flush()
        hashes = [blk.hash for blk in node.ledger.blocks]
        heads = [tuple(r.heads) for r in b.history]
        node.close()
        return hashes, heads

    assert drive() == drive()


def test_task_failure_isolated_and_error_names_task_and_round():
    """Regression: a failing round computation aborts only its own task's
    round — the raised TaskSettlementError carries the task_id AND the
    round index (the settle failure used to report only the round), the
    co-tenant keeps settling and finalizes normally, and the failed
    task's state/chain lane stays exactly as before the failing round."""
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.data.datasets import make_federated_mnist

    cfg = get_config("paper-net")
    tc = TrainConfig(lr=0.01, momentum=0.5, optimizer="sgd", remat=False)
    fed = FederationConfig(num_clusters=1, workers_per_cluster=3,
                           trust_threshold=0.2, merkle_chunk_size=1)
    node = ChainNode(pipeline_depth=2)
    a = node.create_task("task-a", cfg, fed, tc, seed=0)
    b = node.create_task("task-b", cfg, fed, tc, seed=1)
    dsa = make_federated_mnist(3, samples=256, seed=0)
    dsb = make_federated_mnist(3, samples=256, seed=1)

    orig = a.contract.compute_round

    def failing_compute(round_index, *args, **kwargs):
        if round_index >= 1:
            raise RuntimeError("round computation died")
        return orig(round_index, *args, **kwargs)

    a.contract.compute_round = failing_compute
    node.run_tick({"task-a": dsa.round_batches(16),
                   "task-b": dsb.round_batches(16)})
    stake_before = a.contract.stake.copy()     # settled through round 0
    with pytest.raises(TaskSettlementError) as ei:
        for _ in range(3):
            node.run_tick({"task-a": dsa.round_batches(16),
                           "task-b": dsb.round_batches(16)})
    err = ei.value
    assert err.task_id == "task-a" and err.round_index == 1
    assert "'task-a'" in str(err) and "round 1" in str(err)
    assert isinstance(err, RuntimeError)       # wrapper-compatible
    # the co-tenant's round from the partially-failed tick was still
    # recorded and queued — only the poisoned task's round is dropped
    ticks_b_ran = len(b.history)
    assert ticks_b_ran > len(a.history)
    # the co-tenant keeps going: drop the poisoned task and drive on
    for _ in range(2):
        node.run_tick({"task-b": dsb.round_batches(16)})
    assert len(b.history) == ticks_b_ran + 2
    node.drain()                               # raises only node-fatal
    assert all(r.settled for r in b.history)
    with pytest.raises(TaskSettlementError):   # sticky, per task
        node.run_tick({"task-a": dsa.round_batches(16)})
    with pytest.raises(TaskSettlementError):
        node.flush()
    assert node.task_errors.keys() == {"task-a"}
    # task-a's lane froze before round 1: stakes untouched, round-1+ rounds
    # of task-a absent from every block, while task-b kept committing
    np.testing.assert_array_equal(a.contract.stake, stake_before)
    assert a.contract._round_blocks.keys() == {0}
    assert len(b.contract._round_blocks) == len(b.history)
    a_round0_settled = a.history[0].settled
    assert a_round0_settled and not any(r.settled for r in a.history[1:])
    payouts = node.finalize()                  # skips the poisoned task
    assert set(payouts) == {"task-b"}
    assert node.ledger.verify_chain(deep=True)


def _decision_trace(proto):
    return {
        "blocks": [b.hash for b in proto.ledger.blocks],
        "heads": [tuple(r.heads) for r in proto.history],
        "penalties": np.stack([r.penalties for r in proto.history]),
        "cids": [r.model_cid for r in proto.history],
    }


@pytest.mark.parametrize("depth", [1, 2])
def test_pipelined_settler_matches_inline_driver(depth):
    """Property: the settler thread (pipeline_depth ∈ {1, 2}) produces
    byte-identical chains, elections, penalties, model cids and payouts to
    the inline reference driver (depth 0) on the same data."""
    from repro.data.datasets import make_federated_mnist

    cfg, tc, fed = _paper_setup()
    runs = {}
    for d in (0, depth):
        ds = make_federated_mnist(6, samples=768, seed=5)
        proto = SDFLBProtocol(cfg, dataclasses.replace(fed, pipeline_depth=d),
                              tc, use_blockchain=True, seed=11)
        for _ in range(6):
            proto.run_round(ds.round_batches(32))
        proto.flush()
        payouts = proto.finalize()
        assert proto.ledger.verify_chain(deep=True)
        runs[d] = (_decision_trace(proto), payouts)
    inline, piped = runs[0], runs[depth]
    assert inline[0]["blocks"] == piped[0]["blocks"]      # byte-identical
    assert inline[0]["heads"] == piped[0]["heads"]
    assert inline[0]["cids"] == piped[0]["cids"]
    np.testing.assert_array_equal(inline[0]["penalties"],
                                  piped[0]["penalties"])
    assert inline[1] == piped[1]                          # payouts


def test_failed_round_computation_is_sticky_and_never_half_commits():
    """A round whose computation fails aborts with contract state and
    chain untouched, poisons the task for later rounds (sticky re-raise),
    and discards everything still queued."""
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.data.datasets import make_federated_mnist

    cfg = get_config("paper-net")
    tc = TrainConfig(lr=0.01, momentum=0.5, optimizer="sgd", remat=False)
    fed = FederationConfig(num_clusters=1, workers_per_cluster=6,
                           trust_threshold=0.2, merkle_chunk_size=1,
                           pipeline_depth=2)
    ds = make_federated_mnist(6, samples=256, seed=0)
    proto = SDFLBProtocol(cfg, fed, tc, use_blockchain=True, seed=0)

    def failing_compute(*args, **kwargs):
        raise RuntimeError("round computation died")

    proto.contract.compute_round = failing_compute
    stake_before = proto.contract.stake.copy()
    with pytest.raises(RuntimeError):
        for _ in range(4):
            proto.run_round(ds.round_batches(16))
    with pytest.raises(RuntimeError):
        proto.flush()
    with pytest.raises(RuntimeError):                     # sticky
        proto.flush()
    # nothing was applied or committed: genesis only, stakes untouched
    assert len(proto.ledger.blocks) == 1
    np.testing.assert_array_equal(proto.contract.stake, stake_before)
    assert proto.contract.requester_balance == 0.0
