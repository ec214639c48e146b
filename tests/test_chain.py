"""Blockchain substrate: ledger integrity, contract (Algorithm 1)
correctness + conservation properties, IPFS content addressing, and the
array-native batch settlement path (batch-vs-scalar equivalence, Merkle
commitments, 100k-worker scaling)."""
import time

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.chain.contract import (ContractError, TrustContract,
                                  decode_settlement_record)
from repro.chain.ipfs import IPFSStore
from repro.chain.ledger import Ledger, MerkleTree


def test_ledger_chain_verifies_and_detects_tampering():
    led = Ledger()
    led.append_block([{"type": "x", "v": 1}])
    led.append_block([{"type": "y", "v": 2}])
    assert led.verify_chain()
    led.blocks[1].transactions[0]["v"] = 999       # tamper
    assert not led.verify_chain()


def test_ledger_randomness_deterministic():
    a, b = Ledger(), Ledger()
    a.append_block([{"t": 1}], timestamp=1.0)
    b.append_block([{"t": 1}], timestamp=1.0)
    assert a.randomness(3) == b.randomness(3)
    assert a.randomness(3) != a.randomness(4)


def test_ipfs_roundtrip_and_tamper_detection():
    store = IPFSStore()
    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": jnp.ones((5,), jnp.bfloat16)}
    cid = store.put_tree(tree)
    leaves = store.get_leaves(cid)
    np.testing.assert_allclose(leaves[1], tree["w"])   # dict order: b, w
    store.tamper(cid, b"garbage")
    with pytest.raises(ValueError):
        store.get_leaves(cid)


def test_contract_algorithm1_steps():
    led = Ledger()
    c = TrustContract(led, requester_deposit=100.0, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=2)
    for w in ["w0", "w1", "w2"]:
        c.join(w)
    pens = c.settle_round(0, {"w0": 0.9, "w1": 0.4, "w2": 0.6}, "cid0")
    # Pen(w) = F·P/100 = 10·50/100 = 5 for the one bad worker
    assert pens == {"w1": 5.0}
    assert c.workers["w1"].stake == 5.0
    assert c.requester_balance == 5.0
    payouts = c.finalize()
    # refunds: w0 10, w1 5, w2 10 ; rewards: top-2 (w0, w2) split 100
    assert payouts["w0"] == 10.0 + 50.0
    assert payouts["w1"] == 5.0
    assert payouts["w2"] == 10.0 + 50.0
    assert led.verify_chain()


def test_contract_rejects_unknown_and_double_finalize():
    c = TrustContract(Ledger(), requester_deposit=10, worker_stake=1,
                      penalty_pct=10, trust_threshold=0.5, top_k=1)
    c.join("a")
    with pytest.raises(ContractError):
        c.settle_round(0, {"ghost": 1.0})
    c.finalize()
    with pytest.raises(ContractError):
        c.finalize()


@settings(max_examples=40, deadline=None)
@given(
    n_workers=st.integers(1, 12),
    deposit=st.floats(1.0, 1e4),
    stake=st.floats(0.1, 100.0),
    pct=st.floats(0.0, 100.0),
    threshold=st.floats(0.0, 1.0),
    k=st.integers(1, 12),
    rounds=st.integers(1, 5),
    seed=st.integers(0, 2**31),
)
def test_contract_value_conservation(n_workers, deposit, stake, pct,
                                     threshold, k, rounds, seed):
    """Property: total value (pool + requester + stakes + balances) is
    conserved through any score sequence; stakes never go negative."""
    rng = np.random.default_rng(seed)
    c = TrustContract(Ledger(), requester_deposit=deposit, worker_stake=stake,
                      penalty_pct=pct, trust_threshold=threshold, top_k=k)
    for w in range(n_workers):
        c.join(f"w{w}")
    total0 = c.total_value()
    for r in range(rounds):
        scores = {f"w{w}": float(rng.random()) for w in range(n_workers)}
        c.settle_round(r, scores)
        assert abs(c.total_value() - total0) < 1e-6 * max(total0, 1)
        assert all(a.stake >= -1e-9 for a in c.workers.values())
    c.finalize()
    assert abs(c.total_value() - total0) < 1e-6 * max(total0, 1)
    # after finalize all stakes are zero (everything refunded/penalized)
    assert all(a.stake == 0.0 for a in c.workers.values())


# -- array-native batch settlement -------------------------------------------

class ReferenceContract:
    """Seed-faithful scalar Algorithm 1 (per-worker dict loops) — the oracle
    the vectorized batch path must match exactly."""

    def __init__(self, deposit, stake, pct, threshold, k):
        self.F, self.P, self.T, self.k = stake, pct, threshold, k
        self.reward_pool = deposit
        self.requester_balance = 0.0
        self.accts = {}       # name -> [stake, balance, penalized, scores]

    def join(self, name):
        self.accts[name] = [self.F, 0.0, 0, []]

    def settle_round(self, scores):
        penalties = {}
        for wid, s in sorted(scores.items()):
            a = self.accts[wid]
            a[3].append(float(s))
            if s < self.T:
                pen = min(self.F * self.P / 100.0, a[0])
                a[0] -= pen
                a[2] += 1
                self.requester_balance += pen
                penalties[wid] = pen
        return penalties

    def finalize(self):
        payouts = {}
        for wid, a in sorted(self.accts.items()):
            payouts[wid] = a[0]
            a[1] += a[0]
            a[0] = 0.0
        ranked = sorted(self.accts,
                        key=lambda w: (sum(self.accts[w][3]) /
                                       max(len(self.accts[w][3]), 1)),
                        reverse=True)
        top = ranked[: self.k]
        if top:
            share = self.reward_pool / len(top)
            for wid in top:
                self.accts[wid][1] += share
                payouts[wid] += share
            self.reward_pool = 0.0
        return payouts

    def total_value(self):
        return (self.reward_pool + self.requester_balance +
                sum(a[0] + a[1] for a in self.accts.values()))


@settings(max_examples=30, deadline=None)
@given(
    n_workers=st.integers(1, 24),
    deposit=st.floats(1.0, 1e4),
    stake=st.floats(0.1, 100.0),
    pct=st.floats(0.0, 100.0),
    threshold=st.floats(0.0, 1.0),
    k=st.integers(1, 24),
    rounds=st.integers(1, 5),
    subset=st.booleans(),
    seed=st.integers(0, 2**31),
)
def test_batch_settlement_matches_scalar_reference(n_workers, deposit, stake,
                                                   pct, threshold, k, rounds,
                                                   subset, seed):
    """Property: the vectorized settle_round_batch + finalize produce
    penalties, payouts, stakes, penalized_rounds, and total_value identical
    to the seed's per-worker scalar loops on random score matrices (full
    rounds and random partial-participation rounds)."""
    rng = np.random.default_rng(seed)
    c = TrustContract(Ledger(), requester_deposit=deposit, worker_stake=stake,
                      penalty_pct=pct, trust_threshold=threshold, top_k=k)
    ref = ReferenceContract(deposit, stake, pct, threshold, k)
    ids = c.join_batch(n_workers)
    names = [c.worker_name(i) for i in ids]
    for n in names:
        ref.join(n)
    total0 = c.total_value()
    for r in range(rounds):
        if subset and n_workers > 1:
            m = int(rng.integers(1, n_workers + 1))
            sel = np.sort(rng.choice(n_workers, size=m, replace=False))
        else:
            sel = np.arange(n_workers)
        s = rng.random(len(sel))
        pen_vec = c.settle_round_batch(r, s, worker_ids=sel)
        ref_pen = ref.settle_round({names[w]: float(v)
                                    for w, v in zip(sel, s)})
        got_pen = {names[w]: float(p)
                   for w, p, v in zip(sel, pen_vec, s) if v < threshold}
        assert set(got_pen) == set(ref_pen)
        for n_ in ref_pen:
            assert got_pen[n_] == pytest.approx(ref_pen[n_], abs=1e-12)
        assert c.requester_balance == pytest.approx(ref.requester_balance)
        assert abs(c.total_value() - total0) < 1e-6 * max(total0, 1)
    for i, n_ in enumerate(names):
        assert c.workers[n_].stake == pytest.approx(ref.accts[n_][0])
        assert c.workers[n_].penalized_rounds == ref.accts[n_][2]
        assert c.workers[i].scores == ref.accts[n_][3]
    pay = c.finalize()
    ref_pay = ref.finalize()
    assert set(pay) == set(ref_pay)
    for n_ in pay:
        assert pay[n_] == pytest.approx(ref_pay[n_], abs=1e-9)
    assert c.total_value() == pytest.approx(ref.total_value())
    assert abs(c.total_value() - total0) < 1e-6 * max(total0, 1)


def test_merkle_tree_roots_and_proofs():
    for n in (1, 2, 3, 5, 8, 13):
        leaves = [f"leaf-{i}".encode() for i in range(n)]
        t = MerkleTree(leaves)
        for i, leaf in enumerate(leaves):
            assert MerkleTree.verify(leaf, t.proof(i), t.root)
            assert not MerkleTree.verify(b"forged", t.proof(i), t.root)
        if n > 1:   # a proof for one index never validates another's leaf
            assert not MerkleTree.verify(leaves[0], t.proof(1), t.root)
    with pytest.raises(ValueError):
        MerkleTree([])


def test_batched_block_merkle_audit_and_tamper_detection():
    led = Ledger()
    c = TrustContract(led, requester_deposit=100.0, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=2)
    c.join_batch(6)
    scores = np.array([0.9, 0.4, 0.6, 0.2, 0.8, 0.55])
    pen = c.settle_round_batch(0, scores, model_cid="cid0")
    np.testing.assert_allclose(pen, [0, 5.0, 0, 5.0, 0, 0])
    assert led.verify_chain(deep=True)
    # every worker's settlement is individually auditable in O(log W)
    for w in range(6):
        proof = c.settlement_proof(0, w)
        assert c.verify_settlement(proof)
        assert len(proof["proof"]) <= 3           # ceil(log2(6))
        rec = proof["record"]
        assert rec["worker"] == w
        assert rec["score"] == pytest.approx(scores[w])
        assert rec["penalty"] == pytest.approx(pen[w])
    # proofs also accept string worker names (legacy id scheme)
    assert c.verify_settlement(c.settlement_proof(0, "worker-3"))
    # round-trip decode of the canonical leaf encoding
    blk = led.blocks[-1]
    rec0 = decode_settlement_record(led.record_batch(blk.index)[1])
    assert rec0 == {"round": 0, "worker": 1, "score": pytest.approx(0.4),
                    "penalty": pytest.approx(5.0),
                    "stake_after": pytest.approx(5.0), "staleness": 0}
    # tampering with an off-chain record breaks deep verification and the
    # record's proof, while the block hash chain itself stays intact
    led.tamper_record(blk.index, 1, b"x" * 40)
    assert led.verify_chain() and not led.verify_chain(deep=True)
    assert not led.verify_record(blk.index, 1)
    # tampering with the committed root breaks the shallow chain too
    blk.records_root = "0" * 64
    assert not led.verify_chain()


def test_settle_round_batch_validates_inputs():
    c = TrustContract(Ledger(), requester_deposit=10, worker_stake=1,
                      penalty_pct=10, trust_threshold=0.5, top_k=1)
    c.join_batch(4)
    with pytest.raises(ContractError):          # wrong length
        c.settle_round_batch(0, np.zeros(3))
    with pytest.raises(ContractError):          # unknown id
        c.settle_round_batch(0, np.zeros(1), worker_ids=np.array([9]))
    with pytest.raises(ContractError):          # duplicate ids
        c.settle_round_batch(0, np.zeros(2), worker_ids=np.array([1, 1]))
    c.finalize()
    with pytest.raises(ContractError):          # closed task
        c.settle_round_batch(1, np.zeros(4))


@pytest.mark.parametrize("sparse", [False, True])
def test_compute_round_mutates_nothing(sparse):
    """``compute_round`` is pure: contract state, pending transactions and
    the chain are unchanged until ``apply_round`` — what lets a failing
    task drop out of a multi-task block with nothing applied."""
    led = Ledger()
    c = TrustContract(led, requester_deposit=100.0, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=2,
                      merkle_chunk_size=4, sparse_settlement=sparse,
                      staleness_alpha=0.5)
    c.join_batch(24)
    rng = np.random.default_rng(6)
    c.settle_round_batch(0, rng.random(24), timestamp=1.0)
    before = c.snapshot()
    heads = [b.hash for b in led.blocks]
    ids = rng.permutation(24)[:9]
    rs = c.compute_round(1, rng.random(9), worker_ids=ids,
                         staleness=rng.integers(0, 3, 9))
    after = c.snapshot()
    for key, val in before.items():
        if isinstance(val, np.ndarray):
            np.testing.assert_array_equal(after[key], val)
        else:
            assert after[key] == val, key
    assert [b.hash for b in led.blocks] == heads
    # applying it then settles exactly as the one-call path would
    seal = c.apply_round(rs)
    assert seal.commit is not None and len(seal.penalties) == 9
    assert (c.stake[ids] <= before["stake"][ids]).all()


def test_settlement_scales_to_100k_workers_under_1s():
    """Acceptance: chain-only settlement at W=100,000 completes a full round
    (vectorized Algorithm 1 + Merkle commit + block seal) in < 1s on CPU."""
    W = 100_000
    led = Ledger()
    c = TrustContract(led, requester_deposit=1e6, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=100)
    c.join_batch(W)
    scores = np.random.default_rng(0).random(W)
    t0 = time.monotonic()
    pen = c.settle_round_batch(0, scores)
    dt = time.monotonic() - t0
    assert dt < 1.0, f"100k-worker settlement took {dt:.2f}s"
    assert pen.shape == (W,)
    bad = int((scores < 0.5).sum())
    assert int((pen > 0).sum()) == bad
    assert c.requester_balance == pytest.approx(bad * 5.0)
    # spot-audit one worker without rehashing the round: the node path is
    # over chunk leaves (64 records each), so ceil(log2(ceil(100k/64)))
    proof = c.settlement_proof(0, 31_337)
    assert c.verify_settlement(proof)
    import math
    assert len(proof["proof"]) == math.ceil(math.log2(math.ceil(W / 64)))
    assert len(proof["chunk"]) == 64
    assert proof["chunk"][proof["offset"]] == proof["leaf"]


def test_chunked_root_with_chunk_size_one_matches_per_record_root():
    """chunk_size=1 must reproduce the per-record tree bit-for-bit (and
    both must match an independent reimplementation of the hash rule)."""
    import hashlib
    records = [f"rec-{i}".encode() for i in range(7)]
    per_record = MerkleTree(records)               # default: one record/leaf
    chunk1 = MerkleTree(records, chunk_size=1)
    assert chunk1.root == per_record.root
    # independent recomputation of the 7-leaf root (promote-unpaired rule)
    lvl = [hashlib.sha256(b"\x00" + r).digest() for r in records]
    while len(lvl) > 1:
        nxt = [hashlib.sha256(b"\x01" + lvl[i] + lvl[i + 1]).digest()
               for i in range(0, len(lvl) - 1, 2)]
        if len(lvl) % 2:
            nxt.append(lvl[-1])
        lvl = nxt
    assert per_record.root == lvl[0].hex()
    # chunking changes the root (different leaf bytes) but not the records
    assert MerkleTree(records, chunk_size=3).root != per_record.root


@pytest.mark.parametrize("W,chunk_size", [
    pytest.param(10, k, id=str(k)) for k in (1, 3, 64, 10)] + [
    # one record, one short of / exactly / one past a power-of-two leaf
    # count at the benchmark cells' chunk, and a leaf wider than a page
    pytest.param(W, k, id=f"w{W}-k{k}")
    for W, k in ((1, 64), (1023, 64), (1024, 64), (1025, 64),
                 (4097, 4096))])
def test_chunked_proofs_verify_and_tampering_fails(W, chunk_size):
    """Across (W, chunk) shapes: every checked worker's settlement proof
    verifies, tampered records fail both the proof and deep chain
    verification, and hash work shrinks to ~2·ceil(W/k) nodes."""
    led = Ledger()
    c = TrustContract(led, requester_deposit=100.0, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=2,
                      merkle_chunk_size=chunk_size)
    c.join_batch(W)
    scores = np.linspace(0.05, 0.95, W)
    pen = c.settle_round_batch(0, scores)
    assert led.verify_chain(deep=True)
    n_leaves = -(-W // chunk_size)                 # ceil
    tree = led.commit(led.head.index).commit_for().tree
    assert tree.num_leaves == n_leaves
    # ~2n−1 (+ promoted odd nodes, one per level at most)
    assert tree.hash_ops <= 2 * n_leaves + len(tree.levels)
    # every worker of a small round; the edges of every leaf otherwise
    checked = range(W) if W <= 64 else sorted(
        {0, W - 1} | {i for j in range(0, W, chunk_size)
                      for i in (j - 1, j) if 0 <= i < W})
    for w in checked:
        proof = c.settlement_proof(0, w)
        assert c.verify_settlement(proof)
        assert len(proof["chunk"]) <= chunk_size
        rec = proof["record"]
        assert rec["worker"] == w
        assert rec["score"] == pytest.approx(scores[w])
        assert rec["penalty"] == pytest.approx(pen[w])
        assert led.verify_record(led.head.index, w)
        # a proof whose claimed record disagrees with its chunk is rejected
        forged = dict(proof, leaf=b"\x00" * len(proof["leaf"]))
        assert not c.verify_settlement(forged)
        # ... as is a doctored human-readable view over an authentic leaf,
        # and malformed offsets are rejected, not raised on
        assert not c.verify_settlement(
            dict(proof, record={**proof["record"], "score": 0.99}))
        assert not c.verify_settlement(dict(proof, offset=chunk_size + 99))
        assert not c.verify_settlement(dict(proof, offset=-1))
    # tamper one stored record: its proof and deep verification both break,
    # the shallow hash chain stays intact
    victim = W // 2
    led.tamper_record(led.head.index, victim, b"x" * 40)
    assert led.verify_chain() and not led.verify_chain(deep=True)
    assert not led.verify_record(led.head.index, victim)


@pytest.mark.parametrize("victim", ["first", "last"])
def test_deep_verify_rejects_tampered_edge_record(victim):
    """At W = 1,024 (chunk 64), deep verification rebuilds the round's tree
    and rejects a tampered first or last record, the records whose leaf
    sits on the tree's outer edge; the shallow hash chain stays valid."""
    W = 1024
    led = Ledger()
    c = TrustContract(led, requester_deposit=100.0, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=2,
                      merkle_chunk_size=64)
    c.join_batch(W)
    rng = np.random.default_rng(4)
    for r in range(2):
        c.settle_round_batch(r, rng.random(W), timestamp=float(r + 1))
    blk = c._round_blocks[0]
    assert led.verify_chain(deep=True)
    ri = 0 if victim == "first" else W - 1
    good = bytes(led.record_batch(blk)[ri])
    led.tamper_record(blk, ri, good[:-1] + bytes([good[-1] ^ 1]))
    assert led.verify_chain()
    assert not led.verify_chain(deep=True)
    assert not led.verify_record(blk, ri)
    # the untouched round still proves
    assert led.verify_record(c._round_blocks[1], ri)


def test_chunked_commit_hashes_fewer_nodes_and_same_settlement():
    """Chunked vs per-record commits: identical Algorithm 1 outcome,
    ~k-fold fewer ledger work units for the commit."""
    W = 512
    scores = np.random.default_rng(3).random(W)
    outs = {}
    for k in (1, 64):
        led = Ledger()
        c = TrustContract(led, requester_deposit=1e4, worker_stake=10.0,
                          penalty_pct=50.0, trust_threshold=0.5, top_k=8,
                          merkle_chunk_size=k)
        c.join_batch(W)
        pen = c.settle_round_batch(0, scores)
        outs[k] = (pen, c.stake.copy(), led.work_units)
    np.testing.assert_allclose(outs[1][0], outs[64][0])
    np.testing.assert_allclose(outs[1][1], outs[64][1])
    assert outs[64][2] < outs[1][2] / 8            # far fewer hash ops


def test_finalize_with_zero_top_k_pays_refunds_only():
    c = TrustContract(Ledger(), requester_deposit=50.0, worker_stake=5.0,
                      penalty_pct=10.0, trust_threshold=0.5, top_k=0)
    c.join_batch(3)
    c.settle_round_batch(0, np.array([0.9, 0.8, 0.7]))
    pay = c.finalize()
    assert pay == {"worker-0": 5.0, "worker-1": 5.0, "worker-2": 5.0}
    assert c.reward_pool == 50.0               # undistributed, conserved
    assert c.total_value() == pytest.approx(50.0 + 3 * 5.0)


def test_finalize_topk_tie_break_is_join_order():
    """Exact mean-score ties straddling the k boundary must resolve by join
    order (the legacy stable sort), not argpartition's arbitrary pick."""
    c = TrustContract(Ledger(), requester_deposit=90.0, worker_stake=1.0,
                      penalty_pct=0.0, trust_threshold=0.0, top_k=3)
    c.join_batch(6)
    c.settle_round_batch(0, np.array([0.5, 0.9, 0.5, 0.5, 0.2, 0.5]))
    pay = c.finalize()
    # top-3: worker 1 (0.9) then the first two tied 0.5s by join order (0, 2)
    rewarded = {n for n, p in pay.items() if p > 1.0}
    assert rewarded == {"worker-1", "worker-0", "worker-2"}


def test_settlement_proofs_with_out_of_order_rounds():
    """Audit bookkeeping is keyed by round index, so rounds settled out of
    order (async arrivals) still yield correct per-worker proofs."""
    c = TrustContract(Ledger(), requester_deposit=10.0, worker_stake=2.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=1)
    c.join_batch(4)
    c.settle_round_batch(5, np.array([0.9, 0.1]),
                         worker_ids=np.array([0, 1]))
    c.settle_round_batch(2, np.array([0.3, 0.8]),
                         worker_ids=np.array([2, 3]))
    for rnd, wid, score in ((5, 0, 0.9), (5, 1, 0.1), (2, 2, 0.3),
                            (2, 3, 0.8)):
        proof = c.settlement_proof(rnd, wid)
        assert c.verify_settlement(proof)
        assert proof["record"]["round"] == rnd
        assert proof["record"]["worker"] == wid
        assert proof["record"]["score"] == pytest.approx(score)
