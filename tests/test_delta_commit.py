"""Sparse delta commits: incremental Merkle updates vs. full rebuilds.

The delta path's whole consensus claim is *bit-identity*: a chain of
``DeltaCommit``s must commit exactly the roots a dense rebuild over the
same records would — for any change sets and chunk sizes — while hashing
only the dirty paths. These
properties pin that, plus the audit surface the paper's reliability story
needs: idle workers stay proof-covered and tamper-evident in every delta
block.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.chain.contract import TrustContract, _RECORD_DTYPE
from repro.chain.ledger import (DeltaCommit, DenseCommit, Ledger, MerkleTree,
                                RecordBatch, batch_leaf_digests,
                                gathered_leaf_digests)

REC = _RECORD_DTYPE.itemsize


def _batch(rng, n):
    buf = rng.integers(0, 256, n * REC, dtype=np.uint8)
    return buf, RecordBatch(memoryview(buf).cast("B"), REC)


def _mk_contract(sparse=True, chunk=8, rebase=0, W=60, seed=3):
    led = Ledger()
    c = TrustContract(led, requester_deposit=1e3, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=3,
                      merkle_chunk_size=chunk,
                      sparse_settlement=sparse, sparse_rebase_every=rebase)
    c.join_batch(W)
    return led, c


# -- batched leaf hashing: byte-identical digests ------------------------------


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 200), k=st.integers(1, 70), seed=st.integers(0, 99))
def test_batched_leaf_digests_match_per_leaf_hasher(n, k, seed):
    """The framed single-call hasher is a pure performance change: digests
    (and hence roots/proofs) are byte-identical to the incremental
    two-update ``_leaf_digest`` path and to a list-of-bytes tree."""
    from repro.chain.ledger import _leaf_digest
    rng = np.random.default_rng(seed)
    _, rb = _batch(rng, n)
    ref = [_leaf_digest(rb.chunk_bytes(i, min(i + k, n)))
           for i in range(0, n, k)]
    assert batch_leaf_digests(rb, k) == ref
    assert MerkleTree(rb, k).root == \
        MerkleTree([bytes(rb[i]) for i in range(n)], k).root
    sel = np.arange(len(ref))
    gathered = gathered_leaf_digests(rb, k, sel)
    assert [gathered[i] for i in range(len(ref))] == ref


# -- MerkleTree.update_leaves == rebuild ---------------------------------------


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 150), k=st.integers(1, 9),
       rounds=st.integers(1, 4), seed=st.integers(0, 99))
def test_update_leaves_bit_identical_to_rebuild(n, k, rounds, seed):
    rng = np.random.default_rng(seed)
    recs = [bytes(rng.integers(0, 256, REC, dtype=np.uint8))
            for _ in range(n)]
    t = MerkleTree(recs, k)
    for _ in range(rounds):
        nchg = int(rng.integers(1, n + 1))
        idx = [int(i) for i in rng.choice(n, size=nchg, replace=False)]
        for i in idx:
            recs[i] = bytes(rng.integers(0, 256, REC, dtype=np.uint8))
        t.update_leaves({li: b"".join(recs[li * k:min(li * k + k, n)])
                         for li in {i // k for i in idx}})
        assert t.root == MerkleTree(recs, k).root


# -- DeltaCommit roots == full rebuild (the tentpole property) -----------------


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 120), k=st.integers(1, 9),
       rounds=st.integers(1, 5), seed=st.integers(0, 99))
def test_delta_roots_bit_identical_across_change_sets(n, k, rounds, seed):
    """Delta-commit roots equal full-rebuild roots across random change
    sets × chunk sizes — and equal the ``DenseCommit`` root, so a delta
    block is indistinguishable (by root) from a dense commit over the
    same population."""
    rng = np.random.default_rng(seed)
    cur, rb = _batch(rng, n)
    cur = cur.reshape(n, REC)
    commit = DeltaCommit.full(rb, k)
    for _ in range(rounds):
        nchg = int(rng.integers(0, n + 1))
        idx = np.sort(rng.choice(n, size=nchg, replace=False)
                      ).astype(np.int64)
        rows = rng.integers(0, 256, nchg * REC, dtype=np.uint8)
        cur = cur.copy()
        if nchg:
            cur[idx] = rows.reshape(nchg, REC)
        commit = DeltaCommit.delta(
            commit, idx, RecordBatch(memoryview(rows).cast("B"), REC))
        dense = RecordBatch(memoryview(np.ascontiguousarray(cur)).cast("B"),
                            REC)
        flat_root = MerkleTree(dense, k).root
        assert commit.root == flat_root
        assert DenseCommit(dense, k).root == flat_root
        assert commit.recompute_root() == flat_root
        # every record — changed or inherited — is proof-covered
        i = int(rng.integers(0, n))
        chunk, off = commit.record_chunk(i)
        assert chunk[off] == bytes(cur[i].tobytes())
        assert MerkleTree.verify(b"".join(chunk), commit.record_proof(i),
                                 commit.root)


def test_delta_rejects_malformed_change_sets():
    rng = np.random.default_rng(0)
    _, rb = _batch(rng, 16)
    base = DeltaCommit.full(rb, 4)
    rows = rng.integers(0, 256, 2 * REC, dtype=np.uint8)
    nr = RecordBatch(memoryview(rows).cast("B"), REC)
    with pytest.raises(ValueError):
        DeltaCommit.delta(base, np.array([3, 1]), nr)      # unsorted
    with pytest.raises(ValueError):
        DeltaCommit.delta(base, np.array([1, 1]), nr)      # duplicate
    with pytest.raises(IndexError):
        DeltaCommit.delta(base, np.array([1, 16]), nr)     # out of range
    with pytest.raises(ValueError):
        DeltaCommit.delta(base, np.array([1]), nr)         # length mismatch
    with pytest.raises(TypeError):
        DeltaCommit(rb, 4)          # must go through .full/.delta


def test_empty_change_set_keeps_root():
    rng = np.random.default_rng(1)
    _, rb = _batch(rng, 10)
    base = DeltaCommit.full(rb, 4)
    d = DeltaCommit.delta(base, np.zeros(0, np.int64),
                          RecordBatch(b"", REC))
    assert d.root == base.root and d.hash_ops == 0
    assert d.recompute_root() == base.root


# -- contract-level: sparse == dense Algorithm-1 state -------------------------


def test_sparse_contract_matches_dense_state_and_proofs():
    """Ten rounds of random partial participation: the sparse contract's
    Algorithm-1 state (stakes, penalties, requester transfer, conservation)
    is bit-identical to the dense contract fed the same subsets, the chain
    deep-verifies, and every round's block proves active AND idle
    workers."""
    rng = np.random.default_rng(7)
    W = 60
    led_d, cd = _mk_contract(sparse=False, W=W)
    led_s, cs = _mk_contract(sparse=True, rebase=4, W=W)
    for r in range(10):
        if r == 0:
            ids, s = None, rng.random(W)
        else:
            ids = rng.choice(W, size=int(rng.integers(1, 20)),
                             replace=False).astype(np.int64)
            s = rng.random(len(ids))
        pd = cd.settle_round_batch(r, s, worker_ids=ids, timestamp=float(r))
        ps = cs.settle_round_batch(r, s, worker_ids=ids, timestamp=float(r))
        np.testing.assert_array_equal(pd, ps)
    np.testing.assert_array_equal(cd.stake, cs.stake)
    np.testing.assert_array_equal(cd.penalized_rounds, cs.penalized_rounds)
    assert cd.requester_balance == cs.requester_balance
    assert abs(cd.total_value() - cs.total_value()) < 1e-9
    assert led_s.verify_chain(deep=True)
    for r in (1, 4, 9):
        active = set(cs._round_ids[r].tolist())
        idle = next(w for w in range(W) if w not in active)
        for w in (next(iter(active)), idle):
            proof = cs.settlement_proof(r, w)
            assert cs.verify_settlement(proof)
            assert proof["record"]["worker"] == w
        # idle records carry the last round that actually settled them
        assert cs.settlement_proof(r, idle)["record"]["round"] < r


def test_idle_worker_record_tamper_evident_in_delta_block():
    """The reliability half of the tentpole: an idle worker's (inherited,
    unhashed-this-round) record in a delta block still fails verification
    when tampered, per-record and chain-deep."""
    rng = np.random.default_rng(11)
    W = 50
    led, c = _mk_contract(sparse=True, W=W)
    c.settle_round_batch(0, rng.random(W), timestamp=0.0)
    ids = np.array([2, 30, 47], np.int64)
    c.settle_round_batch(1, rng.random(3), worker_ids=ids, timestamp=1.0)
    blk = c._round_blocks[1]
    idle = 13
    assert led.verify_record(blk, idle)
    proof = c.settlement_proof(1, idle)
    assert c.verify_settlement(proof)
    led.tamper_record(blk, idle, b"forged-idle-record")
    assert not led.verify_record(blk, idle)
    assert not led.verify_chain(deep=True)
    # a forged proof (mutated record claim) is rejected too
    bad = dict(proof)
    rec = dict(bad["record"])
    rec["penalty"] = 0.0 if rec["penalty"] else 1.0
    bad["record"] = rec
    assert not c.verify_settlement(bad)


def test_sparse_rebase_bounds_delta_depth():
    """``sparse_rebase_every=N`` re-anchors with a dense commit every N
    sparse rounds; enrollment growth and full participation force one
    immediately."""
    rng = np.random.default_rng(5)
    W = 40
    led, c = _mk_contract(sparse=True, rebase=3, W=W, chunk=4)
    depths = []
    for r in range(8):
        ids = rng.choice(W, size=5, replace=False).astype(np.int64)
        c.settle_round_batch(r, rng.random(5), worker_ids=ids,
                             timestamp=float(r))
        depths.append(c._last_commit.depth)
    # anchor at r=0 (first), r=3, r=6 — depth never reaches the cap
    assert depths[0] == 0 and max(depths) < 3
    assert depths[3] == 0 and depths[6] == 0
    # enrollment growth forces a fresh anchor covering the larger W
    c.join_batch(10)
    c.settle_round_batch(8, rng.random(5),
                         worker_ids=np.arange(5, dtype=np.int64),
                         timestamp=8.0)
    assert c._last_commit.depth == 0 and len(c._last_commit) == W + 10
    # full participation re-anchors too
    c.settle_round_batch(9, rng.random(W + 10), timestamp=9.0)
    assert c._last_commit.depth == 0
    assert led.verify_chain(deep=True)


def test_sparse_unsorted_ids_penalties_in_caller_order():
    rng = np.random.default_rng(9)
    led, c = _mk_contract(sparse=True, W=30)
    c.settle_round_batch(0, rng.random(30), timestamp=0.0)
    ids = np.array([20, 3, 11], np.int64)
    s = np.array([0.9, 0.1, 0.8])
    pen = c.settle_round_batch(1, s, worker_ids=ids, timestamp=1.0)
    assert pen[1] > 0 and pen[0] == 0 and pen[2] == 0
    assert led.verify_chain(deep=True)


# -- store quota (satellite) ---------------------------------------------------


def test_ipfs_owner_quota_enforced_atomically():
    from repro.chain.ipfs import IPFSStore, QuotaExceeded
    st_free = IPFSStore()                      # default: unlimited
    blob = {"w": np.arange(512, dtype=np.float32)}
    cid = st_free.put_tree(blob, owner="a")
    size = st_free.bytes_by_owner["a"]
    st_cap = IPFSStore(owner_quota_bytes=int(size * 2.5))
    assert st_cap.put_tree(blob, owner="a") == cid
    # dedup'd identical put still counts logical bytes against the owner
    st_cap.put_tree(blob, owner="a")
    assert st_cap.bytes_by_owner["a"] == 2 * size
    assert st_cap.dedup_hits == 1
    with pytest.raises(QuotaExceeded) as ei:
        st_cap.put_tree(blob, owner="a")
    # atomic rejection: nothing was counted, stored, or attributed
    assert st_cap.bytes_by_owner["a"] == 2 * size
    assert st_cap.puts == 2
    assert ei.value.owner == "a" and ei.value.quota == int(size * 2.5)
    # other owners (and anonymous puts) are unaffected
    st_cap.put_tree(blob, owner="b")
    st_cap.put_tree(blob)
    with pytest.raises(ValueError):
        IPFSStore(owner_quota_bytes=-1)
