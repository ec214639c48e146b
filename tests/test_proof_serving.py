"""Chain read path: unified ``SettlementProof``, batched multiproofs, and
the ``repro.serve`` server/light-client pair.

Pins (a) the unified proof surface verifying across every commit flavor
(dense, delta-overlay, multi-task) with the deprecated wrappers
emitting bit-identical proofs; (b) batched multiproof round-trips with
shared-path deduplication, and rejection of tampering at every level
(chunk bytes, shipped siblings, offsets, plan, root, and the stored
records themselves); (c) the light client's header-chain sync — full,
incremental, current-token, corrupt-header rejection — and stale-proof
re-anchoring; (d) bounded content-verified checkpoint streaming under
serve quotas; (e) the ``contract.legacy`` namespace and
DeprecationWarning shims; (f) lock-free reads while a writer settles."""
import hashlib
import threading

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.chain.contract import TrustContract
from repro.chain.ipfs import IPFSStore
from repro.chain.ledger import Ledger
from repro.chain.proofs import (ROOT_KEY, BlockHeader, SettlementProof,
                                build_proof_batch, header_of,
                                verify_proof_batch)
from repro.serve import (ChainReadServer, HeaderVerificationError,
                         LightClient, QuotaExceeded, RoundNotSettled,
                         StaleProofError)


def _contract(W, *, sparse=False, chunk=8, multi=None):
    c = TrustContract(Ledger(), requester_deposit=1e6, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5,
                      top_k=max(W // 4, 1), merkle_chunk_size=chunk,
                      sparse_settlement=sparse, task_id=multi)
    c.join_batch(W)
    return c


def _settle(c, rounds=2, seed=0, cohort=None):
    rng = np.random.default_rng(seed)
    W = c.num_workers
    for r in range(rounds):
        if cohort:
            ids = np.sort(rng.choice(W, cohort, replace=False)).astype(
                np.int64)
            c.settle_round_batch(r, rng.random(cohort), worker_ids=ids,
                                 timestamp=float(r + 1))
        else:
            c.settle_round_batch(r, rng.random(W), timestamp=float(r + 1))
    return c


def _flavors():
    """One settled contract per commit flavor the chain produces; the
    dense-tail round has a partial last leaf and odd-node promotions."""
    return {
        "dense": _settle(_contract(64)),
        "dense-tail": _settle(_contract(70)),
        "delta": _settle(_contract(64, sparse=True), cohort=16),
    }


# -- (a) unified SettlementProof across flavors -------------------------------


@pytest.mark.parametrize("flavor", ["dense", "dense-tail", "delta"])
def test_settlement_proof_roundtrip_all_flavors(flavor):
    c = _flavors()[flavor]
    for w in (0, 7, 63):
        sp = c.proof(1, w)
        blk = c.ledger.blocks[sp.block_index]
        assert sp.verify(blk)
        assert sp.verify(header_of(blk))          # light-client header
        assert sp.verify(blk.records_root)        # bare trusted root
        assert sp.record["worker"] == w
        assert c.verify_settlement(sp)            # typed input accepted


def test_settlement_proof_multi_task_block():
    """Two co-tenant tasks settling in one multi-task block: each task's
    proof resolves through the task Merkle level, single and
    batched, and the serve path spans both tenants."""
    from repro.core.node import TaskRoundWork, settle_tasks_block
    ledger = Ledger()
    a = TrustContract(ledger, requester_deposit=1e4, worker_stake=1.0,
                      penalty_pct=10.0, trust_threshold=0.5, top_k=4,
                      merkle_chunk_size=4, task_id="a")
    b = TrustContract(ledger, requester_deposit=1e4, worker_stake=1.0,
                      penalty_pct=10.0, trust_threshold=0.5, top_k=4,
                      merkle_chunk_size=2, task_id="b")
    a.join_batch(16)
    b.join_batch(8)
    rng = np.random.default_rng(0)
    blk, _, errors = settle_tasks_block(
        ledger, [TaskRoundWork("a", a, 0, rng.random(16)),
                 TaskRoundWork("b", b, 0, rng.random(8))], timestamp=1.0)
    assert not errors and blk.task_roots and set(blk.task_roots) == \
        {"a", "b"}
    for contract, w in ((a, 11), (b, 5)):
        sp = contract.proof(0, w)
        assert sp.task_id == contract.task_id
        assert sp.verify(blk) and sp.verify(header_of(blk))
        assert contract.settlement_proof(0, w) == sp.as_legacy_dict()
    for tid, contract, wids in (("a", a, [0, 5, 11]), ("b", b, [0, 7])):
        batch = build_proof_batch(ledger, blk.index, wids, task_id=tid)
        assert verify_proof_batch(batch, blk)
        assert batch.task_id == tid
        assert [batch.decoded(i)["worker"] for i in range(len(wids))] \
            == wids
    srv = ChainReadServer(contracts={"a": a, "b": b})
    lc = LightClient(srv)
    assert lc.audit("b", 5)["worker"] == 5
    with pytest.raises(ValueError):
        srv.get_proofs(None, [0])                  # ambiguous tenant


def test_verify_rejects_wrong_head_and_garbage():
    c = _settle(_contract(32))
    sp = c.proof(0, 3)
    other = c.ledger.blocks[c._round_blocks[1]]
    assert not sp.verify(other)                  # wrong block
    assert not sp.verify("ab" * 32)              # wrong root
    assert not sp.verify("")                     # unusable head
    assert not sp.verify(None)
    bad = SettlementProof(**{**sp.__dict__, "offset": 99})
    assert not bad.verify(c.ledger.blocks[sp.block_index])


# -- (a) deprecated wrappers: bit-identical proofs ----------------------------


@settings(max_examples=20, deadline=None)
@given(chunk=st.sampled_from([1, 3, 8]),
       w=st.integers(min_value=0, max_value=23))
def test_legacy_wrapper_bit_identity(chunk, w):
    """The deprecated dict ``settlement_proof`` is exactly the typed
    proof's legacy projection, and ``Ledger.merkle_proof`` is its path."""
    c = _settle(_contract(24, chunk=chunk), rounds=1)
    sp = c.proof(0, w)
    legacy = c.settlement_proof(0, w)
    assert legacy == sp.as_legacy_dict()
    assert c.ledger.merkle_proof(sp.block_index, sp.leaf_index) == \
        list(sp.path)
    assert c.verify_settlement(legacy)
    rt = SettlementProof.from_legacy(legacy)
    assert rt.verify(c.ledger.blocks[sp.block_index])
    # ledger-level legacy verify agrees
    assert c.ledger.verify_record(sp.block_index, sp.leaf_index, sp.leaf)


def test_verify_settlement_rejects_malformed_dicts():
    c = _settle(_contract(16), rounds=1)
    good = c.settlement_proof(0, 2)
    assert not c.verify_settlement({})
    assert not c.verify_settlement({**good, "offset": 77})
    assert not c.verify_settlement({**good, "block_index": 10_000})
    assert not c.verify_settlement(
        {**good, "leaf": b"\x00" * len(good["leaf"])})


# -- (b) batched multiproofs ---------------------------------------------------


@pytest.mark.parametrize("flavor", ["dense", "dense-tail", "delta"])
def test_proof_batch_roundtrip_and_dedup(flavor):
    c = _flavors()[flavor]
    blk = c.ledger.blocks[c._round_blocks[1]]
    wids = list(range(0, 64, 3))
    pos = [c.record_position(1, w) for w in wids]
    batch = build_proof_batch(c.ledger, blk.index, pos,
                              worker_ids=wids, round_index=1)
    assert verify_proof_batch(batch, blk)
    assert verify_proof_batch(batch, header_of(blk))
    # every record decodes to the same view the single-proof path attests
    for i, w in enumerate(wids):
        assert batch.decoded(i) == c.proof(1, w).record
    # dedup: far fewer shipped digests than the sum of independent paths
    indep = sum(len(c.settlement_proof(1, w)["proof"]) for w in wids)
    assert batch.num_digests < indep / 2


def test_proof_batch_tamper_rejection_every_level():
    """Flipping any component — leaf chunk bytes, any shipped sibling,
    record offset, plan, claimed root, or the record's leaf assignment —
    must flip verification to False (never raise)."""
    c = _settle(_contract(64))
    blk = c.ledger.blocks[c._round_blocks[1]]
    wids = [0, 9, 33, 63]

    def fresh():
        return build_proof_batch(c.ledger, blk.index, wids)

    assert verify_proof_batch(fresh(), blk)
    # chunk bytes (leaf level)
    b = fresh()
    key = next(iter(b.chunks))
    raw = bytearray(b.chunks[key])
    raw[5] ^= 1
    b.chunks[key] = bytes(raw)
    assert not verify_proof_batch(b, blk)
    # each shipped sibling digest (interior levels, one at a time)
    for skey in fresh().siblings:
        b = fresh()
        flipped = bytearray(bytes.fromhex(b.siblings[skey]))
        flipped[0] ^= 1
        b.siblings[skey] = flipped.hex()
        assert not verify_proof_batch(b, blk), f"sibling {skey}"
    # record offset out of its chunk
    b = fresh()
    ri, key, _ = b.records[0]
    b.records[0] = (ri, key, 10_000)
    assert not verify_proof_batch(b, blk)
    # record pointed at a key never lifted to the root
    b = fresh()
    b.chunks[("N", 99, 0)] = b.chunks[key]
    b.records[0] = (ri, ("N", 99, 0), 0)
    assert not verify_proof_batch(b, blk)
    # truncated plan: root never computed
    b = fresh()
    b.plan = b.plan[:-1]
    assert not verify_proof_batch(b, blk)
    # forged root claim
    b = fresh()
    b.root = "cd" * 32
    assert not verify_proof_batch(b, blk)
    # a sibling may not override a computed node
    b = fresh()
    b.siblings[ROOT_KEY] = blk.records_root
    assert not verify_proof_batch(b, blk)
    # tampering the *stored* records poisons freshly built batches too
    c.ledger.tamper_record(blk.index, 9, b"\x00" * 48)
    assert not verify_proof_batch(fresh(), blk)


# -- (c) head sync + stale re-anchoring ---------------------------------------


def _serving_pair(**kw):
    c = _settle(_contract(64), rounds=3)
    srv = ChainReadServer(contracts=c, **kw)
    return c, srv, LightClient(srv)


def test_head_sync_full_incremental_current():
    c, srv, lc = _serving_pair()
    gained = lc.sync()
    assert gained == lc.height == srv.height
    assert lc.sync() == 0                         # O(1) current token
    reply = srv.sync_head(lc.height, lc.headers[-1].hash)
    assert reply.current and not reply.headers and not reply.reset
    # incremental: settle one more round, delta is exactly one header
    c.settle_round_batch(3, np.random.default_rng(9).random(64),
                         timestamp=9.0)
    reply = srv.sync_head(lc.height, lc.headers[-1].hash)
    assert not reply.reset and len(reply.headers) == 1
    assert lc.sync() == 1
    # a client claiming an unknown head gets a full reset resync
    reply = srv.sync_head(2, "ff" * 32)
    assert reply.reset and len(reply.headers) == srv.height


def test_corrupt_headers_rejected_state_untouched():
    _, srv, lc = _serving_pair()
    lc.sync()
    h = lc.headers[1]
    for attr, val in (("hash", "f" * 64), ("prev_hash", "e" * 64),
                      ("index", 40), ("records_root", "d" * 64)):
        bad = list(lc.headers)
        bad[1] = BlockHeader(**{**h.__dict__, attr: val})
        victim = LightClient(srv)
        with pytest.raises(HeaderVerificationError):
            victim._verify_and_adopt(bad, [])
        assert victim.headers == []               # nothing adopted


def test_stale_proof_reanchors_after_sync():
    c, srv, lc = _serving_pair()
    lc.sync()
    c.settle_round_batch(3, np.random.default_rng(5).random(64),
                         timestamp=5.0)
    batch = lc.fetch_proofs(None, [4, 40], round_index=3)
    with pytest.raises(StaleProofError):
        lc.verify_batch(batch)
    lc.sync()
    assert lc.verify_batch(batch)                 # same batch, re-anchored
    rec = lc.audit(None, 4, round_index=3)        # audit path does it alone
    assert rec["worker"] == 4 and rec["round"] == 3


def test_server_round_and_batch_errors():
    c, srv, lc = _serving_pair(max_batch=8)
    with pytest.raises(RoundNotSettled):
        srv.get_proofs(None, [0], round_index=77)
    with pytest.raises(ValueError):
        srv.get_proofs(None, list(range(9)))      # over max_batch
    assert srv.latest_settled_round(None) == 2
    # partial dense round (unsorted cohort): present workers resolve
    # through the argsort index, absent ones are named in the KeyError
    cs = _contract(64)
    ids = np.array([40, 3, 17, 9, 55, 21, 0, 33], np.int64)
    cs.settle_round_batch(0, np.random.default_rng(3).random(len(ids)),
                          worker_ids=ids, timestamp=1.0)
    srv2 = ChainReadServer(contracts=cs)
    lc2 = LightClient(srv2)
    for w in (40, 0, 33):
        assert lc2.audit(None, w, round_index=0)["worker"] == w
    missing = next(w for w in range(64) if w not in set(ids.tolist()))
    with pytest.raises(KeyError):
        srv2.get_proofs(None, [missing], round_index=0)
    # sparse (delta-overlay) rounds cover the whole population — even a
    # worker outside the cohort is proof-served (round -1 = never settled)
    cd = _settle(_contract(64, sparse=True), rounds=1, cohort=8)
    srv3 = ChainReadServer(contracts=cd)
    idle = next(w for w in range(64)
                if w not in set(cd._round_ids[0].tolist()))
    assert LightClient(srv3).audit(None, idle, round_index=0)["worker"] \
        == idle


# -- (d) checkpoint streaming --------------------------------------------------


def test_checkpoint_stream_roundtrip_tamper_and_quota():
    c = _settle(_contract(16), rounds=1)
    ipfs = IPFSStore()
    tree = {"w": np.arange(4096, dtype=np.float32),
            "b": np.ones(7, np.float32)}
    cid = ipfs.put_tree(tree, owner="t")
    srv = ChainReadServer(contracts=c, ipfs=ipfs, chunk_bytes=512)
    lc = LightClient(srv, client_id="aud")
    leaves = lc.fetch_checkpoint(cid)
    assert any(np.asarray(x).size == 4096 for x in leaves)
    man = srv.checkpoint_manifest(cid)
    assert man.num_chunks == -(-man.size // 512) and srv.chunks_streamed \
        == man.num_chunks
    assert hashlib.sha256(
        b"".join(srv.checkpoint_chunk(cid, i)
                 for i in range(man.num_chunks))).hexdigest() == cid
    with pytest.raises(IndexError):
        srv.checkpoint_chunk(cid, man.num_chunks)
    # tamper: reassembled bytes no longer match the content address
    ipfs.tamper(cid, b"z" * man.size)
    with pytest.raises(ValueError, match="content hash"):
        LightClient(srv).fetch_checkpoint(cid)
    # per-client serve quota
    srv2 = ChainReadServer(contracts=c, ipfs=IPFSStore(), chunk_bytes=64,
                           serve_quota_bytes=128)
    cid2 = srv2.ipfs.put_tree(        # incompressible → blob > quota
        {"x": np.random.default_rng(0).random(500).astype(np.float32)})
    with pytest.raises(QuotaExceeded):
        LightClient(srv2, client_id="greedy").fetch_checkpoint(cid2)
    anon = LightClient(srv2)                       # quota needs a client_id
    assert anon.fetch_checkpoint(cid2)


# -- (e) legacy namespace + deprecation shims ---------------------------------


def test_legacy_namespace_and_deprecation_warnings():
    c = TrustContract(Ledger(), requester_deposit=100.0, worker_stake=5.0,
                      penalty_pct=20.0, trust_threshold=0.5, top_k=1)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        c.legacy.join("a")                        # namespace: no warning
        c.legacy.join("b")
    with pytest.deprecated_call():
        c.join("c")
    with pytest.deprecated_call():
        c.settle_round(0, {"a": 0.9, "b": 0.1, "c": 0.8})
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        pen = c.legacy.settle_round(1, {"a": 0.9, "b": 0.2, "c": 0.8})
    assert "b" in pen
    # shim and namespace share state: both rounds are on one chain
    assert {0, 1} <= set(c._round_blocks)
    sp = c.proof(1, "b")
    assert sp.verify(c.ledger.blocks[sp.block_index])


def test_node_read_server_end_to_end():
    """``ChainNode.read_server()`` serves a real node: a light client
    syncs the node's chain and audits a worker of a task it never ran."""
    from repro.configs.base import FederationConfig, TrainConfig
    from repro.configs.registry import get_config
    from repro.core.node import ChainNode
    from repro.data.datasets import make_federated_mnist

    node = ChainNode(pipeline_depth=2)
    fed = FederationConfig(num_clusters=1, workers_per_cluster=2,
                           trust_threshold=0.2, merkle_chunk_size=2)
    tc = TrainConfig(lr=0.01, momentum=0.5, optimizer="sgd", remat=False)
    node.create_task("t", get_config("paper-net"), fed, tc, seed=0)
    ds = make_federated_mnist(2, samples=256, seed=0)
    for _ in range(3):
        node.run_tick({"t": ds.round_batches(16)})
    node.flush()
    lc = LightClient(node.read_server())
    assert lc.sync() == len(node.ledger.blocks)
    rec = lc.audit("t", 1)
    assert rec["worker"] == 1 and rec["round"] >= 0
    node.finalize()


# -- (f) lock-free reads under live settlement --------------------------------


def test_concurrent_readers_never_see_torn_state():
    W, rounds = 2_000, 12
    c = _contract(W, chunk=64)
    srv = ChainReadServer(contracts=c)
    c.settle_round_batch(0, np.random.default_rng(0).random(W),
                         timestamp=1.0)
    stop = threading.Event()
    failures = []

    def writer():
        rng = np.random.default_rng(1)
        for r in range(1, rounds):
            c.settle_round_batch(r, rng.random(W), timestamp=float(r + 1))
        stop.set()

    def reader(i):
        lc = LightClient(srv)
        rng = np.random.default_rng((2, i))
        try:
            while not stop.is_set() or lc.height < srv.height:
                lc.sync()
                ids = rng.integers(0, W, size=32)
                r = srv.latest_settled_round(None)
                batch = srv.get_proofs(None, ids, round_index=r)
                try:
                    ok = lc.verify_batch(batch)
                except StaleProofError:
                    lc.sync()
                    ok = lc.verify_batch(batch)
                if not ok:
                    failures.append((i, r))
                    return
        except Exception as e:                     # pragma: no cover
            failures.append((i, repr(e)))

    threads = [threading.Thread(target=writer)] + \
        [threading.Thread(target=reader, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not failures
    assert srv.proofs_served > 0
