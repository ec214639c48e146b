"""Golden chains: consensus bytes pinned as constants.

Each scenario settles a seeded score stream and compares its block hashes,
one record's settlement proof, and a digest of every penalty and payout
against values recorded when settlement still had a second (sharded)
execution path. Any change to record encoding, Merkle commits, block
layout, penalties or payouts fails here, whatever the other tests say.

Scenarios:

- ``dense``: one task at the benchmark cells' settlement shape — W = 1,024,
  64 records per Merkle leaf, 3 rounds, then finalize.
- ``sparse``: one task with sparse (delta) commits sealing cohorts of 256
  of 1,024 workers (the async cell's shape), unsorted cohort ids and
  staleness discounting at alpha = 0.5.
- ``two_task``: one dense and one sparse task co-committed per round into
  multi-task blocks by ``settle_tasks_block``.
"""
import hashlib

import numpy as np
import pytest

from repro.chain.contract import TrustContract
from repro.chain.ledger import Ledger
from repro.core.node import TaskRoundWork, settle_tasks_block

W, CHUNK, COHORT, ROUNDS = 1024, 64, 256, 3


def _contract(led, tid=None, *, sparse=False):
    c = TrustContract(led, requester_deposit=1000.0, worker_stake=10.0,
                      penalty_pct=50.0, trust_threshold=0.5, top_k=4,
                      merkle_chunk_size=CHUNK, sparse_settlement=sparse,
                      staleness_alpha=0.5 if sparse else 0.0, task_id=tid)
    c.join_batch(W)
    return c


def _dense_round(rng):
    return {"scores": rng.random(W)}


def _cohort_round(rng):
    ids = rng.permutation(W)[:COHORT].astype(np.int64)   # unsorted ids
    return {"scores": rng.random(COHORT), "worker_ids": ids,
            "staleness": rng.integers(0, 4, COHORT)}


def _summary(led, pens, payouts, proof):
    h = hashlib.sha256()
    for p in pens:
        h.update(np.asarray(p, np.float64).tobytes())
    for name in sorted(payouts):
        h.update(f"{name}={payouts[name]!r};".encode())
    return {"hashes": [b.hash for b in led.blocks[1:]],
            "proof_path": [list(p) for p in proof.path],
            "proof_chunk": hashlib.sha256(b"".join(proof.chunk)).hexdigest(),
            "economics": h.hexdigest()}


def _run_dense():
    rng = np.random.default_rng(1)
    led = Ledger()
    c = _contract(led)
    pens = [c.settle_round_batch(r, **_dense_round(rng),
                                 timestamp=float(r + 1))
            for r in range(ROUNDS)]
    payouts = c.finalize(timestamp=float(ROUNDS + 1))
    return _summary(led, pens, payouts, c.proof(1, 517))


def _run_sparse():
    rng = np.random.default_rng(2)
    led = Ledger()
    c = _contract(led, sparse=True)
    pens = [c.settle_round_batch(r, **_cohort_round(rng),
                                 timestamp=float(r + 1))
            for r in range(ROUNDS)]
    payouts = c.finalize(timestamp=float(ROUNDS + 1))
    return _summary(led, pens, payouts, c.proof(2, 1000))


def _run_two_task():
    rng = np.random.default_rng(3)
    led = Ledger()
    dense = _contract(led, "dense-task")
    sparse = _contract(led, "sparse-task", sparse=True)
    pens = []
    for r in range(ROUNDS):
        work = [TaskRoundWork("dense-task", dense, r, **_dense_round(rng)),
                TaskRoundWork("sparse-task", sparse, r,
                              **_cohort_round(rng))]
        _, out, errors = settle_tasks_block(led, work,
                                            timestamp=float(r + 1))
        assert not errors
        pens += [out["dense-task"], out["sparse-task"]]
    payouts = {f"dense/{k}": v for k, v in dense.finalize(
        timestamp=float(ROUNDS + 1)).items()}
    payouts.update({f"sparse/{k}": v for k, v in sparse.finalize(
        timestamp=float(ROUNDS + 2)).items()})
    return _summary(led, pens, payouts, sparse.proof(1, 3))


RUNS = {"dense": _run_dense, "sparse": _run_sparse,
        "two_task": _run_two_task}

# computed at the commit before settlement lost its sharded path
GOLDEN = {
    "dense": {
        "hashes": [
            "1996663c5c262821697d9ec01072a50017b984967b569065f549aae575f0c8a2",
            "4cdaee6c08c833f4524826ac49a8b04b542b40766eac1f3074e1287890422b70",
            "4b5a0179deca3f7dcb42cf5212c3c69ccbb029ffbed354e0e9b5341ca94daf7f",
            "829968d4008e62213ec2392d5980748187ebba4a822cba6d11a1b663c876816a",
        ],
        "proof_path": [
            ["R", "83dd650029b57c6757496ef5563fa0643a8c22436f04f99a935e32062b0d35cb"],
            ["R", "59729a85e6b3b027541ec9e8651d0b1486ac11e465a2615f28ec275bb2f65f50"],
            ["R", "c9e1de8b594ad82de6df7701779d3979d685d3f8eb1924594715ff1a447079e5"],
            ["L", "c2398c78fc110d43520d4456159b307eac9407b0ec720eae064e82935475c29d"],
        ],
        "proof_chunk":
            "4b80acc1ef56fbddefe15ba18d5f45c9430c801480f1da432761b96b1bdb7dc7",
        "economics":
            "e5f936182c2840d638da1ea400c414252aca8f634ce29567519222446b45228f",
    },
    "sparse": {
        "hashes": [
            "dc22cf0f6c55cf0323563f5ece6aa22e49f97bbb8728b8ac02416f5bee4f1867",
            "0ed8b302e3c6cb0af47ae7b95782361f37f0c102bb6eea636ac885385648856c",
            "cf889000e43a0718ef9a32af41b1b6c96510cfa5c5684ec4bc022ca818fc0634",
            "86185cda79dfe7aeaf0d196c55cb469711137801a0c095d936715c0b69b8f201",
        ],
        "proof_path": [
            ["L", "7ce97b41ca271586126ec016d457c8ffbdc3df8ae9606efaf6576144d08cc6f5"],
            ["L", "85ad84e0bb154934fccd561c75f95b4585a51b36a0bf8c8d2d753e48c272e020"],
            ["L", "f4e8d7cfb456db258710d6435fdd1faa3786c3981783ba6b5a94026b95fbf7bf"],
            ["L", "7412d2e0e666bdb183e3c4c2b85023782bc0393e75ceea9ed0cb945bbd18ba96"],
        ],
        "proof_chunk":
            "95a97632d2406e0db5e1280e408ba17591c8b0e197ee7ca7b5dd45d2b94fb7d9",
        "economics":
            "1652f35750ba476503e5537bdc119cfb8df0d1345e22ac4a0af93c130addddc9",
    },
    "two_task": {
        "hashes": [
            "077092435ff0145042202d87c23a3b866db4a06be5fa85334d26f38cdf9c9045",
            "88c5174d4f06d6ece101251e1c52dcd42a15894c1cbc6ea87ef79935fe112a07",
            "36e80d0a3ee75e11ffe2e9f182cd6e39c6b1125cf46e810fd2464d460ed9ece7",
            "061d99320d5f6e0b41747d6cfa632e42ae1a952ea442a7892d85e2203a975580",
            "63d4d902fbf0c327ebd00fc156dc6a48caf6acb01da0ac26f25f21afe1bcf2ce",
        ],
        "proof_path": [
            ["R", "61296b7da554eaba83c6f378e525f9e53b54b1681384e791ae9599e61864ffe5"],
            ["R", "cb706f6ccb0d3b002041bf918b436fd3a58d6b9cad16eaa06a316e796f1bef83"],
            ["R", "c55e791ed24239b06205e06f87e1f25926afcd927b0d30a38dfac6bc998c3b21"],
            ["R", "995e14c760707d74f1927cc2f4da1eecdb998becd54030ae05e86cf58555ab05"],
            ["L", "872d2378b7f4447bf2ea9759109810aaac4b0603b27f83c92f8e462bfe9b08e0"],
        ],
        "proof_chunk":
            "a48f973c996036d3032f9f8d3b90e99fb14dec47c2e7367b58e82c7cb1756c36",
        "economics":
            "ae89f18b49359236a2e52c0a0a5330b6cf538d6a8bb1d1aa83c00d5e7ba5c834",
    },
}


@pytest.mark.parametrize("scenario", sorted(RUNS))
def test_golden_chain_bytes_never_move(scenario):
    got = RUNS[scenario]()
    want = GOLDEN[scenario]
    assert got["hashes"] == want["hashes"]
    assert got["proof_path"] == want["proof_path"]
    assert got["proof_chunk"] == want["proof_chunk"]
    assert got["economics"] == want["economics"]
