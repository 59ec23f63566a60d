"""ZK proof plane: verifiable getProof serving + batched verification.

Covers the commit-time render path (zero tree walks on a hit), the
tamper-detect negative cases (proof / value / root), state-changeset
proofs anchored at header.state_root, the verifyProofs batched RPC, and
the crypto lane's poseidon op (two concurrent callers merge into ONE
base-suite call)."""

import json
import threading
import time

import numpy as np
import pytest

from fisco_bcos_tpu.crypto.lane import CryptoLane, LaneSuite
from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.executor.executor import state_leaf_payload
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.ops import merkle
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.zk import poseidon as zp
from fisco_bcos_tpu.zk import proof as zkproof

from test_rpc_batch import cohort_node, cohort_txs, wait_until


def _unhex(s):
    return bytes.fromhex(s[2:] if s.startswith("0x") else s)


def _commit_tx(node, kp, nonce, who=b"zkp", amount=9):
    tx = Transaction(to=pc.BALANCE_ADDRESS,
                     input=pc.encode_call(
                         "register", lambda w: w.blob(who).u64(amount)),
                     nonce=nonce,
                     block_limit=node.ledger.current_number() + 100
                     ).sign(node.suite, kp)
    res = node.send_transaction(tx)
    rc = node.txpool.wait_for_receipt(res.tx_hash, 20)
    assert rc is not None and rc.status == 0
    return res.tx_hash


def _commit_cohort(node, kp, tag, n=4):
    """Commit n txs submitted as one batch (one or few blocks) and return
    a tx hash whose block carries >= 2 txs — so its inclusion proof has
    at least one real level (a single-leaf tree's proof is empty)."""
    txs = [Transaction(to=pc.BALANCE_ADDRESS,
                       input=pc.encode_call(
                           "register",
                           lambda w, i=i: w.blob(b"%s%d" % (tag, i)).u64(i + 1)),
                       nonce=f"{tag.decode()}-{i}",
                       block_limit=node.ledger.current_number() + 100
                       ).sign(node.suite, kp) for i in range(n)]
    for res in node.txpool.submit_batch(txs):
        assert int(res.status) == 0, res
    hashes = [tx.hash(node.suite) for tx in txs]
    for h in hashes:
        assert node.txpool.wait_for_receipt(h, 20) is not None
    for h in hashes:
        num = node.ledger.receipt(h).block_number
        if len(node.ledger.tx_hashes_by_number(num)) >= 2:
            return h
    raise AssertionError("every block came out single-tx")


def _wait_primed(impl, h, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if impl.cache is not None and impl.cache.get(("proof", h)):
            return True
        time.sleep(0.02)
    return False


def _node():
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0))
    impl = node.make_rpc_impl()
    node.start()
    return node, impl


def test_get_proof_roundtrip_and_tamper():
    node, impl = _node()
    try:
        kp = node.suite.generate_keypair(b"zk-proof-1")
        h = _commit_cohort(node, kp, b"zp1")
        doc = impl.get_proof("group0", tx_hash="0x" + h.hex())
        assert doc["found"]
        suite = node.suite
        tx_items = [(h, zkproof.w16_proof_from_json(doc["txProof"]),
                     _unhex(doc["txsRoot"]))]
        assert zkproof.verify_inclusion_batch(suite, tx_items).all()
        rc = node.ledger.receipt(h)
        rc_items = [(rc.hash(suite),
                     zkproof.w16_proof_from_json(doc["receiptProof"]),
                     _unhex(doc["receiptsRoot"]))]
        assert zkproof.verify_inclusion_batch(suite, rc_items).all()
        # the roots anchor to the committed header
        header = node.ledger.header_by_number(doc["blockNumber"])
        assert header.txs_root == _unhex(doc["txsRoot"])
        assert header.receipts_root == _unhex(doc["receiptsRoot"])
        # tampered value (leaf), root, and proof all reject
        leaf, proof, root = tx_items[0]
        bad_leaf = bytes([leaf[0] ^ 1]) + leaf[1:]
        assert not zkproof.verify_inclusion_batch(
            suite, [(bad_leaf, proof, root)]).any()
        assert not zkproof.verify_inclusion_batch(
            suite, [(leaf, proof, b"\x05" * 32)]).any()
        sibs, pos = proof[0]
        forged = [([b"\x06" * 32] * len(sibs), pos)] + proof[1:]
        assert not zkproof.verify_inclusion_batch(
            suite, [(leaf, forged, root)]).any()
        # unknown hash: typed not-found (unpruned chain -> floor 0)
        missing = impl.get_proof("group0", tx_hash="0x" + b"\x07".hex() * 32)
        assert missing == {"found": False, "prunedBelow": 0}
    finally:
        node.stop()


def test_get_proof_served_from_commit_prime():
    """After the commit-time prime lands, getProof hits cost ZERO tree
    walks — the ledger proof builders are never touched."""
    node, impl = _node()
    try:
        kp = node.suite.generate_keypair(b"zk-proof-2")
        h = _commit_cohort(node, kp, b"zp2")
        assert _wait_primed(impl, h), "commit prime never rendered"

        def boom(*_a, **_k):
            raise AssertionError("tree walk on a primed hit")

        node.ledger.tx_proof = boom
        node.ledger.receipt_proof = boom
        doc = impl.get_proof("group0", tx_hash="0x" + h.hex())
        assert doc["found"] and doc["txProof"]
        assert node.zk.stats()["proofHits"] >= 1
    finally:
        node.stop()


def test_state_proof_roundtrip_and_tamper():
    """getProof state entries prove 'block N wrote key := value' against
    header.state_root: leaf digest recomputed from the claimed value via
    the canonical payload, inclusion checked batched, tamper rejected."""
    node, impl = _node()
    try:
        kp = node.suite.generate_keypair(b"zk-proof-3")
        h = _commit_tx(node, kp, "zp3", who=b"zks", amount=44)
        n = node.ledger.receipt(h).block_number
        table, key = "c_balance", None
        for t, k, _d in node.ledger.state_leaf_index(n):
            if t == table:
                key = k
                break
        assert key is not None, "balance write missing from state index"
        doc = impl.get_proof("group0", number=n,
                             state_keys=[[table, "0x" + key.hex()]])
        entry = doc["stateEntries"][0]
        assert entry["present"]
        value = node.storage.get(table, key)
        suite = node.suite
        leaf = suite.hash(state_leaf_payload(table, key, value))
        assert leaf == _unhex(entry["leafDigest"])
        root = _unhex(entry["stateRoot"])
        assert node.ledger.header_by_number(n).state_root == root
        proof = zkproof.w16_proof_from_json(entry["stateProof"])
        assert zkproof.verify_inclusion_batch(
            suite, [(leaf, proof, root)]).all()
        # a lying value produces a different leaf -> rejected
        bad = suite.hash(state_leaf_payload(table, key, value + b"\x01"))
        assert not zkproof.verify_inclusion_batch(
            suite, [(bad, proof, root)]).any()
        # a key the block never wrote: typed absence
        doc2 = impl.get_proof("group0", number=n,
                              state_keys=[[table, "0x" + b"\xaa".hex() * 4]])
        assert doc2["stateEntries"][0]["present"] is False
    finally:
        node.stop()


def test_verify_proofs_rpc_batched():
    node, impl = _node()
    try:
        kp = node.suite.generate_keypair(b"zk-proof-4")
        hashes = [_commit_tx(node, kp, f"zp4-{i}", who=b"z4%d" % i)
                  for i in range(3)]
        docs = [impl.get_proof("group0", tx_hash="0x" + h.hex())
                for h in hashes]
        proofs = [{"leaf": "0x" + h.hex(), "proof": d["txProof"],
                   "root": d["txsRoot"]} for h, d in zip(hashes, docs)]
        proofs.append({"leaf": "0x" + b"\x09".hex() * 32,
                       "proof": docs[0]["txProof"],
                       "root": docs[0]["txsRoot"]})
        out = impl.verify_proofs("group0", proofs=proofs)
        assert out["results"] == [True, True, True, False]
        assert out["verified"] == 3
        assert node.zk.stats()["proofsVerified"] >= 4
        assert node.system_status()["zk"]["verifyCalls"] >= 1
    finally:
        node.stop()


def test_lane_merges_poseidon_batches():
    """Two groups' concurrent poseidon_batch calls land in ONE base-suite
    call (the gated-dispatch idiom from test_crypto_lane)."""
    base = make_suite(backend="host")
    calls = []
    gate = threading.Event()
    entered = threading.Event()
    orig = base.poseidon_batch

    def counting(lefts, rights):
        calls.append(len(lefts))
        if not entered.is_set():
            entered.set()
            assert gate.wait(30)
        return orig(lefts, rights)

    base.poseidon_batch = counting
    lane = CryptoLane(base)
    g0, g1 = LaneSuite(lane, "g0"), LaneSuite(lane, "g1")
    rng = np.random.default_rng(3)
    a = [rng.bytes(32) for _ in range(8)]
    b = [rng.bytes(32) for _ in range(8)]
    try:
        # park the dispatcher on a first call so the two real submissions
        # below provably queue together
        warm = lane.submit("poseidon", ([a[0], a[1]], [b[0], b[1]]), 2, "w")
        assert entered.wait(30)
        results = {}
        threads = [
            threading.Thread(target=lambda: results.__setitem__(
                "g0", g0.poseidon_batch(a[:5], b[:5]))),
            threading.Thread(target=lambda: results.__setitem__(
                "g1", g1.poseidon_batch(a[5:], b[5:]))),
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30
        while sum(len(q) for q in lane._q.values()) < 2:
            assert time.monotonic() < deadline, "submissions never queued"
            time.sleep(0.01)
        gate.set()
        for t in threads:
            t.join(30)
        warm.result(30)
        # call 1 = the gated warm-up; call 2 = BOTH groups merged
        assert calls == [2, 8], calls
        want = zp.hash2_batch_host(a, b)
        assert results["g0"] == want[:5]
        assert results["g1"] == want[5:]
        stats = lane.stats()
        assert stats["per_op"]["poseidon"]["calls"] == 2
        assert stats["merged_calls"] >= 1
    finally:
        base.poseidon_batch = orig
        lane.stop()


# ---------------------------------------------------------------------------
# proof bundles from shared rows: the same documents, the same walks
# ---------------------------------------------------------------------------


def _copying_walk(levels, index):
    """The walk `proof_from_levels` replaced: pad a copy of every level
    for every leaf."""
    proof, idx = [], index
    for level in levels[:-1]:
        cur = list(level)
        while len(cur) % merkle.WIDTH:
            cur.append(b"\x00" * merkle.DIGEST)
        group = idx // merkle.WIDTH
        proof.append((cur[group * merkle.WIDTH:(group + 1) * merkle.WIDTH],
                      idx % merkle.WIDTH))
        idx = group
    return proof


@pytest.mark.parametrize("alg", ["keccak256", "sm3"])
@pytest.mark.parametrize("n", [1, 2, 16, 17, 255, 256, 257, 1000])
def test_proof_from_levels_slices_what_the_copying_walk_padded(n, alg):
    leaves = [bytes([i % 251, i // 251]) * 16 for i in range(n)]
    levels = merkle.merkle_levels_host(leaves, alg)
    root = levels[-1][0]
    for i in sorted({0, n // 3, n - 1}):
        proof = merkle.proof_from_levels(levels, i)
        assert proof == _copying_walk(levels, i)
        assert merkle.verify_merkle_proof(leaves[i], proof, root, alg)
    before = [list(level) for level in levels]
    merkle.proof_from_levels(levels, n - 1)
    assert levels == before  # a padded row is the proof's, not the level's


@pytest.mark.parametrize("sm", [False, True], ids=["secp", "sm"])
@pytest.mark.parametrize("n", [1, 17, 300])
def test_primed_proof_documents_equal_render_proof_doc(n, sm):
    """Zero, one and two real levels above the leaves: every document the
    commit renders from shared rows is `render_proof_doc`'s, verifies
    against the header's roots, and is sized without being dumped."""
    node, kp, impl = cohort_node(sm, tx_count_limit=1000)
    try:
        txs = cohort_txs(node, kp, f"doc{n}", n)
        node.txpool.submit_batch(txs)
        hashes = [tx.hash(node.suite) for tx in txs]
        for h in hashes:
            assert node.txpool.wait_for_receipt(h, 30) is not None
        assert wait_until(lambda: impl.cache.get(("proof", hashes[-1])))
        suite = node.suite
        for h in hashes:
            doc = impl.cache.get(("proof", h))
            assert doc == zkproof.render_proof_doc(node.ledger, h)
            assert abs(zkproof.proof_doc_size(doc) - len(json.dumps(
                doc, separators=(",", ":")))) <= 64
        items = []
        for h in (hashes[0], hashes[n // 2], hashes[-1]):
            doc = impl.get_proof("group0", tx_hash="0x" + h.hex())
            assert doc["found"]
            header = node.ledger.header_by_number(doc["blockNumber"])
            assert _unhex(doc["txsRoot"]) == header.txs_root
            items.append((h, zkproof.w16_proof_from_json(doc["txProof"]),
                          header.txs_root))
            items.append((node.ledger.receipt(h).hash(suite),
                          zkproof.w16_proof_from_json(doc["receiptProof"]),
                          header.receipts_root))
        assert zkproof.verify_inclusion_batch(suite, items).all()
        assert node.zk.stats()["proofsRendered"] >= n
    finally:
        node.stop()
