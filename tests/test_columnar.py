"""Columnar transaction substrate (protocol/columnar.py).

The layout contract that makes the hot path safe: wire frames round-trip
through columns BYTE-IDENTICALLY (encode/encode_unsigned are arena
slices), identity (hash/sender) matches the object path exactly, and
failure is isolated PER ROW — a malformed frame, a bad signature or a
padded non-canonical variant rejects its own slot without poisoning
batchmates. Plus the admission integration: `TxPool.submit_columns`
admits a mixed batch with per-row statuses and ONE batched hash + ONE
batched recover, and a solo node commits txs submitted as raw wire bytes
through the ingest lane's wire door.
"""

import time

import pytest

from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.ledger.ledger import ConsensusNode, Ledger
from fisco_bcos_tpu.protocol import Transaction, TransactionStatus
from fisco_bcos_tpu.protocol.columnar import (TxView, columns_from_transactions,
                                              decode_columns)
from fisco_bcos_tpu.storage.memory import MemoryStorage
from fisco_bcos_tpu.txpool import TxPool

from tests.test_ingest import CountingSuite, _make_pool, _tx


@pytest.fixture(scope="module")
def suite():
    return make_suite(False, backend="host")


@pytest.fixture(scope="module")
def kp(suite):
    return suite.generate_keypair(b"columnar-user")


def _wire(suite, kp, i, group="group0", attribute=0):
    tx = Transaction(group_id=group, to=pc.BALANCE_ADDRESS,
                     input=b"payload-%d" % i, nonce=f"col-{i}",
                     block_limit=100, attribute=attribute,
                     import_time=1700000000000 + i).sign(suite, kp)
    return tx, tx.encode()


# -- round-trip identity ----------------------------------------------------

def test_roundtrip_byte_identical(suite, kp):
    txs, wires = zip(*(_wire(suite, kp, i, attribute=(i % 3) << 24)
                       for i in range(16)))
    cols = decode_columns(list(wires))
    assert len(cols) == 16 and cols.decode_ok.all() and not cols.fallback
    for i, (tx, w) in enumerate(zip(txs, wires)):
        v = cols.view(i)
        assert isinstance(v, TxView)
        assert v.encode() == w                      # arena slice == wire
        assert v.encode_unsigned() == tx.encode_unsigned()
        assert v.signature == tx.signature
        # payload fields decode straight from the arena
        assert (v.chain_id, v.group_id, v.nonce) == \
            (tx.chain_id, tx.group_id, tx.nonce)
        assert (v.to, v.input, v.abi) == (tx.to, tx.input, tx.abi)
        assert (v.version, v.block_limit) == (tx.version, tx.block_limit)
        assert (v.import_time, v.attribute) == \
            (tx.import_time, tx.attribute)
        assert cols.band(i) == (tx.attribute >> 24) & 0xFF


def test_identity_matches_object_path(suite, kp):
    txs, wires = zip(*(_wire(suite, kp, i) for i in range(8)))
    cols = decode_columns(list(wires))
    cols.ensure_hashes(suite)
    ok = cols.ensure_senders(suite)
    assert ok.all()
    for i, tx in enumerate(txs):
        assert cols.hashes[i] == tx.hash(suite)
        assert cols.senders[i] == tx.sender(suite)
        # the view shares the column cache both ways
        v = cols.view(i)
        assert v.hash(suite) == tx.hash(suite)
        assert v.sender(suite) == tx.sender(suite)
        t = v.to_transaction()
        assert t._hash == tx.hash(suite) and t.encode() == tx.encode()


def test_view_publishes_identity_back_to_column(suite, kp):
    _tx0, w = _wire(suite, kp, 0)
    cols = decode_columns([w])
    v = cols.view(0)  # created BEFORE any batch fill
    h = v.hash(suite)
    assert cols.hashes[0] == h  # solo compute published to the column
    assert v.sender(suite) is not None
    assert cols.senders[0] == v._sender
    # and the reverse: a later batch fill is visible through the view
    cols2 = decode_columns([w])
    v2 = cols2.view(0)
    cols2.ensure_senders(suite)
    assert v2.sender(suite) == cols2.senders[0]


def test_chain_group_interned_per_batch(suite, kp):
    _, wires = zip(*(_wire(suite, kp, i) for i in range(4)))
    cols = decode_columns(list(wires))
    assert cols.chain_id[0] is cols.chain_id[3]  # one str per batch
    assert cols.group_id[0] is cols.group_id[2]


def test_mixed_group_batch(suite, kp):
    pairs = [_wire(suite, kp, i, group=f"group{i % 2}") for i in range(6)]
    cols = decode_columns([w for _t, w in pairs])
    for i, (tx, _w) in enumerate(pairs):
        assert cols.view(i).group_id == tx.group_id == f"group{i % 2}"


# -- per-slice failure isolation --------------------------------------------

def test_malformed_rows_isolated(suite, kp):
    txs, wires = zip(*(_wire(suite, kp, i) for i in range(4)))
    batch = [wires[0], b"\xff\xff", wires[1], b"", wires[2],
             wires[3][:9], wires[3]]
    cols = decode_columns(batch)
    assert list(cols.decode_ok) == [True, False, True, False, True,
                                    False, True]
    cols.ensure_hashes(suite)
    good = [0, 2, 4, 6]
    for j, i in enumerate(good):
        assert cols.hashes[i] == txs[j].hash(suite)
        assert cols.wire(i) == wires[j]
    with pytest.raises(ValueError):
        cols.view(1)


def test_non_canonical_frame_falls_back_with_object_identity(suite, kp):
    tx, w = _wire(suite, kp, 0)
    padded = w + b"\x00\x00"  # trailing garbage: parses, NOT canonical
    cols = decode_columns([w, padded])
    assert cols.decode_ok.all()
    assert 1 in cols.fallback and 0 not in cols.fallback
    cols.ensure_hashes(suite)
    # identity is canonical (re-serialise-from-fields), NOT over the
    # padded bytes — exactly what Transaction.decode does
    assert cols.hashes[1] == Transaction.decode(padded).hash(suite) \
        == cols.hashes[0]
    # the fallback row's view is the materialised Transaction and its
    # re-encode is the CANONICAL form, not the padded input
    v = cols.view(1)
    assert isinstance(v, Transaction)
    assert v.encode() == w != padded
    assert cols.wire(1) == w


def test_bad_signature_isolated_in_recover(suite, kp):
    good = [_tx(suite, kp, i) for i in range(3)]
    bad = _tx(suite, kp, 99, valid=False)
    order = [good[0], bad, good[1], good[2]]
    cols = decode_columns([t.encode() for t in order])
    ok = cols.ensure_senders(suite)
    assert list(ok) == [True, False, True, True]
    assert cols.senders[1] is None
    assert all(cols.senders[i] is not None for i in (0, 2, 3))


def test_columns_from_transactions_carries_caches(suite, kp):
    txs = [_tx(suite, kp, i) for i in range(3)]
    for t in txs:
        t.hash(suite), t.sender(suite)
    cols = columns_from_transactions(txs)
    for i, t in enumerate(txs):
        assert cols.hashes[i] == t._hash and cols.senders[i] == t._sender
        assert cols.wire(i) == t.encode()


# -- admission integration ---------------------------------------------------

@pytest.mark.parametrize("way_in", ["frames", "objects"])
def test_submit_columns_statuses_and_batched_crypto(way_in):
    """The per-row verdicts of the one admission body, for both of its
    callers: frames through `submit_columns`, objects (as decoded off a
    wire, nothing cached) through `submit_batch`."""
    counting = CountingSuite(make_suite(False, backend="host"))
    pool = _make_pool(counting)
    kp = counting.generate_keypair(b"columnar-admit")
    good = [_tx(counting, kp, i) for i in range(5)]
    bad = _tx(counting, kp, 98, valid=False)
    wires = [t.encode() for t in good[:2]] + [bad.encode(), b"junk"] + \
        [t.encode() for t in good[2:]]
    want = [TransactionStatus.OK, TransactionStatus.OK,
            TransactionStatus.INVALID_SIGNATURE,
            TransactionStatus.REQUEST_NOT_BELIEVABLE,
            TransactionStatus.OK, TransactionStatus.OK, TransactionStatus.OK]
    if way_in == "objects":  # junk has no object form
        del wires[3], want[3]

    def submit(ws):
        if way_in == "frames":
            return pool.submit_columns(decode_columns(ws))
        return pool.submit_batch([Transaction.decode(w) for w in ws])

    counting.recover_calls = counting.hash_batch_calls = 0
    res = submit(wires)
    assert [r.status for r in res] == want
    if way_in == "frames":
        assert res[3].tx_hash == b""  # no trustworthy identity to report
    assert counting.hash_batch_calls == 1 and counting.recover_calls == 1
    assert pool.pending_count() == 5
    sender = good[0].sender(counting)
    assert [r.sender for r in res if r.status == TransactionStatus.OK] == \
        [sender] * 5
    assert res[0].tx_hash == good[0].hash(counting)
    # a duplicate batch dedupes without a second recover
    counting.recover_calls = 0
    res2 = submit([t.encode() for t in good])
    assert all(r.status == TransactionStatus.ALREADY_IN_TXPOOL
               for r in res2)
    assert counting.recover_calls == 0
    # sealed set returns tx-shaped things whose re-encode is byte-identical
    txs, hashes = pool.seal(10)
    assert sorted(t.encode() for t in txs) == \
        sorted(t.encode() for t in good)


def test_wire_ingest_solo_commit():
    """E2E: raw wire bytes -> ingest lane wire door -> columnar admission
    -> seal -> execute -> commit on a solo node."""
    from fisco_bcos_tpu.init.node import Node, NodeConfig

    node = Node(NodeConfig(consensus="solo", p2p_port=0, rpc_port=0,
                           min_seal_time=0.01))
    node.start()
    try:
        suite = node.suite
        kp = suite.generate_keypair(b"wire-e2e")
        wires = [Transaction(to=pc.BALANCE_ADDRESS,
                             input=b"register w%d 50" % i,
                             nonce=f"wire-{i}", block_limit=600)
                 .sign(suite, kp).encode() for i in range(4)]
        results = [node.ingest.submit_wire(w, timeout=30.0) for w in wires]
        assert all(r.status == TransactionStatus.OK for r in results)
        deadline = time.time() + 20
        while time.time() < deadline:
            # txs may split across blocks — wait for every receipt
            if all(node.ledger.receipt(r.tx_hash) is not None
                   for r in results):
                break
            time.sleep(0.05)
        assert node.ledger.current_number() >= 1
        for r in results:
            assert node.ledger.receipt(r.tx_hash) is not None
    finally:
        node.stop()
