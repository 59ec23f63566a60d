"""Continuous-batching ingest lane (txpool/ingest.py).

Asserts the lane's contract: N concurrent submitters cost FAR fewer
device/native recover calls than N (one `submit_columns` per drained set),
every submitter gets its OWN admission result (including invalid-signature
mixes), a full queue rejects with `TxPoolIsFull` instead of blocking
forever, an idle lane adds no coalescing latency, and the tx-hash cache
survives submit -> seal -> verify_proposal without a rehash.
"""

import threading
import time

import pytest

from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.ledger.ledger import ConsensusNode, Ledger
from fisco_bcos_tpu.protocol import Block, Transaction, TransactionStatus
from fisco_bcos_tpu.storage.memory import MemoryStorage
from fisco_bcos_tpu.txpool import IngestLane, TxPool, TxPoolIsFull
from fisco_bcos_tpu.txpool.txpool import TxSubmitResult
from fisco_bcos_tpu.utils.metrics import REGISTRY


class CountingSuite:
    """Delegating suite wrapper that counts batch crypto entry points —
    the instrument behind every "calls << N" assertion here."""

    def __init__(self, suite):
        self._suite = suite
        self.recover_calls = 0
        self.recover_sigs = 0
        self.hash_batch_calls = 0

    def __getattr__(self, name):
        return getattr(self._suite, name)

    def recover_addresses(self, hashes, sigs):
        self.recover_calls += 1
        self.recover_sigs += len(hashes)
        return self._suite.recover_addresses(hashes, sigs)

    def hash_batch(self, msgs):
        self.hash_batch_calls += 1
        return self._suite.hash_batch(msgs)


class _GatedPool:
    """Pool stub whose submit_columns parks on `gate` — backpressure tests
    use it to hold the dispatcher mid-dispatch while the queue fills."""

    def __init__(self):
        self.gate = threading.Event()
        self.entered = threading.Event()

    def submit_columns(self, cols, broadcast=True):
        self.entered.set()
        assert self.gate.wait(30)
        return [TxSubmitResult(b"\x00" * 32, TransactionStatus.OK)
                for _ in range(len(cols))]


def _make_pool(suite):
    ledger = Ledger(MemoryStorage(), suite)
    ledger.build_genesis([ConsensusNode(b"\x01" * 64)])
    return TxPool(suite, ledger)


def _tx(suite, kp, i, valid=True):
    tx = Transaction(to=pc.BALANCE_ADDRESS, input=b"payload-%d" % i,
                     nonce=f"ing-{i}", block_limit=100).sign(suite, kp)
    if not valid:
        # r = 2^256-1 > curve order: deterministically unrecoverable (a
        # random byte flip can still recover SOME key — ecrecover is
        # total over on-curve r values)
        sig = bytearray(tx.signature)
        sig[:32] = b"\xff" * 32
        tx.signature = bytes(sig)
    return tx


@pytest.fixture()
def counting_lane():
    counting = CountingSuite(make_suite(False, backend="host"))
    pool = _make_pool(counting)
    lane = IngestLane(pool, max_batch=512, max_wait_ms=20.0, queue_cap=1024)
    lane.start()
    yield counting, pool, lane
    lane.stop()


def test_concurrent_submits_coalesce(counting_lane):
    """N threads x M txs -> recover calls << N*M, every result per-tx OK."""
    counting, pool, lane = counting_lane
    kp = counting.generate_keypair(b"ingest-user")
    n_threads, per_thread = 16, 8
    txs = [[_tx(counting, kp, t * per_thread + i)
            for i in range(per_thread)] for t in range(n_threads)]
    counting.recover_calls = 0
    results: dict[int, list] = {}
    barrier = threading.Barrier(n_threads)

    def worker(t):
        barrier.wait()
        results[t] = [lane.submit(tx, timeout=30.0) for tx in txs[t]]

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(60)
    total = n_threads * per_thread
    flat = [r for rs in results.values() for r in rs]
    assert len(flat) == total
    assert all(r.status == TransactionStatus.OK for r in flat)
    assert pool.pending_count() == total
    # the whole point: coalescing must amortize the verify engine. 16
    # concurrent submitters keep the queue non-empty while a dispatch is
    # in flight, so batches grow well past 1 even before the adaptive
    # window engages.
    assert counting.recover_calls <= total // 4, (
        f"{counting.recover_calls} recover calls for {total} txs — "
        f"lane is not coalescing")
    stats = lane.stats()
    assert stats["txs_total"] == total
    assert stats["mean_batch"] > 2.0


def test_per_tx_results_with_invalid_mix(counting_lane):
    """Concurrent valid/invalid submitters each get their own verdict."""
    counting, pool, lane = counting_lane
    kp = counting.generate_keypair(b"ingest-mixed")
    n = 24
    outcomes: dict[int, object] = {}
    barrier = threading.Barrier(n)

    def worker(i):
        tx = _tx(counting, kp, i, valid=(i % 3 != 0))
        barrier.wait()
        outcomes[i] = lane.submit(tx, timeout=30.0)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(60)
    assert len(outcomes) == n
    for i, res in outcomes.items():
        want = TransactionStatus.OK if i % 3 != 0 \
            else TransactionStatus.INVALID_SIGNATURE
        assert res.status == want, f"tx {i}: {res.status} != {want}"


def test_full_queue_rejects_not_blocks():
    """Backpressure: at capacity the lane rejects IMMEDIATELY with
    TxPoolIsFull — no unbounded memory, no blocked submitter."""
    pool = _GatedPool()
    gate = pool.gate
    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"ingest-full")
    lane = IngestLane(pool, max_batch=64, max_wait_ms=0.0, queue_cap=4)
    lane.start()
    try:
        # first tx occupies the dispatcher inside the gated submit_columns
        first = lane.submit_async(_tx(suite, kp, 0))
        assert pool.entered.wait(10)
        # fill the queue to its cap behind the blocked dispatch
        queued = [lane.submit_async(_tx(suite, kp, 1 + i)) for i in range(4)]
        t0 = time.monotonic()
        with pytest.raises(TxPoolIsFull):
            lane.submit_async(_tx(suite, kp, 99))
        assert time.monotonic() - t0 < 1.0, "rejection must not block"
        gate.set()
        for task in [first] + queued:
            assert task.result(30).status == TransactionStatus.OK
        assert lane.stats()["rejected_total"] == 1
    finally:
        gate.set()
        lane.stop()


def test_idle_submit_has_no_coalescing_tax(counting_lane):
    """A lone tx on an idle lane dispatches immediately (window ~0)."""
    counting, pool, lane = counting_lane
    kp = counting.generate_keypair(b"ingest-idle")
    t0 = time.monotonic()
    res = lane.submit(_tx(counting, kp, 0), timeout=10.0)
    elapsed = time.monotonic() - t0
    assert res.status == TransactionStatus.OK
    # generous bound for a loaded CI host; the claim is "no deliberate
    # max_wait park", not a latency SLO
    assert elapsed < 2.0


def test_gossip_bulk_enqueue_drops_over_cap():
    """submit_many_wire_nowait accepts what fits and drops the rest (gossip
    is fire-and-forget; anti-entropy re-delivers)."""
    pool = _GatedPool()
    gate = pool.gate
    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"ingest-gossip")
    lane = IngestLane(pool, max_batch=64, max_wait_ms=0.0, queue_cap=8)
    lane.start()
    try:
        lane.submit_async(_tx(suite, kp, 0))
        assert pool.entered.wait(10)
        wires = [_tx(suite, kp, 1 + i).encode() for i in range(12)]
        accepted = lane.submit_many_wire_nowait(wires)
        assert accepted == 8
        assert lane.stats()["dropped_total"] == 4
    finally:
        gate.set()
        lane.stop()


def test_lane_metrics_emitted(counting_lane):
    counting, pool, lane = counting_lane
    kp = counting.generate_keypair(b"ingest-metrics")
    lane.submit(_tx(counting, kp, 0), timeout=10.0)
    snap = REGISTRY.snapshot()
    assert snap["counters"].get("bcos_ingest_txs_total", 0) >= 1
    assert snap["counters"].get("bcos_ingest_batches_total", 0) >= 1
    assert any(k.startswith("bcos_ingest_batch_size")
               for k in snap["histograms"])
    text = REGISTRY.prometheus_text()
    assert "bcos_ingest_queue_depth" in text
    assert 'bcos_ingest_batch_size_bucket{le="64"}' in text


def test_hash_cache_survives_submit_seal_verify():
    """Satellite: batch_hash fills each tx's cache ONCE at submit; seal and
    verify_proposal reuse it — zero additional hash_batch calls."""
    counting = CountingSuite(make_suite(False, backend="host"))
    pool = _make_pool(counting)
    kp = counting.generate_keypair(b"hash-cache")
    txs = [_tx(counting, kp, i) for i in range(32)]
    for tx in txs:
        assert tx._hash is not None  # sign() hashed it already
    counting.hash_batch_calls = 0
    pool.submit_batch(txs)
    assert counting.hash_batch_calls == 0, "submit rehashed cached txs"
    sealed, hashes = pool.seal(32)
    assert len(sealed) == 32
    block = Block(transactions=sealed)
    assert pool.verify_proposal(block)
    assert counting.hash_batch_calls == 0, (
        "seal/verify_proposal rehashed txs whose hash was cached at submit")
    # a decoded copy (gossip/proposal arrival) hashes ONCE, in one batch
    fresh = [Transaction.decode(tx.encode()) for tx in txs]
    from fisco_bcos_tpu.protocol import batch_hash
    assert batch_hash(fresh, counting) == hashes
    assert counting.hash_batch_calls == 1
    assert batch_hash(fresh, counting) == hashes  # now cached
    assert counting.hash_batch_calls == 1


def test_rpc_concurrent_clients_share_batches():
    """End to end over real HTTP: 8 concurrent sendTransaction clients on
    a live solo node coalesce into shared verify batches, and every
    client gets its own committed receipt (event-driven wait).

    De-flaked for the 2-core CI host: the first dispatch is HELD until the
    whole first cohort is enqueued (deterministic coalescing instead of
    hoping 8 client threads race in before the dispatcher drains), client
    failures propagate as the test failure instead of a confusing
    missing-receipts count, and the join asserts the threads actually
    finished."""
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.sdk.client import SdkClient

    counting = CountingSuite(make_suite(False, backend="host"))
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0), suite=counting)
    node.start()
    try:
        kp = counting.generate_keypair(b"rpc-ingest")
        n_clients, per_client = 8, 4
        wire: dict[int, list[str]] = {}
        for c in range(n_clients):
            wire[c] = []
            for i in range(per_client):
                tx = Transaction(
                    to=pc.BALANCE_ADDRESS,
                    input=pc.encode_call(
                        "register",
                        lambda w, c=c, i=i: w.blob(b"rc%d-%d" % (c, i))
                        .u64(1)),
                    nonce=f"rpc-{c}-{i}", block_limit=100,
                ).sign(counting, kp)
                wire[c].append("0x" + tx.encode().hex())
        counting.recover_calls = 0
        # deterministic readiness: the dispatcher's first submit_columns
        # parks until every client's first tx is in the lane queue (or a
        # generous deadline), so the cohort coalesces regardless of how
        # the scheduler interleaves 8 client threads on 2 cores
        orig_sb = node.txpool.submit_columns
        state = {"first": True}

        def gated_submit(cols, broadcast=True):
            if state["first"]:
                state["first"] = False
                deadline = time.monotonic() + 10
                while (time.monotonic() < deadline
                       and len(cols) + len(node.ingest._q) < n_clients):
                    time.sleep(0.002)
            return orig_sb(cols, broadcast)

        node.txpool.submit_columns = gated_submit
        receipts: dict[int, list] = {}
        errors: list[str] = []
        barrier = threading.Barrier(n_clients)

        def client(c):
            try:
                sdk = SdkClient(f"http://{node.rpc.host}:{node.rpc.port}")
                barrier.wait()
                receipts[c] = [
                    sdk.request("sendTransaction",
                                ["group0", "", tx_hex, False, True, 30.0])
                    for tx_hex in wire[c]]
            except Exception as exc:  # noqa: BLE001 — surface, don't hang
                errors.append(f"client {c}: {type(exc).__name__}: {exc}")

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(n_clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
        assert not any(th.is_alive() for th in threads), \
            "client wedged past join deadline"
        assert not errors, errors
        node.txpool.submit_columns = orig_sb
        flat = [r for rs in receipts.values() for r in rs]
        assert len(flat) == n_clients * per_client
        assert all(r["status"] == 0 for r in flat)
        # coalescing across independent HTTP connections: far fewer
        # recover calls than txs (solo node: submit is the only recover
        # site). With the gated first dispatch this is deterministic:
        # at least the first cohort shares one batch.
        assert counting.recover_calls < n_clients * per_client
        assert node.ingest.stats()["mean_batch"] > 1.0
    finally:
        node.stop()


def test_node_send_transaction_contract_survives_lane_conditions():
    """Node.send_transaction must ALWAYS return a TxSubmitResult (the
    lightnode wire path encodes res.status): a full lane maps to a
    TXPOOL_FULL status, a stopped lane falls back to the direct pool."""
    from fisco_bcos_tpu.init.node import Node, NodeConfig

    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0))
    node.ingest.queue_cap = 1  # follows from the limits; a test's to cut
    node.start()
    try:
        kp = node.suite.generate_keypair(b"contract")
        res = node.send_transaction(_tx(node.suite, kp, 0))
        assert res.status == TransactionStatus.OK
        # wedge the dispatcher, fill the 1-slot queue, then submit: the
        # lane's TxPoolIsFull must surface as a status, not an exception.
        # Deterministic readiness: `entered` proves the dispatcher is
        # parked INSIDE submit_columns (no sleep guessing on a loaded host).
        gate = threading.Event()
        entered = threading.Event()
        orig = node.txpool.submit_columns

        def gated(cols, broadcast=True):
            entered.set()
            gate.wait(20)
            return orig(cols, broadcast)

        node.txpool.submit_columns = gated
        node.ingest.submit_async(_tx(node.suite, kp, 1))
        assert entered.wait(10), "dispatcher never picked up the tx"
        node.ingest.submit_async(_tx(node.suite, kp, 2))  # fills cap=1
        res = node.send_transaction(_tx(node.suite, kp, 3))
        assert res.status == TransactionStatus.TXPOOL_FULL
        gate.set()
        node.txpool.submit_columns = orig
        # stopped lane: falls back to the pool, still a result
        node.ingest.stop()
        res = node.send_transaction(_tx(node.suite, kp, 4))
        assert res.status == TransactionStatus.OK
    finally:
        node.stop()


def test_wait_for_receipt_concurrent_waiters_survive_timeout():
    """Regression: with the old per-hash Event dict, the FIRST waiter to
    time out popped the registration and stranded every other waiter on
    the same hash. The shared condition variable must deliver to all."""

    class _FakeLedger:
        def __init__(self):
            self.receipts = {}

        def current_number(self):
            return 0

        def receipt(self, h):
            return self.receipts.get(h)

    suite = make_suite(False, backend="host")
    ledger = _FakeLedger()
    pool = TxPool(suite, ledger)
    h = b"\xab" * 32
    got: dict[str, object] = {}

    def short_waiter():
        got["short"] = pool.wait_for_receipt(h, timeout=0.15)

    def long_waiter():
        got["long"] = pool.wait_for_receipt(h, timeout=10.0)

    ts = threading.Thread(target=short_waiter)
    tl = threading.Thread(target=long_waiter)
    ts.start()
    tl.start()
    ts.join(5)
    assert got["short"] is None  # timed out before commit
    marker = object()
    ledger.receipts[h] = marker
    pool.on_block_committed(1, [h], [])
    tl.join(5)
    assert not tl.is_alive(), "long waiter stranded after peer timeout"
    assert got["long"] is marker
