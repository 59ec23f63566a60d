"""End-to-end overload control (ISSUE 12).

Covers the four layers of the plane: txpool watermark admission +
priority eviction + typed drop settling (txpool/txpool.py), the ingest
dispatcher's pre-crypto deadline shed (txpool/ingest.py), the edge's
per-client token buckets / fair-share / -32005 (rpc/admission.py +
rpc/edge.py), the busy-state controller with hysteresis
(utils/overload.py + utils/health.py), gossip import gating under busy
(net/txsync.py), the per-peer p2p send-queue's drop-oldest-gossip policy
(net/p2p.py), and a failpoint-armed brownout/recovery run on a live node.
"""

import threading
import time

import pytest

from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.ledger.ledger import ConsensusNode, Ledger
from fisco_bcos_tpu.protocol import Transaction, TransactionStatus
from fisco_bcos_tpu.storage.memory import MemoryStorage
from fisco_bcos_tpu.txpool import IngestLane, TxPool
from fisco_bcos_tpu.txpool.txpool import TxDropped
from fisco_bcos_tpu.utils.metrics import REGISTRY
from fisco_bcos_tpu.utils.overload import OverloadController


class CountingSuite:
    """Delegating wrapper counting batch-recover calls — the instrument
    behind every 'zero crypto for a shed/reject' assertion."""

    def __init__(self, suite):
        self._suite = suite
        self.recover_calls = 0

    def __getattr__(self, name):
        return getattr(self._suite, name)

    def recover_addresses(self, hashes, sigs):
        self.recover_calls += 1
        return self._suite.recover_addresses(hashes, sigs)


def _make_pool(suite, pool_limit=10, low=0.5, high=0.8):
    ledger = Ledger(MemoryStorage(), suite)
    ledger.build_genesis([ConsensusNode(b"\x01" * 64)])
    return TxPool(suite, ledger, pool_limit=pool_limit,
                  low_watermark=low, high_watermark=high)


def _tx(suite, kp, i, block_limit=100, band=0):
    tx = Transaction(to=pc.BALANCE_ADDRESS, input=b"ov-%d" % i,
                     nonce=f"ov-{i}", block_limit=block_limit)
    tx.attribute = (band & 0xFF) << 24  # priority band: attribute's top byte
    tx.sign(suite, kp)
    # wire round-trip: sign() caches _sender, which would let admission
    # skip the recover — decode strips it, like a real client submission
    return Transaction.decode(tx.encode())


@pytest.fixture(scope="module")
def suite():
    return make_suite(False, backend="host")


@pytest.fixture(scope="module")
def kp(suite):
    return suite.generate_keypair(b"overload-tests")


# -- watermark admission + priority eviction --------------------------------

def test_watermark_admission_and_eviction_ordering(suite, kp):
    pool = _make_pool(suite)  # limit 10, low mark 5, high mark 8
    # below the low watermark: everything admits, even near-deadline
    res = pool.submit_batch([_tx(suite, kp, i, block_limit=2)
                             for i in range(3)])
    assert all(r.status == TransactionStatus.OK for r in res)
    res = pool.submit_batch([_tx(suite, kp, 10 + i, block_limit=50)
                             for i in range(4)])
    assert all(r.status == TransactionStatus.OK for r in res)
    assert pool.status()["pending"] == 7  # between the watermarks now

    # between watermarks: a band-0 tx without deadline slack is shed with
    # the TYPED status; a long-deadline one still admits
    shed = pool.submit_batch([_tx(suite, kp, 20, block_limit=2)])[0]
    assert shed.status == TransactionStatus.DEADLINE_UNMEETABLE
    ok = pool.submit_batch([_tx(suite, kp, 21, block_limit=90)])[0]
    assert ok.status == TransactionStatus.OK
    assert pool.status()["pending"] == 8  # at the high watermark

    # at the high watermark: an equal-priority tx is refused (FULL), a
    # higher-band tx admits by EVICTING the lowest-priority/soonest-
    # expiring pending tx — which settles with TXPOOL_EVICTED
    full = pool.submit_batch([_tx(suite, kp, 30, block_limit=2)])[0]
    assert full.status == TransactionStatus.TXPOOL_FULL
    victims = pool._victims_locked()
    victim_hash = victims[0][2]  # lowest (band, block_limit)
    win = pool.submit_batch([_tx(suite, kp, 31, block_limit=90,
                                 band=1)])[0]
    assert win.status == TransactionStatus.OK
    assert pool.status()["pending"] == 8  # exchanged, not grown
    assert pool.dropped_status(victim_hash) == \
        TransactionStatus.TXPOOL_EVICTED

    # eviction order among the survivors: bands before deadlines — a
    # band-1 incomer must evict a band-0 tx before any band-1 tx
    vb = [v[0] for v in pool._victims_locked()]
    assert vb == sorted(vb)


def test_full_pool_reject_pays_zero_crypto(suite, kp):
    counting = CountingSuite(suite)
    pool = _make_pool(counting)  # high mark 8
    res = pool.submit_batch([_tx(suite, kp, i, block_limit=50)
                             for i in range(8)])
    assert all(r.status == TransactionStatus.OK for r in res)
    before = counting.recover_calls
    # equal-priority txs against a high-watermark pool: rejected in the
    # PRE-crypto phase — zero recover calls for the whole batch
    res = pool.submit_batch([_tx(suite, kp, 100 + i, block_limit=50)
                             for i in range(5)])
    assert all(r.status == TransactionStatus.TXPOOL_FULL for r in res)
    assert counting.recover_calls == before, \
        "full-pool reject must not reach the crypto lane"


def test_consensus_imports_bypass_watermark_admission(suite, kp):
    """fetch-missing (proposal verification) must import into a SATURATED
    pool: a replica refusing the leader's txs would view-change exactly
    while overloaded (found in review)."""
    pool = _make_pool(suite)  # high mark 8
    res = pool.submit_batch([_tx(suite, kp, i, block_limit=50)
                             for i in range(8)])
    assert all(r.status == TransactionStatus.OK for r in res)
    blocked = pool.submit_batch([_tx(suite, kp, 50, block_limit=50)])[0]
    assert blocked.status == TransactionStatus.TXPOOL_FULL
    proposal_tx = _tx(suite, kp, 51, block_limit=50)
    ok = pool.submit_batch([proposal_tx], broadcast=False,
                           consensus=True)[0]
    assert ok.status == TransactionStatus.OK
    # the drop verdict is node-local: the nonce is NOT freed on drop (a
    # peer may still commit the gossiped tx) — same-nonce resubmits stay
    # blocked for the window
    victims = pool._victims_locked()
    vh = victims[0][2]
    vtx = pool._pending[vh]
    pool.submit_batch([_tx(suite, kp, 52, block_limit=90, band=3)])
    assert pool.dropped_status(vh) is not None
    dup = Transaction(to=pc.BALANCE_ADDRESS, input=b"other",
                      nonce=vtx.nonce, block_limit=90).sign(suite, kp)
    r = pool.submit_batch([Transaction.decode(dup.encode())])[0]
    assert r.status == TransactionStatus.NONCE_CHECK_FAIL


def test_evicted_tx_settles_waiters_promptly(suite, kp):
    pool = _make_pool(suite)
    # the eventual victim: unique lowest block_limit, with BOTH kinds of
    # waiter attached (async task + a parked wait_for_receipt thread)
    victim = _tx(suite, kp, 0, block_limit=30)
    task = pool.submit_async(victim)
    h = victim.hash(suite)
    got: dict = {}

    def waiter():
        t0 = time.monotonic()
        try:
            pool.wait_for_receipt(h, timeout=20.0)
            got["result"] = "receipt-or-timeout"
        except TxDropped as exc:
            got["result"] = exc.status
        got["seconds"] = time.monotonic() - t0

    th = threading.Thread(target=waiter, daemon=True)
    th.start()
    time.sleep(0.1)  # let the waiter park on the CV
    for i in range(7):  # fill to the high mark
        pool.submit_batch([_tx(suite, kp, 1 + i, block_limit=60)])
    r = pool.submit_batch([_tx(suite, kp, 50, block_limit=90, band=2)])[0]
    assert r.status == TransactionStatus.OK
    th.join(timeout=5)
    assert not th.is_alive(), "waiter still parked after eviction"
    assert got["result"] == TransactionStatus.TXPOOL_EVICTED
    assert got["seconds"] < 5.0, "settle must be prompt, not timeout-bound"
    with pytest.raises(TxDropped):
        task.result(1.0)
    # wait_for_receipt on the already-recorded drop raises immediately
    with pytest.raises(TxDropped):
        pool.wait_for_receipt(h, timeout=5.0)


def test_seal_drops_expired_for_target_height_with_typed_status(suite, kp):
    pool = _make_pool(suite, pool_limit=50)
    short = _tx(suite, kp, 0, block_limit=3)
    long_ = _tx(suite, kp, 1, block_limit=9)
    pool.submit_batch([short, long_])
    # sealing for height 4: the block_limit=3 tx would be expired INSIDE
    # its own block — dropped with the typed status, zero seal slots
    txs, hashes = pool.seal(10, for_number=4)
    assert [t.nonce for t in txs] == ["ov-1"]
    assert pool.dropped_status(short.hash(suite)) == \
        TransactionStatus.BLOCK_LIMIT_CHECK_FAIL
    # block_limit == target height is still sealable (valid through it)
    pool.unseal(hashes)
    txs, _ = pool.seal(10, for_number=9)
    assert [t.nonce for t in txs] == ["ov-1"]


# -- ingest dispatcher: an expired frame is answered before any crypto ------

def test_ingest_dispatcher_sheds_expired_before_crypto(suite, kp):
    from fisco_bcos_tpu.txpool.ingest import _Entry
    from fisco_bcos_tpu.utils.task import Task

    counting = CountingSuite(suite)
    pool = _make_pool(counting, pool_limit=50)
    lane = IngestLane(pool)  # not started: dispatch driven directly
    expired = _tx(suite, kp, 0, block_limit=0)  # <= current height (0)
    live = _tx(suite, kp, 1, block_limit=50)
    e1, e2 = _Entry(expired.encode(), Task()), _Entry(live.encode(), Task())
    before = counting.recover_calls
    lane._dispatch([e1, e2])
    r1 = e1.task.result(1.0)
    assert r1.status == TransactionStatus.BLOCK_LIMIT_CHECK_FAIL
    assert e2.task.result(1.0).status == TransactionStatus.OK
    # exactly ONE recover, of the live row alone: the pool's precheck
    # answered the expired one before the batch verify
    assert counting.recover_calls == before + 1

    # an all-expired batch costs zero crypto
    e3 = _Entry(_tx(suite, kp, 2, block_limit=0).encode(), Task())
    before = counting.recover_calls
    lane._dispatch([e3])
    assert e3.task.result(1.0).status == \
        TransactionStatus.BLOCK_LIMIT_CHECK_FAIL
    assert counting.recover_calls == before


# -- edge admission: token buckets + fairness -------------------------------

def test_token_bucket_fairness_ten_to_one():
    from fisco_bcos_tpu.rpc.admission import ClientAdmission

    clock = [0.0]
    adm = ClientAdmission(write_rate=10.0, write_burst=10.0,
                          clock=lambda: clock[0])
    admits = {"aggr": 0, "polite": 0}
    # 30 simulated seconds in 10 ms steps: the aggressor offers every
    # step (100/s), the polite client every 10th step (10/s) — 10:1
    for step in range(3000):
        clock[0] = step * 0.01
        if adm.try_admit("aggr", True) is None:
            adm.release("aggr")
            admits["aggr"] += 1
        if step % 10 == 0 and adm.try_admit("polite", True) is None:
            adm.release("polite")
            admits["polite"] += 1
    # near-equal admitted share: both are clamped to ~rate * 30s
    ratio = admits["aggr"] / max(1, admits["polite"])
    assert 0.8 <= ratio <= 1.3, admits
    assert admits["polite"] >= 250  # polite traffic passed ~unscathed


def test_fair_share_concurrency_and_retry_hint():
    from fisco_bcos_tpu.rpc.admission import ClientAdmission

    adm = ClientAdmission(fair_capacity=8)  # no token limits: rate 0
    for _ in range(8):
        assert adm.try_admit("hog", True) is None
    retry = adm.try_admit("hog", True)  # past its share (sole client: 8)
    assert isinstance(retry, int) and retry >= 1
    # a second client still admits — the hog's monopoly is bounded
    assert adm.try_admit("newcomer", False) is None
    # with two ACTIVE clients the hog's share halves; it stays rejected
    assert isinstance(adm.try_admit("hog", True), int)
    for _ in range(8):
        adm.release("hog")
    assert adm.try_admit("hog", True) is None  # slots freed -> admitted


def test_batch_bodies_bill_per_entry_not_per_request(suite):
    """A JSON-RPC batch must charge one write token PER sendTransaction
    entry (found in review: per-body billing multiplied the budget by
    max_batch)."""
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.sdk.client import SdkClient

    node = Node(NodeConfig(consensus="solo", crypto_backend="host",
                           min_seal_time=0.0, rpc_port=0,
                           client_write_rate=3.0, client_write_burst=6.0))
    node.start()
    try:
        kp2 = node.suite.generate_keypair(b"batch-bill")
        sdk = SdkClient(f"http://{node.rpc.host}:{node.rpc.port}")

        def call(i):
            tx = Transaction(to=pc.BALANCE_ADDRESS,
                             input=pc.encode_call(
                                 "register",
                                 lambda w: w.blob(b"bb%d" % i).u64(1)),
                             nonce=f"bb-{i}",
                             block_limit=100).sign(node.suite, kp2)
            return ("sendTransaction",
                    ["group0", "", "0x" + tx.encode().hex(), False, False])

        from fisco_bcos_tpu.sdk.client import RpcCallError
        # first 10-write batch: gated at the 6-token burst but CHARGED
        # its full 10-entry cost — the bucket goes into DEBT (per-body
        # billing would have charged 1 token and left 5; the 256x bypass
        # this regression pins)
        out = sdk.request_batch([call(i) for i in range(10)])
        assert all("result" in o for o in out), out
        # an immediate second batch is rejected whole with -32005
        try:
            out = sdk.request_batch([call(100 + i) for i in range(10)])
            raise AssertionError(f"batch admitted: {out[:2]}")
        except RpcCallError as exc:
            assert exc.code == -32005
        # refills pay the 4-token debt FIRST: after ~2.5s (+7.5 tokens)
        # the balance is ~3.5 and a small batch admits again
        time.sleep(2.5)
        out = sdk.request_batch([call(200), call(201)])
        assert all("result" in o for o in out), out
    finally:
        node.stop()


def test_sub_one_burst_paces_instead_of_banning():
    """rate 0.4/s (burst would default to 0.8 < the 1-token gate) must
    throttle, not permanently reject (found in review)."""
    from fisco_bcos_tpu.rpc.admission import ClientAdmission

    clock = [0.0]
    adm = ClientAdmission(write_rate=0.4, clock=lambda: clock[0])
    admits = 0
    for step in range(40):  # 100 simulated seconds in 2.5s steps
        clock[0] = step * 2.5
        if adm.try_admit("slow", True) is None:
            adm.release("slow")
            admits += 1
    assert 30 <= admits <= 45, admits  # ~0.4/s over 100s, not zero


def test_lru_never_evicts_the_just_inserted_client():
    from fisco_bcos_tpu.rpc.admission import ClientAdmission

    adm = ClientAdmission(fair_capacity=10_000)
    adm.MAX_CLIENTS = 4  # shrink the bound for the test
    for i in range(4):  # all tracked clients HOLD inflight slots
        assert adm.try_admit(f"hold{i}", False) is None
    assert adm.try_admit("newcomer", False) is None
    adm.release("newcomer")  # must find its entry: _active returns to 4
    assert adm.stats()["active"] == 4
    for i in range(4):
        adm.release(f"hold{i}")
    assert adm.stats()["active"] == 0


def test_submit_async_settles_when_drop_races_registration(suite, kp):
    """A tx dropped between submit() and the waiter registration must
    still settle the task with TxDropped (found in review)."""
    pool = _make_pool(suite, pool_limit=50)
    tx = _tx(suite, kp, 0, block_limit=30)
    orig_receipt = pool.ledger.receipt
    hooked = {"done": False}

    def racing_receipt(h, _orig=orig_receipt):
        # fire the drop INSIDE submit_async's post-submit window, before
        # the waiter registration's own re-check runs
        if not hooked["done"] and pool.pending_count() == 1:
            hooked["done"] = True
            drops = []
            with pool._lock:
                t = pool._drop_locked(
                    h, TransactionStatus.TXPOOL_EVICTED)
                drops.append((h, TransactionStatus.TXPOOL_EVICTED, t))
            pool._settle_dropped(drops)
        return _orig(h)

    pool.ledger.receipt = racing_receipt
    try:
        task = pool.submit_async(tx)
    finally:
        pool.ledger.receipt = orig_receipt
    with pytest.raises(TxDropped):
        task.result(2.0)


def test_escaped_json_cannot_smuggle_writes_past_the_scan():
    """`"sendTransactio\\u006e"` decodes to the write method but evades
    the byte scan — escaped payloads must bill conservatively as writes
    (found in review)."""
    from fisco_bcos_tpu.rpc.admission import ClientAdmission, admit_payload

    clock = [0.0]
    adm = ClientAdmission(write_rate=1.0, write_burst=1.0,
                          clock=lambda: clock[0])
    smuggled = (b'{"jsonrpc":"2.0","id":1,'
                b'"method":"sendTransactio\\u006e","params":[]}')
    assert admit_payload(adm, "c", smuggled) is None  # burst token
    adm.release("c")
    retry = admit_payload(adm, "c", smuggled)  # billed as a WRITE
    assert isinstance(retry, int) and retry >= 1
    # plain reads stay unmetered (read_rate 0)
    plain = b'{"jsonrpc":"2.0","id":2,"method":"getBlockNumber"}'
    assert admit_payload(adm, "c", plain) is None
    adm.release("c")


def test_busy_shrinks_write_budget_only():
    from fisco_bcos_tpu.rpc.admission import ClientAdmission

    class FakeOverload:
        factor = 1.0

        def write_rate_factor(self):
            return self.factor

    clock = [0.0]
    ov = FakeOverload()
    # bursts of a few tokens: strict per-step refill would alias with
    # float accumulation in the simulated clock
    adm = ClientAdmission(write_rate=100.0, write_burst=5.0,
                          read_rate=100.0, read_burst=5.0,
                          overload=ov, clock=lambda: clock[0])

    def drain(kind_write):
        n = 0
        for step in range(100):  # 1 simulated second, 10ms steps
            clock[0] += 0.01
            if adm.try_admit("c", kind_write) is None:
                adm.release("c")
                n += 1
        return n

    base_w = drain(True)
    ov.factor = 0.25  # brownout: busy shrinks WRITES by 4x...
    busy_w = drain(True)
    busy_r = drain(False)  # ...while READS keep their full budget
    assert busy_w < base_w * 0.5, (base_w, busy_w)
    assert busy_r > base_w * 0.6, (base_w, busy_r)


def test_edge_answers_32005_with_retry_hint(suite):
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.sdk.client import RpcCallError, SdkClient

    node = Node(NodeConfig(consensus="solo", crypto_backend="host",
                           min_seal_time=0.0, rpc_port=0,
                           client_write_rate=1.0, client_write_burst=1.0))
    node.start()
    try:
        kp2 = node.suite.generate_keypair(b"edge-32005")
        sdk = SdkClient(f"http://{node.rpc.host}:{node.rpc.port}")

        def send(i, wait=False):
            tx = Transaction(to=pc.BALANCE_ADDRESS,
                             input=pc.encode_call(
                                 "register",
                                 lambda w: w.blob(b"e%d" % i).u64(1)),
                             nonce=f"edge-{i}",
                             block_limit=100).sign(node.suite, kp2)
            return sdk.send_transaction(tx, wait=wait)

        send(0)  # consumes the single-token burst
        with pytest.raises(RpcCallError) as ei:
            send(1)
        assert ei.value.code == -32005
        # reads ride a SEPARATE (here unlimited) budget: never throttled
        for _ in range(20):
            sdk.get_block_number()
        # the raw reject body carries the retryAfterMs hint
        from fisco_bcos_tpu.rpc.admission import rate_limited_body
        assert b'"retryAfterMs"' in rate_limited_body(123)
    finally:
        node.stop()


def test_ws_edge_shares_the_admission_budget(suite):
    """The WS endpoint must not be an unmetered side door around the
    token buckets (found in review): the same write budget applies."""
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.sdk.client import RpcCallError
    from fisco_bcos_tpu.sdk.ws import WsSdkClient

    node = Node(NodeConfig(consensus="solo", crypto_backend="host",
                           min_seal_time=0.0, ws_port=0,
                           client_write_rate=1.0, client_write_burst=1.0))
    node.start()
    try:
        kp2 = node.suite.generate_keypair(b"ws-32005")
        cli = WsSdkClient("127.0.0.1", node.ws.port)
        try:
            def send(i):
                tx = Transaction(to=pc.BALANCE_ADDRESS,
                                 input=pc.encode_call(
                                     "register",
                                     lambda w: w.blob(b"w%d" % i).u64(1)),
                                 nonce=f"wsov-{i}",
                                 block_limit=100).sign(node.suite, kp2)
                return cli.request("sendTransaction",
                                   ["group0", "", "0x" + tx.encode().hex(),
                                    False, False])

            send(0)  # consumes the single-token burst
            with pytest.raises(RpcCallError) as ei:
                send(1)
            assert ei.value.code == -32005
            # reads stay unmetered (separate budget, here unlimited)
            for _ in range(10):
                cli.get_block_number()
        finally:
            cli.close()
    finally:
        node.stop()


# -- busy-state controller: hysteresis --------------------------------------

def test_busy_hysteresis_no_flapping():
    from fisco_bcos_tpu.utils.health import Health

    clock = [0.0]
    health = Health()
    load = [0.0]
    ctl = OverloadController(health=health, enter=0.8, exit=0.5,
                             hold_s=1.0, alpha=1.0,  # no smoothing: the
                             clock=lambda: clock[0])  # hysteresis alone
    ctl.add_signal("x", lambda: load[0])

    def tick(t, v):
        clock[0], load[0] = t, v
        ctl.sample_once()

    tick(0.0, 1.0)
    assert not ctl.busy()  # crossing seen, hold not yet served
    tick(0.5, 1.0)
    assert not ctl.busy()
    tick(1.1, 1.0)
    assert ctl.busy() and health.state() == "busy"
    assert health.sealing_allowed() and not health.writes_shed()
    # oscillation BETWEEN the thresholds: stays busy, no flapping
    for i, v in enumerate((0.6, 0.9, 0.55, 0.85, 0.6)):
        tick(1.2 + i * 0.3, v)
    assert ctl.busy() and ctl.stats()["transitions"] == 1
    # sustained recovery below exit: leaves busy after the hold
    tick(3.0, 0.2)
    assert ctl.busy()
    tick(3.5, 0.2)
    assert ctl.busy()
    tick(4.1, 0.2)
    assert not ctl.busy() and health.state() == "ok"
    assert ctl.stats()["transitions"] == 2
    # a dip that RECOVERS before the hold never clears busy (and vice
    # versa on entry): re-enter and test the cancelled exit crossing
    tick(5.0, 1.0)
    tick(6.1, 1.0)
    assert ctl.busy()
    tick(6.2, 0.2)   # dip starts
    tick(6.5, 0.9)   # ...but load returns before hold_s elapses
    tick(7.6, 0.9)
    assert ctl.busy() and ctl.stats()["transitions"] == 3


def test_busy_gauge_slots_between_health_levels():
    from fisco_bcos_tpu.utils.health import Health
    from fisco_bcos_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    h = Health(registry=reg)
    assert reg.snapshot()["gauges"]["bcos_node_health"] == 0
    h.busy("overload", "test")
    assert reg.snapshot()["gauges"]["bcos_node_health"] == 0.5
    h.degraded("storage", "worse")  # degraded outranks busy
    assert reg.snapshot()["gauges"]["bcos_node_health"] == 1
    h.clear("storage")
    assert h.state() == "busy"
    h.clear("overload")
    assert reg.snapshot()["gauges"]["bcos_node_health"] == 0


# -- gossip import gating under busy ----------------------------------------

def test_gossip_import_gated_while_busy(suite, kp):
    from fisco_bcos_tpu.net.front import FrontService
    from fisco_bcos_tpu.net.gateway import FakeGateway
    from fisco_bcos_tpu.net.txsync import TransactionSync

    gw = FakeGateway()
    pool_a = _make_pool(suite, pool_limit=100)
    pool_b = _make_pool(suite, pool_limit=100)
    front_a = FrontService(b"\xaa" * 8, gw)
    front_b = FrontService(b"\xbb" * 8, gw)
    gate_open = [False]
    ts_a = TransactionSync(front_a, pool_a, suite,
                           anti_entropy_interval=0.3)
    ts_b = TransactionSync(front_b, pool_b, suite,
                           import_gate=lambda: gate_open[0])
    ts_a.start()
    ts_b.start()
    try:
        gated0 = REGISTRY.snapshot()["counters"].get(
            "bcos_txsync_import_gated_total", 0)
        tx = _tx(suite, kp, 0, block_limit=50)
        pool_a.submit_batch([tx])  # broadcast hook gossips to B
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            if REGISTRY.snapshot()["counters"].get(
                    "bcos_txsync_import_gated_total", 0) > gated0:
                break
            time.sleep(0.05)
        assert pool_b.pending_count() == 0, \
            "busy node must not import remote pending txs"
        assert REGISTRY.snapshot()["counters"].get(
            "bcos_txsync_import_gated_total", 0) > gated0
        # recovery: the gate opens and A's anti-entropy sweep re-delivers
        gate_open[0] = True
        deadline = time.monotonic() + 6.0
        while time.monotonic() < deadline and pool_b.pending_count() == 0:
            time.sleep(0.05)
        assert pool_b.pending_count() == 1
    finally:
        ts_a.stop()
        ts_b.stop()
        gw.stop()


# -- p2p send queue: drop-oldest gossip, never consensus --------------------

def _front_frame(module: int, kind: int = 0,
                 payload: bytes = b"x" * 100) -> bytes:
    from fisco_bcos_tpu.codec.wire import Writer
    return Writer().u16(module).u8(kind).u64(0).blob(payload).bytes()


def test_p2p_sendq_drops_oldest_gossip_never_consensus():
    from fisco_bcos_tpu.net.moduleid import ModuleID
    from fisco_bcos_tpu.net.p2p import _Session, _is_gossip

    assert _is_gossip(_front_frame(int(ModuleID.TxsSync)))
    assert not _is_gossip(_front_frame(int(ModuleID.PBFT)))
    # TxsSync REQUEST/RESPONSE = PBFT's fetch-missing path: protected
    assert not _is_gossip(_front_frame(int(ModuleID.TxsSync), kind=1))
    assert not _is_gossip(_front_frame(int(ModuleID.TxsSync), kind=2))
    # mux-tagged frames classify through the group tag
    from fisco_bcos_tpu.net.gateway import MUX_MAGIC
    tagged = bytes([MUX_MAGIC, 2]) + b"g0" + \
        _front_frame(int(ModuleID.TxsSync))
    assert _is_gossip(tagged)

    class BlockedSock:
        def sendall(self, data):
            time.sleep(60)  # writer parks on the first frame it picks up

        def close(self):
            pass

    sess = _Session(b"\xcc" * 8, BlockedSock(), lambda s: None,
                    max_queue=1000)
    sess.start()  # writer thread is no longer started by __init__
    try:
        # park the writer on a sacrificial frame so everything after
        # stays QUEUED deterministically
        assert sess.enqueue(b"p" * 10, droppable=False)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and sess._bytes:
            time.sleep(0.01)
        assert sess._bytes == 0, "writer never picked up the park frame"

        gossip = b"g" * 300
        consensus = b"c" * 300
        assert sess.enqueue(gossip, droppable=True)
        assert sess.enqueue(gossip, droppable=True)
        assert sess.enqueue(gossip, droppable=True)
        # queue 900/1000: a consensus frame evicts the OLDEST gossip
        assert sess.enqueue(consensus, droppable=False)
        assert sess.dropped == 1
        # two more consensus frames: evict remaining gossip, never each
        # other...
        assert sess.enqueue(consensus, droppable=False)
        assert sess.enqueue(consensus, droppable=False)
        assert sess.dropped == 3
        # ...and once only consensus remains, overflow refuses the NEW
        # frame instead of evicting protected backlog
        assert not sess.enqueue(consensus, droppable=False)
        with sess._cv:
            live = [e for e in sess._q if not e[2]]
            assert live and all(not e[1] for e in live), \
                "every surviving live frame is consensus-class"
        counters = REGISTRY.snapshot()["counters"]
        peer = (b"\xcc" * 8)[:8].hex()
        assert counters.get("bcos_p2p_sendq_dropped_total"
                            f"{{'kind': 'gossip', 'peer': '{peer}'}}",
                            0) >= 3
    finally:
        sess.close()


# -- failpoint-armed brownout + recovery on a live node ---------------------

def test_failpoint_commit_stall_triggers_brownout_and_recovery():
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.utils import failpoints as fp

    node = Node(NodeConfig(
        consensus="solo", crypto_backend="host", min_seal_time=0.0,
        tx_count_limit=5, txpool_limit=40,
        overload_enter=0.6, overload_exit=0.3, overload_hold_s=0.2,
        client_write_rate=0.0))
    suite2, kp2 = node.suite, node.suite.generate_keypair(b"brownout")
    node.start()
    try:
        # stall every commit: the pool backlog (the brownout signal here)
        # grows while the sealer keeps sealing through it
        fp.arm("scheduler.2pc.commit", "sleep(250)*40")
        txs = []
        for i in range(36):
            txs.append(Transaction(
                to=pc.BALANCE_ADDRESS,
                input=pc.encode_call(
                    "register", lambda w, i=i: w.blob(b"bo%d" % i).u64(1)),
                nonce=f"bo-{i}", block_limit=200).sign(suite2, kp2))
        res = node.txpool.submit_batch(txs)
        assert all(int(r.status) == 0 for r in res)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not node.overload.busy():
            time.sleep(0.05)
        assert node.overload.busy(), node.overload.stats()
        assert node.health.state() == "busy"
        # brownout, not blackout: sealing continues, writes NOT shed,
        # remote-tx import IS gated
        assert node.health.sealing_allowed()
        assert not node.health.writes_shed()
        assert not node.accepting_remote_txs()
        extra = Transaction(
            to=pc.BALANCE_ADDRESS,
            input=pc.encode_call("register",
                                 lambda w: w.blob(b"bo-x").u64(1)),
            nonce="bo-x", block_limit=200).sign(suite2, kp2)
        assert int(node.send_transaction(extra).status) == 0
        # recovery: disarm, drain, and the hysteresis exits busy
        fp.disarm("scheduler.2pc.commit")
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and (
                node.txpool.pending_count() > 0 or node.overload.busy()):
            time.sleep(0.1)
        assert not node.overload.busy(), node.overload.stats()
        assert node.health.state() == "ok"
        assert node.accepting_remote_txs()
    finally:
        fp.disarm_all()
        node.stop()


# -- ini round-trip of the overload knobs -----------------------------------

def test_overload_config_ini_roundtrip():
    from fisco_bcos_tpu.init.node import NodeConfig
    from fisco_bcos_tpu.tool.config import (node_config_from_ini,
                                            node_config_to_ini)

    cfg = NodeConfig(txpool_low_watermark=0.6, txpool_high_watermark=0.9,
                     overload_enabled=False, overload_enter=0.7,
                     overload_exit=0.4, overload_hold_s=1.5,
                     overload_commit_backlog=9,
                     overload_busy_write_factor=0.5,
                     client_write_rate=123.0, client_write_burst=456.0,
                     client_read_rate=789.0, client_read_burst=1000.0)
    back = node_config_from_ini(node_config_to_ini(cfg))
    for field in ("txpool_low_watermark", "txpool_high_watermark",
                  "overload_enabled", "overload_enter", "overload_exit",
                  "overload_hold_s", "overload_commit_backlog",
                  "overload_busy_write_factor", "client_write_rate",
                  "client_write_burst", "client_read_rate",
                  "client_read_burst"):
        assert getattr(back, field) == getattr(cfg, field), field


# -- compaction-debt backpressure (ISSUE 17) --------------------------------

def test_compaction_debt_backpressure_ok_busy_ok(tmp_path):
    """A compaction-starved node under write load must transition
    ok -> busy on debt (the overload plane's `compaction_debt` signal),
    KEEP serving reads while busy, and drain back to ok once the
    compactor catches up — the contract that keeps a node from silently
    falling behind its own write rate at GB scale."""
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    node = Node(NodeConfig(
        consensus="solo", crypto_backend="host",
        storage_backend="disk", storage_path=str(tmp_path / "data"),
        storage_memtable_mb=0,           # flush on every write batch
        storage_compact_segments=2,
        overload_hold_s=0.0,             # deterministic: no hold window
        overload_compact_debt_mb=1))     # 1 MB of debt saturates the signal
    try:
        engine = node.storage.backend    # key_page_size=auto wraps disk
        assert type(engine).__name__ == "DiskStorage"
        node.overload.sample_once()
        assert "compaction_debt" in node.overload.stats()["signals"]
        assert not node.overload.busy()

        engine._compactor.pause()        # starve compaction deliberately
        rows = [(b"bp%04d-%02d" % (i, j), b"x" * 2048)
                for i in range(24) for j in range(32)]
        for i in range(0, len(rows), 32):
            engine.set_batch("t", rows[i:i + 32])  # one flush per batch
        assert engine.compaction_debt_bytes() > (1 << 20)
        for _ in range(8):               # EWMA convergence over enter=0.85
            node.overload.sample_once()
        assert node.overload.busy()
        status = node.system_status()
        assert status["health"]["state"] == "busy"
        # reads keep serving while writes are being shed
        assert engine.get("t", b"bp0000-00") == b"x" * 2048
        assert engine.get("t", b"bp0023-31") == b"x" * 2048
        assert REGISTRY.snapshot()["gauges"][
            "bcos_storage_compaction_debt_bytes"] > 0

        engine._compactor.resume()       # catch-up drains the backlog
        deadline = time.monotonic() + 60
        while engine.compaction_debt_bytes() > 0:
            assert time.monotonic() < deadline, "debt never drained"
            time.sleep(0.05)
        for _ in range(16):              # EWMA decay below exit=0.5
            node.overload.sample_once()
        assert not node.overload.busy()
        assert node.system_status()["health"]["state"] == "ok"
        assert engine.get("t", b"bp0000-00") == b"x" * 2048
    finally:
        node.stop()
        node.storage.close()


def test_a_routine_merge_is_not_compaction_starvation(tmp_path):
    """The debt cap follows the engine's geometry unless the operator sets
    one: the whole L0 a routine merge finds (the debt signal's jump at
    every trigger) scores a quarter, far from busy; an L0 left to grow to
    four such loads scores 1.0."""
    from fisco_bcos_tpu.init.node import (Node, NodeConfig,
                                          compact_debt_cap_mb)

    cfg = NodeConfig(consensus="solo", crypto_backend="host",
                     storage_backend="disk",
                     storage_path=str(tmp_path / "data"),
                     storage_memtable_mb=1, storage_compact_segments=2,
                     overload_hold_s=0.0)
    assert cfg.overload_compact_debt_mb == 0
    assert compact_debt_cap_mb(cfg) == 12
    assert compact_debt_cap_mb(NodeConfig()) == 4 * 9 * 64
    node = Node(cfg)
    try:
        engine = node.storage.backend
        engine._compactor.pause()
        rows = [(b"k%05d" % i, b"x" * 1000) for i in range(1100)]
        for n in range(3):               # three flushed memtables: L0 over
            engine.set_batch("t%d" % n, rows)
        assert len(engine._levels[0]) == 3
        debt = engine.compaction_debt_bytes()
        assert 3 << 20 <= debt < 4 << 20
        for _ in range(12):
            node.overload.sample_once()
        st = node.overload.stats()
        assert 0.2 < st["signals"]["compaction_debt"] < 0.35
        assert not node.overload.busy()
        for n in range(3, 12):           # starved: four such loads pile up
            engine.set_batch("t%d" % n, rows)
        for _ in range(12):
            node.overload.sample_once()
        assert node.overload.stats()["signals"]["compaction_debt"] >= 0.95
        assert node.overload.busy()
    finally:
        node.stop()
        node.storage.close()
