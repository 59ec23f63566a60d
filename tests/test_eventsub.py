"""Push-based subscription plane (rpc/eventsub.SubHub) — correctness
under adversity, and the zero-extra-render claim.

The plane's contract: commit-time fan-out sources the SAME serialized
fragment bytes the QueryCache primed, so a notification costs buffer
joins — zero extra `json.dumps`, zero recover batches beyond the
existing prime — and the cache-generation fence means a rollback or
snapshot install can never push a stale fragment. Delivery rides the
bounded per-session outbox: a never-draining subscriber sheds (droppable
streams) or is killed (lossless) without delaying anyone else.
"""

import itertools
import json
import threading
import time

import pytest

from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.rpc.eventsub import EventFilter, SubLimitError


def wait_until(pred, timeout=15.0, tick=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(tick)
    return pred()


def _mk_node(**kw):
    cfg = NodeConfig(crypto_backend="host", min_seal_time=0.0,
                     rpc_port=0, **kw)
    node = Node(cfg)
    node.start()
    return node


def _register(node, kp, name: bytes, value: int, nonce: str):
    """-> (receipt, tx_hash)"""
    tx = Transaction(to=pc.BALANCE_ADDRESS,
                     input=pc.encode_call(
                         "register", lambda w: w.blob(name).u64(value)),
                     nonce=nonce, block_limit=100).sign(node.suite, kp)
    h = tx.hash(node.suite)
    rc = node.txpool.wait_for_receipt(node.send_transaction(tx).tx_hash, 30)
    assert rc is not None and rc.status == 0, rc
    return rc, h


def _transfer(node, kp, src: bytes, dst: bytes, amount: int, nonce: str):
    tx = Transaction(to=pc.BALANCE_ADDRESS,
                     input=pc.encode_call(
                         "transfer", lambda w: w.blob(src).blob(dst)
                         .u64(amount)),
                     nonce=nonce, block_limit=100).sign(node.suite, kp)
    rc = node.txpool.wait_for_receipt(node.send_transaction(tx).tx_hash, 30)
    assert rc is not None and rc.status == 0, rc
    return rc


class _Sink:
    """In-process subscriber: records decoded notification frames."""

    def __init__(self):
        self.frames: list[dict] = []
        self.ok = True

    def __call__(self, frame: bytes, lossless: bool, t0) -> bool:
        if not self.ok:
            return False
        self.frames.append(json.loads(frame))
        return True

    def results(self):
        return [f["params"]["result"] for f in self.frames]


# ---------------------------------------------------------------------------
# staleness: rollback + generation fence
# ---------------------------------------------------------------------------

def test_rollback_pushes_nothing_stale():
    """A storage 2PC rollback between fan-outs: every header the
    subscriber ever receives must be a header of the REAL committed
    chain (the retry's block), never the rolled-back attempt's bytes."""
    node = _mk_node()
    try:
        sink = _Sink()
        node.subhub.subscribe("newBlockHeaders", sink, owner=object())
        kp = node.suite.generate_keypair(b"sub-rb")
        _register(node, kp, b"rb-a", 7, "rb-0")

        orig_commit = node.storage.commit
        state = {"tripped": False}

        def flaky(number):
            if not state["tripped"]:
                state["tripped"] = True
                raise RuntimeError("injected commit failure")
            return orig_commit(number)

        node.storage.commit = flaky
        _register(node, kp, b"rb-b", 9, "rb-1")  # survives the rollback
        node.storage.commit = orig_commit
        assert state["tripped"], "injection never fired"

        head = node.ledger.current_number()
        assert wait_until(lambda: any(
            r.get("number") == head for r in sink.results()))
        for r in sink.results():
            want = node.ledger.header_by_number(r["number"])
            assert want is not None, f"pushed header for unknown #{r}"
            assert r["hash"] == "0x" + want.hash(node.suite).hex(), (
                f"stale header pushed for block {r['number']}")
    finally:
        node.stop()


def test_fanout_generation_fence_gives_up_on_racing_invalidation():
    """White-box: when the cache generation keeps moving under the
    fan-out's fragment reads (an invalidation storm — rollback or
    snapshot install racing the worker), the batch is DROPPED after one
    retry rather than pushing bytes read across a wipe."""
    node = _mk_node()
    try:
        hub = node.subhub
        sink = _Sink()
        hub.subscribe("newBlockHeaders", sink, owner=object())
        kp = node.suite.generate_keypair(b"sub-fence")
        _register(node, kp, b"fence", 1, "fe-0")
        assert wait_until(lambda: len(sink.frames) >= 1)
        got = len(sink.frames)

        class EverMoving:
            """Delegates to the real cache but every generation() call
            observes a new generation — no read window can close."""

            def __init__(self, real):
                self._real = real
                self._g = itertools.count()

            def generation(self):
                return next(self._g)

            def __getattr__(self, name):
                return getattr(self._real, name)

        hub.cache = EverMoving(node.query_cache)
        hub.on_commit(node.ledger.current_number())
        time.sleep(0.5)  # worker runs, fence trips twice, batch dropped
        assert len(sink.frames) == got, \
            "fan-out pushed a batch whose reads raced an invalidation"
        hub.cache = node.query_cache  # heal: pushes resume
        _register(node, kp, b"fence2", 1, "fe-1")
        assert wait_until(lambda: len(sink.frames) > got)
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# delivery: slow subscribers shed without delaying anyone
# ---------------------------------------------------------------------------

def test_never_draining_subscriber_sheds_without_delaying_others():
    """One subscriber whose outbox never drains: droppable frames evict
    oldest-first (counted), the healthy subscriber keeps receiving every
    head promptly, and the fan-out worker never blocks on the stuck one
    (push() is enqueue-only)."""
    from fisco_bcos_tpu.rpc.ws_server import _Session
    from fisco_bcos_tpu.utils.metrics import REGISTRY

    class FakeSock:
        closed = False

        def close(self):
            self.closed = True

    class StuckConn:
        peer = "stuck"

        def __init__(self):
            self._gate = threading.Event()
            self.sock = FakeSock()

        def send_text(self, text):
            self._gate.wait(30)  # writer parks: outbox never drains

    node = _mk_node()
    try:
        stuck = _Session(StuckConn())
        stuck.MAX_OUTBOX = 4
        healthy = _Sink()
        hub = node.subhub
        hub.subscribe("newBlockHeaders", stuck.push, owner=stuck)
        hub.subscribe("newBlockHeaders", healthy, owner=object())
        before = REGISTRY.snapshot()["counters"].get(
            "bcos_ws_push_dropped_total", 0.0)
        kp = node.suite.generate_keypair(b"sub-stuck")
        for i in range(10):
            _register(node, kp, b"st%d" % i, 1, f"st-{i}")
        head = node.ledger.current_number()
        # the healthy subscriber saw the final head promptly...
        assert wait_until(lambda: any(
            r.get("number") == head for r in healthy.results()))
        # ...while the stuck one overflowed its bounded outbox
        assert wait_until(lambda: REGISTRY.snapshot()["counters"].get(
            "bcos_ws_push_dropped_total", 0.0) > before), \
            "stuck subscriber's overflow was never shed/counted"
        assert not stuck.conn.sock.closed  # droppable stream: shed, not
        stuck.close_push()  # killed
    finally:
        node.stop()


def test_dead_sink_is_evicted_from_the_hub():
    """A sink that reports death (session killed by lossless overflow,
    socket gone) is unsubscribed by the fan-out — no zombie streams."""
    node = _mk_node()
    try:
        hub = node.subhub
        sink = _Sink()
        hub.subscribe("newBlockHeaders", sink, owner=object())
        kp = node.suite.generate_keypair(b"sub-dead")
        _register(node, kp, b"dd", 1, "dd-0")
        assert wait_until(lambda: len(sink.frames) >= 1)
        sink.ok = False  # session died
        _register(node, kp, b"dd2", 1, "dd-1")
        assert wait_until(
            lambda: hub.stats()["byKind"]["newBlockHeaders"] == 0), \
            "dead sink never evicted"
    finally:
        node.stop()


def test_unsubscribe_races_commit_fanout_cleanly():
    """unsubscribe concurrent with a storm of fan-outs: no exception, the
    registry converges to empty, and the worker stays healthy (a fresh
    subscriber still receives pushes afterwards)."""
    node = _mk_node()
    try:
        hub = node.subhub
        kp = node.suite.generate_keypair(b"sub-race")
        _register(node, kp, b"race", 1, "ra-0")
        head = node.ledger.current_number()
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                hub.on_commit(head)

        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        try:
            for i in range(50):
                sid = hub.subscribe("newBlockHeaders", _Sink(),
                                    owner=object())
                hub.unsubscribe(sid)
        finally:
            stop.set()
            t.join(timeout=5)
        assert hub.stats()["byKind"]["newBlockHeaders"] == 0
        late = _Sink()
        hub.subscribe("newBlockHeaders", late, owner=object())
        _register(node, kp, b"race2", 1, "ra-1")
        assert wait_until(lambda: len(late.frames) >= 1), \
            "fan-out worker died during the unsubscribe race"
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# filtering + admission
# ---------------------------------------------------------------------------

def test_log_filters_match_topics_exactly():
    """logs streams filter per-position topic OR-sets exactly: the
    matching filter sees the transfer log, the non-matching one sees
    NOTHING (and an address mismatch also excludes)."""
    node = _mk_node()
    try:
        hub = node.subhub
        kp = node.suite.generate_keypair(b"sub-filter")
        _register(node, kp, b"fa", 100, "fl-0")
        _register(node, kp, b"fb", 0, "fl-1")

        match = _Sink()
        wrong_topic = _Sink()
        wrong_addr = _Sink()
        both = _Sink()  # no filter: sees everything
        hub.subscribe("logs", match, owner=object(),
                      flt=EventFilter(topics=[{b"transfer"}]))
        hub.subscribe("logs", wrong_topic, owner=object(),
                      flt=EventFilter(topics=[{b"not-a-topic"}]))
        hub.subscribe("logs", wrong_addr, owner=object(),
                      flt=EventFilter(addresses={b"\xde\xad" * 10},
                                      topics=[{b"transfer"}]))
        hub.subscribe("logs", both, owner=object())

        _transfer(node, kp, b"fa", b"fb", 7, "fl-2")
        assert wait_until(lambda: len(match.frames) >= 1), \
            "matching filter never saw the transfer log"
        row = match.results()[0]
        assert row["topics"][0] == "0x" + b"transfer".hex()
        assert row["address"] == "0x" + pc.BALANCE_ADDRESS.hex()
        assert wait_until(lambda: len(both.frames) >= 1)
        time.sleep(0.3)  # give any wrong push time to surface
        assert wrong_topic.frames == [], "topic filter leaked a log"
        assert wrong_addr.frames == [], "address filter leaked a log"
    finally:
        node.stop()


def test_subscription_storm_sheds_with_typed_error():
    """Beyond the caps the hub answers SubLimitError (wire: -32006) —
    a storm sheds with a TYPED reject, it does not grow unbounded."""
    node = _mk_node(sub_max_sessions=2)
    try:
        hub = node.subhub
        assert hub.max_sessions == 2
        hub.subscribe("newBlockHeaders", _Sink(), owner="s1")
        hub.subscribe("newBlockHeaders", _Sink(), owner="s2")
        with pytest.raises(SubLimitError):
            hub.subscribe("newBlockHeaders", _Sink(), owner="s3")
        # existing sessions may still add streams; new sessions may not
        hub.subscribe("logs", _Sink(), owner="s1")
        assert hub.stats()["rejects"] == 1
    finally:
        node.stop()


def test_receipt_subscription_is_lossless_one_shot():
    """A receipt stream for an ALREADY-committed hash completes at
    subscribe time (lossless), and the stream auto-closes after the
    single frame."""
    node = _mk_node()
    try:
        hub = node.subhub
        kp = node.suite.generate_keypair(b"sub-rc")
        _, h = _register(node, kp, b"rc1", 5, "rc-0")
        sink = _Sink()
        hub.subscribe("receipt", sink, owner=object(), tx_hash=h)
        assert wait_until(lambda: len(sink.frames) >= 1)
        assert sink.frames[0]["params"]["kind"] == "receipt"
        assert int(sink.results()[0]["status"]) == 0
        assert hub.stats()["byKind"]["receipt"] == 0  # one-shot closed
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# the zero-extra-render claim (acceptance criterion)
# ---------------------------------------------------------------------------

class _DumpsCounter:
    """Counts JSON encodings whose argument is a CONTAINER (fragment
    renders), whichever door they take: `json.dumps` and the fragments'
    own encoder (rpc/cache.encode_compact) both end in
    `JSONEncoder.encode`. Id-only dumps (ints/strings, the envelope
    splice) are free by design and not counted."""

    def __init__(self):
        self.container_calls = 0
        self._orig = json.JSONEncoder.encode

    def __enter__(self):
        orig = self._orig

        def counting(encoder, obj):
            if isinstance(obj, (dict, list, tuple)):
                self.container_calls += 1
            return orig(encoder, obj)

        json.JSONEncoder.encode = counting
        return self

    def __exit__(self, *exc):
        json.JSONEncoder.encode = self._orig


def test_notification_render_cost_is_independent_of_subscriber_count():
    """The acceptance instrument: a commit's dumps count with 8
    subscribers equals the count with 1 — every extra subscriber costs
    buffer joins only, zero extra fragment renders beyond the prime."""
    node = _mk_node()
    try:
        hub = node.subhub
        kp = node.suite.generate_keypair(b"sub-zero")
        _register(node, kp, b"z-warm", 1, "zw-0")  # warm the planes

        def measured_commit(n_subs: int, tag: str) -> int:
            sinks = [_Sink() for _ in range(n_subs)]
            sids = [hub.subscribe("newBlockHeaders", s, owner=object())
                    for s in sinks]
            time.sleep(0.2)  # quiesce prior prime/fan-out work
            with _DumpsCounter() as dc:
                _register(node, kp, b"z-" + tag.encode(), 1, f"z-{tag}")
                head = node.ledger.current_number()
                assert wait_until(lambda: all(
                    any(r.get("number") == head for r in s.results())
                    for s in sinks))
                # let the prime observer finish rendering this block
                assert wait_until(lambda: node.query_cache.get(
                    ("senders", head)) is not None)
                time.sleep(0.3)  # zk/proof prime tail settles
                count = dc.container_calls
            for sid in sids:
                hub.unsubscribe(sid)
            return count

        one = measured_commit(1, "a")
        eight = measured_commit(8, "b")
        assert one > 0  # the prime itself renders fragments
        assert eight <= one + 1, (
            f"{eight} container dumps with 8 subscribers vs {one} with 1 "
            "— notifications are paying per-subscriber renders")
    finally:
        node.stop()


def test_polled_hits_reuse_primed_fragment_bytes():
    """Satellite: N identical polled getBlockByNumber /
    getTransactionReceipt hits after one commit perform ZERO further
    fragment dumps — the envelope writer splices the bytes rendered
    once at prime time (the only dumps per hit is the response id)."""
    import http.client

    node = _mk_node()
    try:
        kp = node.suite.generate_keypair(b"sub-poll")
        rc, h = _register(node, kp, b"poll", 5, "po-0")
        n = rc.block_number
        tx_hash = "0x" + h.hex()
        assert wait_until(lambda: node.query_cache.get(
            ("senders", n)) is not None)  # prime settled

        # pre-serialize request bodies: the client must not dump either
        blk_body = json.dumps({"jsonrpc": "2.0", "id": 1,
                               "method": "getBlockByNumber",
                               "params": ["group0", "", n, False, False]
                               }).encode()
        rc_body = json.dumps({"jsonrpc": "2.0", "id": 2,
                              "method": "getTransactionReceipt",
                              "params": ["group0", "", tx_hash, False]
                              }).encode()

        def post(body: bytes) -> dict:
            conn = http.client.HTTPConnection(node.rpc.host, node.rpc.port,
                                              timeout=30)
            try:
                conn.request("POST", "/", body=body,
                             headers={"Content-Type": "application/json"})
                return json.loads(conn.getresponse().read())
            finally:
                conn.close()

        warm = post(blk_body)  # first touch may lazily render
        assert warm["result"]["number"] == n
        post(rc_body)
        with _DumpsCounter() as dc:
            for _ in range(6):
                blk = post(blk_body)
                assert blk["result"]["number"] == n
                rcj = post(rc_body)
                assert int(rcj["result"]["status"]) == 0
            assert dc.container_calls == 0, (
                f"{dc.container_calls} fragment dumps across 12 cached "
                "hits — the envelope splice path is not being used")
    finally:
        node.stop()
