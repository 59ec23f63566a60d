"""JSON-RPC 2.0 batch semantics + the event-loop edge's HTTP behaviors.

Covers the spec shapes (mixed valid/invalid entries with per-id error
objects, empty batch, parse error, notifications, order preservation)
over BOTH transports (HTTP and WS share JsonRpcImpl.handle_payload), and
the rpc/edge.py serving properties: keep-alive connection reuse and
request pipelining with in-order responses.
"""

import http.client
import json
import socket
import threading

import pytest

from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.net.websocket import ws_connect
from fisco_bcos_tpu.protocol import Receipt, Transaction
from fisco_bcos_tpu.rpc import server as rpc_server
from fisco_bcos_tpu.sdk.client import SdkClient

from test_eventsub import wait_until


@pytest.fixture(scope="module")
def batch_node():
    n = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                        rpc_port=0, ws_port=0))
    n.start()
    yield n
    n.stop()


def _post_raw(node, body: bytes, extra_headers: str = "") -> bytes:
    """One raw POST, returns the response body bytes."""
    conn = http.client.HTTPConnection(node.rpc.host, node.rpc.port,
                                      timeout=30)
    try:
        conn.request("POST", "/", body=body,
                     headers={"Content-Type": "application/json"})
        return conn.getresponse().read()
    finally:
        conn.close()


def test_batch_mixed_entries_per_id_errors(batch_node):
    """Valid + unknown-method + non-dict + notification + bad params in
    ONE batch: per-entry verdicts, response order matches request order,
    the notification is absent from the response."""
    payload = [
        {"jsonrpc": "2.0", "id": 1, "method": "getBlockNumber",
         "params": ["group0", ""]},
        {"jsonrpc": "2.0", "id": 2, "method": "noSuchMethod", "params": []},
        42,  # not a request object at all
        {"jsonrpc": "2.0", "method": "getBlockNumber",
         "params": ["group0", ""]},  # notification: no id -> no response
        {"jsonrpc": "2.0", "id": 3, "method": "getBlockNumber",
         "params": ["wrong-group", ""]},
    ]
    out = json.loads(_post_raw(batch_node, json.dumps(payload).encode()))
    assert isinstance(out, list) and len(out) == 4
    assert out[0]["id"] == 1 and out[0]["result"] >= 0
    assert out[1]["id"] == 2 and out[1]["error"]["code"] == -32601
    assert out[2]["id"] is None and out[2]["error"]["code"] == -32600
    assert out[3]["id"] == 3 and "error" in out[3]
    assert [r.get("id") for r in out] == [1, 2, None, 3]


def test_empty_batch_is_single_error(batch_node):
    out = json.loads(_post_raw(batch_node, b"[]"))
    assert isinstance(out, dict)
    assert out["error"]["code"] == -32600 and out["id"] is None


def test_oversized_batch_rejected(batch_node):
    cap = batch_node.config.rpc_max_batch
    payload = [{"jsonrpc": "2.0", "id": i, "method": "getBlockNumber",
                "params": ["group0", ""]} for i in range(cap + 1)]
    out = json.loads(_post_raw(batch_node, json.dumps(payload).encode()))
    assert isinstance(out, dict) and out["error"]["code"] == -32600


def test_parse_error(batch_node):
    out = json.loads(_post_raw(batch_node, b"{not json"))
    assert out["error"]["code"] == -32700 and out["id"] is None


def test_all_notifications_empty_body(batch_node):
    payload = [
        {"jsonrpc": "2.0", "method": "getBlockNumber",
         "params": ["group0", ""]},
        {"jsonrpc": "2.0", "method": "getPendingTxSize",
         "params": ["group0", ""]},
    ]
    assert _post_raw(batch_node, json.dumps(payload).encode()) == b""
    # single notification too
    assert _post_raw(batch_node, json.dumps(payload[0]).encode()) == b""


def test_sdk_request_batch_roundtrip(batch_node):
    sdk = SdkClient(f"http://{batch_node.rpc.host}:{batch_node.rpc.port}")
    resps = sdk.request_batch([
        ("getBlockNumber", ["group0", ""]),
        ("getGroupList", []),
        ("noSuchMethod", []),
    ])
    assert len(resps) == 3
    assert resps[0]["result"] >= 0
    assert resps[1]["result"]["groupList"] == ["group0"]
    assert resps[2]["error"]["code"] == -32601


def test_keepalive_connection_reuse(batch_node):
    """Many sequential requests on ONE persistent connection."""
    conn = http.client.HTTPConnection(batch_node.rpc.host,
                                      batch_node.rpc.port, timeout=30)
    try:
        for i in range(16):
            body = json.dumps({"jsonrpc": "2.0", "id": i,
                               "method": "getBlockNumber",
                               "params": ["group0", ""]}).encode()
            conn.request("POST", "/", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read())
            assert out["id"] == i and not resp.will_close
    finally:
        conn.close()


def test_pipelined_requests_answered_in_order(batch_node):
    """Two POSTs written back-to-back before reading either response:
    the edge must answer both, in request order, on one connection."""
    reqs = b""
    for i in (101, 102):
        body = json.dumps({"jsonrpc": "2.0", "id": i,
                           "method": "getBlockNumber",
                           "params": ["group0", ""]}).encode()
        reqs += (b"POST / HTTP/1.1\r\nHost: x\r\n"
                 b"Content-Type: application/json\r\n"
                 b"Content-Length: " + str(len(body)).encode() +
                 b"\r\n\r\n" + body)
    sock = socket.create_connection(
        (batch_node.rpc.host, batch_node.rpc.port), timeout=30)
    try:
        sock.sendall(reqs)
        buf = b""
        bodies = []
        while len(bodies) < 2:
            chunk = sock.recv(65536)
            assert chunk, "edge closed mid-pipeline"
            buf += chunk
            while b"\r\n\r\n" in buf:
                head, rest = buf.split(b"\r\n\r\n", 1)
                length = int([ln.split(b":")[1] for ln in head.split(b"\r\n")
                              if ln.lower().startswith(b"content-length")][0])
                if len(rest) < length:
                    break
                bodies.append(rest[:length])
                buf = rest[length:]
        assert [json.loads(b)["id"] for b in bodies] == [101, 102]
    finally:
        sock.close()


def test_connection_close_honored(batch_node):
    """Connection: close -> the edge answers, then closes the socket."""
    body = json.dumps({"jsonrpc": "2.0", "id": 7,
                       "method": "getBlockNumber",
                       "params": ["group0", ""]}).encode()
    sock = socket.create_connection(
        (batch_node.rpc.host, batch_node.rpc.port), timeout=30)
    try:
        sock.sendall(b"POST / HTTP/1.1\r\nHost: x\r\nConnection: close\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: " + str(len(body)).encode() +
                     b"\r\n\r\n" + body)
        data = b""
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            data += chunk
        head, payload = data.split(b"\r\n\r\n", 1)
        assert b"Connection: close" in head
        assert json.loads(payload)["id"] == 7
    finally:
        sock.close()


def test_batch_budget_bounds_worker_time(monkeypatch):
    """A batch whose entries block must stop executing once the payload
    budget is spent: remaining entries get per-id -32000 errors (order
    preserved, notifications silent) so the shared-pool worker returns."""
    import time as _time

    from fisco_bcos_tpu.rpc import server as srv

    monkeypatch.setattr(srv, "BATCH_BUDGET_SECONDS", 0.2)

    class SlowImpl:
        def handle(self, req):
            _time.sleep(0.15)
            return {"jsonrpc": "2.0", "id": req.get("id"), "result": "ok"}

    payload = [{"jsonrpc": "2.0", "id": i, "method": "m", "params": []}
               for i in range(5)]
    t0 = _time.monotonic()
    out = srv.handle_payload_with(SlowImpl(), payload)
    assert _time.monotonic() - t0 < 1.0  # nowhere near 5 * 0.15 + slack
    assert [r["id"] for r in out] == list(range(5))
    exhausted = [r for r in out if "error" in r]
    assert exhausted and all(
        r["error"]["message"] == "batch budget exhausted" for r in exhausted)
    assert any("result" in r for r in out)  # early entries did execute


def test_negative_content_length_rejected(batch_node):
    """A negative Content-Length must be answered 400 and the connection
    closed — not re-parsed forever (it would un-consume rbuf and spin the
    event loop)."""
    sock = socket.create_connection(
        (batch_node.rpc.host, batch_node.rpc.port), timeout=10)
    try:
        sock.sendall(b"POST / HTTP/1.1\r\nHost: x\r\n"
                     b"Content-Length: -999999\r\n\r\n")
        data = b""
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            data += chunk
        assert data.startswith(b"HTTP/1.1 400"), data[:80]
    finally:
        sock.close()
    # the edge survived: a normal request still works
    out = json.loads(_post_raw(batch_node, json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": "getBlockNumber",
         "params": ["group0", ""]}).encode()))
    assert out["result"] >= 0


def test_ws_request_without_method_gets_error(batch_node):
    """An id-carrying WS frame with no \"method\" is answered with a
    -32600 error (not silently dropped, which would hang the client)."""
    conn = ws_connect(batch_node.config.rpc_host, batch_node.ws.port)
    try:
        conn.send_text(json.dumps({"jsonrpc": "2.0", "id": 5,
                                   "params": []}))
        _op, data = conn.recv()
        out = json.loads(data)
        assert out["id"] == 5 and out["error"]["code"] == -32600
    finally:
        conn.close()


def test_nondraining_connection_reaped():
    """A peer that sends requests but never reads responses must be
    reaped after keepalive_s of zero write progress — not pin an fd and
    its outbuf forever."""
    import time as _time

    from fisco_bcos_tpu.rpc.edge import EventLoopHttpServer

    # responses far larger than the kernel socket buffer, so the server's
    # sends stall and outbuf stays nonempty (exercising the stalled-WRITE
    # reap, not the idle reap)
    srv = EventLoopHttpServer(lambda body: b'{"ok": 1}' * (256 * 1024),
                              keepalive_s=0.6)
    srv.start()
    try:
        sock = socket.create_connection((srv.host, srv.port), timeout=10)
        body = b'{"jsonrpc": "2.0", "id": 1}'
        for _ in range(4):
            sock.sendall(b"POST / HTTP/1.1\r\nHost: x\r\n"
                         b"Content-Length: " + str(len(body)).encode() +
                         b"\r\n\r\n" + body)
        # never recv(): responses pile in outbuf server-side (tiny socket
        # buffers aside, last_active stops advancing once sends stall)
        deadline = _time.monotonic() + 8
        while _time.monotonic() < deadline:
            if not srv._conns:
                break
            _time.sleep(0.1)
        assert not srv._conns, "non-draining connection never reaped"
        sock.close()
    finally:
        srv.stop()


def test_ws_batch_parity(batch_node):
    """The SAME batch semantics over the WS transport (one list frame in,
    one list frame out; notifications omitted)."""
    conn = ws_connect(batch_node.config.rpc_host, batch_node.ws.port)
    try:
        payload = [
            {"jsonrpc": "2.0", "id": "a", "method": "getBlockNumber",
             "params": ["group0", ""]},
            {"jsonrpc": "2.0", "id": "b", "method": "noSuchMethod",
             "params": []},
            {"jsonrpc": "2.0", "method": "getBlockNumber",
             "params": ["group0", ""]},  # notification
        ]
        conn.send_text(json.dumps(payload))
        _op, data = conn.recv()
        out = json.loads(data)
        assert isinstance(out, list) and len(out) == 2
        assert out[0]["id"] == "a" and out[0]["result"] >= 0
        assert out[1]["id"] == "b" and out[1]["error"]["code"] == -32601
    finally:
        conn.close()


def test_ws_single_notification_no_response(batch_node):
    """A lone notification over WS gets no reply; a follow-up request on
    the same session is answered normally (the session survives)."""
    conn = ws_connect(batch_node.config.rpc_host, batch_node.ws.port)
    try:
        conn.send_text(json.dumps(
            {"jsonrpc": "2.0", "method": "getBlockNumber",
             "params": ["group0", ""]}))
        conn.send_text(json.dumps(
            {"jsonrpc": "2.0", "id": 9, "method": "getBlockNumber",
             "params": ["group0", ""]}))
        _op, data = conn.recv()
        out = json.loads(data)
        assert out["id"] == 9 and out["result"] >= 0
    finally:
        conn.close()


def test_parse_burst_respects_pipeline_cap(monkeypatch):
    """One recv burst of tiny pipelined requests must not dispatch past
    MAX_PIPELINE: the cap gates the PARSE loop (excess stays in rbuf),
    and parsing resumes as completions free slots — every request is
    still answered, in order."""
    import threading as _threading
    import time as _time

    from fisco_bcos_tpu.rpc import edge as edge_mod
    from fisco_bcos_tpu.rpc.edge import EventLoopHttpServer, WorkerPool

    monkeypatch.setattr(edge_mod, "MAX_PIPELINE", 4)
    gate = _threading.Event()

    class CountingPool(WorkerPool):
        def __init__(self):
            super().__init__(workers=2)
            self.submitted = 0

        def try_submit(self, fn):
            ok = super().try_submit(fn)
            if ok:
                self.submitted += 1
            return ok

    pool = CountingPool()
    pool.start()

    def handler(body: bytes) -> bytes:
        gate.wait(10)
        return body  # echo: response carries the request id

    srv = EventLoopHttpServer(handler, pool=pool)
    srv.start()
    try:
        n = 50
        burst = b"".join(
            b"POST / HTTP/1.1\r\nHost: x\r\nContent-Length: " +
            str(len(b)).encode() + b"\r\n\r\n" + b
            for b in (json.dumps({"id": i}).encode() for i in range(n)))
        sock = socket.create_connection((srv.host, srv.port), timeout=10)
        sock.sendall(burst)  # one buffer: arrives in very few recvs
        _time.sleep(0.5)
        # with the gate held nothing completes, so dispatch depth IS the
        # number of pool submissions — must be capped, not ~n
        assert pool.submitted <= 4, pool.submitted
        gate.set()
        sock.settimeout(15)
        buf = b""
        ids = []
        while len(ids) < n:
            chunk = sock.recv(65536)
            if not chunk:
                break
            buf += chunk
            while True:
                head, sep, rest = buf.partition(b"\r\n\r\n")
                if not sep:
                    break
                clen = int([ln for ln in head.split(b"\r\n")
                            if ln.lower().startswith(b"content-length")
                            ][0].split(b":")[1])
                if len(rest) < clen:
                    break
                ids.append(json.loads(rest[:clen])["id"])
                buf = rest[clen:]
        assert ids == list(range(n))  # all answered, request order
        sock.close()
    finally:
        srv.stop()
        pool.stop()


def test_stop_without_start_releases_listener():
    """stop() on a never-started edge must close the bound listener and
    the selector/wake fds (Node binds the port in __init__; Node.start()
    can raise before rpc.start() — cleanup used to rely on the loop
    thread's exit path). Double-stop stays idempotent."""
    from fisco_bcos_tpu.rpc.edge import EventLoopHttpServer

    srv = EventLoopHttpServer(lambda body: b"{}")
    port = srv.port
    srv.stop()
    assert srv._listener.fileno() == -1
    assert srv._wake_r.fileno() == -1 and srv._wake_w.fileno() == -1
    srv.stop()  # second stop: no-op, no raise
    # the port is actually free again
    relisten = socket.create_server(("127.0.0.1", port))
    relisten.close()


def test_ws_fallback_threads_bounded(batch_node, monkeypatch):
    """When the shared pool can't take a WS dispatch, the one-off-thread
    fallback is BOUNDED: past the cap the frame is shed with the same
    -32000 busy error HTTP answers, not given yet another OS thread."""
    import threading as _threading

    ws = batch_node.ws
    monkeypatch.setattr(ws, "pool", None)  # every _offload hits fallback
    taken = 0
    while ws._fallback.acquire(blocking=False):
        taken += 1
    replies = []

    class FakeSess:
        def push(self, obj):
            replies.append(obj)
            return True

        send_now = push  # shed errors are lossless sends (same capture)

    try:
        ws._offload(lambda s, m: None, FakeSess(),
                    {"id": 7, "method": "x"})
        assert replies and replies[0]["id"] == 7
        assert replies[0]["error"]["code"] == -32000
    finally:
        for _ in range(taken):
            ws._fallback.release()
    # with permits back, the fallback dispatches (and returns its permit)
    ran = _threading.Event()
    ws._offload(lambda s, m: ran.set(), FakeSess(), {"id": 8})
    assert ran.wait(5)


def test_chunked_transfer_encoding_rejected(batch_node):
    """A Transfer-Encoding: chunked POST is answered 411 and the
    connection closed — not treated as a zero-length body with the chunk
    framing misparsed as a pipelined request."""
    sock = socket.create_connection(
        (batch_node.rpc.host, batch_node.rpc.port), timeout=10)
    try:
        sock.sendall(b"POST / HTTP/1.1\r\nHost: x\r\n"
                     b"Transfer-Encoding: chunked\r\n\r\n"
                     b"24\r\n" + b"x" * 0x24 + b"\r\n0\r\n\r\n")
        data = b""
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            data += chunk
        assert data.startswith(b"HTTP/1.1 411"), data[:80]
        assert data.count(b"HTTP/1.1") == 1  # chunk framing NOT re-parsed
    finally:
        sock.close()
    # the edge survived
    out = json.loads(_post_raw(batch_node, json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": "getBlockNumber",
         "params": ["group0", ""]}).encode()))
    assert out["result"] >= 0


def test_ws_shed_keeps_notifications_silent(batch_node, monkeypatch):
    """A notification frame shed at full fallback capacity gets NO reply
    (the id:null busy error would be uncorrelatable to an SDK); an
    id-carrying frame shed in the same state still gets its error."""
    ws = batch_node.ws
    monkeypatch.setattr(ws, "pool", None)
    taken = 0
    while ws._fallback.acquire(blocking=False):
        taken += 1
    replies = []

    class FakeSess:
        def push(self, obj):
            replies.append(obj)
            return True

        send_now = push  # shed errors are lossless sends (same capture)

    try:
        ws._offload(lambda s, m: None, FakeSess(),
                    {"jsonrpc": "2.0", "method": "getBlockNumber",
                     "params": ["group0", ""]})  # notification: no id
        assert replies == []
        ws._offload(lambda s, m: None, FakeSess(),
                    {"jsonrpc": "2.0", "id": 4, "method": "x"})
        assert len(replies) == 1 and replies[0]["id"] == 4
    finally:
        for _ in range(taken):
            ws._fallback.release()


def test_ws_shed_batch_gets_per_id_errors(batch_node, monkeypatch):
    """A batch frame shed at full fallback capacity is answered with
    PER-ID busy errors (notifications and non-dict entries silent) — a
    single id:null error would strand every per-id response waiter."""
    ws = batch_node.ws
    monkeypatch.setattr(ws, "pool", None)
    taken = 0
    while ws._fallback.acquire(blocking=False):
        taken += 1
    replies = []

    class FakeSess:
        def push(self, obj):
            replies.append(obj)
            return True

        send_now = push  # shed errors are lossless sends (same capture)

    try:
        ws._offload(lambda s, m: None, FakeSess(), [
            {"jsonrpc": "2.0", "id": 1, "method": "getBlockNumber",
             "params": ["group0", ""]},
            {"jsonrpc": "2.0", "method": "getBlockNumber",
             "params": ["group0", ""]},  # notification
            "garbage",
            {"jsonrpc": "2.0", "id": 2, "method": "getBlockNumber",
             "params": ["group0", ""]},
        ])
        assert len(replies) == 1 and isinstance(replies[0], list)
        assert [e["id"] for e in replies[0]] == [1, 2]
        assert all(e["error"]["code"] == -32000 for e in replies[0])
    finally:
        for _ in range(taken):
            ws._fallback.release()


# ---------------------------------------------------------------------------
# a cohort is answered from its committed block's shared fragments
# ---------------------------------------------------------------------------

NOTIFIER = "sched-notify"
COHORT = 64


def cohort_node(sm: bool, **cfg):
    """A started solo host-crypto node with `rich` funded, its key pair
    and the impl whose prime_block rides the commit."""
    node = Node(NodeConfig(crypto_backend="host", sm_crypto=sm,
                           min_seal_time=0.0, rpc_port=0, ws_port=0, **cfg))
    node.start()
    kp = node.suite.generate_keypair(b"cohort-client")
    fund = Transaction(to=pc.BALANCE_ADDRESS, nonce="fund", block_limit=400,
                       input=pc.encode_call("register", lambda w: w.blob(
                           b"rich").u64(10 ** 6))).sign(node.suite, kp)
    rc = node.txpool.wait_for_receipt(node.send_transaction(fund).tx_hash, 30)
    assert rc is not None and rc.status == 0
    return node, kp, node.rpc.impl


def cohort_txs(node, kp, tag: str, n: int = COHORT) -> list:
    """n signed txs for one cohort: transfers out of `rich` (a log each),
    one register (no log) and one transfer out of nowhere (it fails: a
    status, and a message the stored receipt does not keep)."""
    def call(i):
        if i == 1:
            return pc.encode_call("register", lambda w: w.blob(
                f"{tag}-new".encode()).u64(5))
        src = b"nobody" if i == 2 else b"rich"
        return pc.encode_call("transfer", lambda w: w.blob(src).blob(
            f"{tag}-{i}".encode()).u64(1 + i % 7))
    return [Transaction(to=pc.BALANCE_ADDRESS, nonce=f"{tag}-{i}",
                        block_limit=400, input=call(i)).sign(node.suite, kp)
            for i in range(n)]


def send_cohort(node, txs, require_proof=False, ws=False) -> list:
    """One JSON-RPC batch of sendTransaction (wait=true) -> the parsed
    results in request order."""
    payload = [{"jsonrpc": "2.0", "id": i, "method": "sendTransaction",
                "params": ["group0", "", "0x" + tx.encode().hex(),
                           require_proof, True]}
               for i, tx in enumerate(txs)]
    if ws:
        conn = ws_connect(node.config.rpc_host, node.ws.port)
        try:
            conn.send_text(json.dumps(payload))
            out = json.loads(conn.recv()[1])
        finally:
            conn.close()
    else:
        out = json.loads(_post_raw(node, json.dumps(payload).encode()))
    assert [r["id"] for r in out] == list(range(len(txs))), out[:2]
    assert all("result" in r for r in out), [r for r in out
                                             if "result" not in r][:2]
    return [r["result"] for r in out]


class Tally:
    """Who rendered, decoded and encoded what, by thread name: the three
    things a cohort's response used to do per entry."""

    def __init__(self, monkeypatch):
        self.render, self.decode, self.encode = [], [], []
        render, decode = rpc_server._receipt_json, Receipt.decode.__func__
        encode = json.JSONEncoder.encode

        def me():
            return threading.current_thread().name

        def counted_render(rc, h):
            self.render.append(me())
            return render(rc, h)

        def counted_decode(cls, data):
            self.decode.append(me())
            return decode(cls, data)

        def counted_encode(encoder, obj):
            if isinstance(obj, (dict, list, tuple)):
                self.encode.append(me())
            return encode(encoder, obj)

        monkeypatch.setattr(rpc_server, "_receipt_json", counted_render)
        monkeypatch.setattr(Receipt, "decode", classmethod(counted_decode))
        monkeypatch.setattr(json.JSONEncoder, "encode", counted_encode)

    def by_workers(self, calls: list) -> int:
        """Calls made by neither the notifier nor the test's own thread:
        the RPC edge's."""
        return sum(1 for name in calls
                   if name not in (NOTIFIER, "MainThread"))


def hold_prime(node, impl):
    """Put a gate before the commit observer's prime_block and a flag
    after it -> (gate, primed numbers)."""
    gate, primed = threading.Event(), set()
    gate.set()
    at = node.scheduler.on_commit.index(impl.prime_block)

    def held(number):
        gate.wait(20)
        impl.prime_block(number)
        primed.add(number)

    node.scheduler.on_commit[at] = held
    return gate, primed


@pytest.mark.parametrize("sm", [False, True], ids=["secp", "sm"])
@pytest.mark.parametrize("how", ["http", "ws", "require_proof", "cache8",
                                 "worker_first", "notifier_first"])
def test_cohort_answers_equal_the_ledgers_render(sm, how, monkeypatch):
    """A 64-tx cohort's sendTransaction results equal `_receipt_json` of
    the ledger's receipts and `getTransactionReceipt` of the same hashes —
    over both transports, with proofs, with every fragment evicted before
    the response, and whichever of the cohort's worker and the notifier
    reaches the committed block first (the once-guard, both orders). Each
    receipt is rendered once, whoever needs it; the response decodes one
    receipt a block and, once the block's fragments are there, encodes
    nothing."""
    proof = how == "require_proof"
    node, kp, impl = cohort_node(
        sm, **({"rpc_cache_entries": 8} if how == "cache8" else {}))
    try:
        gate, primed = hold_prime(node, impl)
        if how == "notifier_first":
            waited = node.txpool.wait_for_receipt

            def late(h, timeout=30.0):
                rc = waited(h, timeout)
                if rc is not None:  # the worker arrives after the prime
                    wait_until(lambda: rc.block_number in primed)
                return rc
            monkeypatch.setattr(node.txpool, "wait_for_receipt", late)
        txs = cohort_txs(node, kp, how)
        hashes = [tx.hash(node.suite) for tx in txs]
        before = node.system_status()["trace"]["counters"]
        tally = Tally(monkeypatch)
        if how == "worker_first":
            gate.clear()  # the notifier waits until the response is out
        got = send_cohort(node, txs, require_proof=proof, ws=how == "ws")
        in_response = (tally.by_workers(tally.decode),
                       tally.by_workers(tally.encode), len(tally.render))
        if how == "worker_first":
            assert not primed - {1} and in_response[2] == COHORT
        gate.set()
        head = node.ledger.current_number()
        assert wait_until(lambda: head in primed)
        rendered = len(tally.render)
        monkeypatch.undo()

        # the answers: what the ledger holds, rendered the plain way
        ledger = node.ledger
        receipts = [ledger.receipt(h) for h in hashes]
        blocks = {rc.block_number for rc in receipts}
        want = [rpc_server._receipt_json(rc, h)
                for rc, h in zip(receipts, hashes)]
        assert [rc.status == 0 for rc in receipts].count(False) == 1
        assert sum(len(rc.logs) for rc in receipts) == COHORT - 2
        if proof:
            assert all(g.pop("receiptsRoot") and g.pop("receiptProof")
                       for g in got)
        assert got == want
        cli = SdkClient(f"http://{node.rpc.host}:{node.rpc.port}")
        polled = cli.request_batch([
            ("getTransactionReceipt", ["group0", "", "0x" + h.hex(), proof])
            for h in hashes])
        polled = [r["result"] for r in polled]
        if proof:
            assert all(p.pop("receiptsRoot") and p.pop("receiptProof")
                       for p in polled)
        assert polled == want

        # the work: one render a receipt, shared; the edge counted it
        assert rendered == COHORT, tally.render
        after = node.system_status()["trace"]["counters"]
        assert after["cohort_receipts"] - before["cohort_receipts"] == COHORT
        assert after["cohort_receipts_shared"] \
            - before["cohort_receipts_shared"] == COHORT
        if not proof:  # proofs read the block back per entry, as ever
            assert in_response[0] <= len(blocks), tally.decode
        if how == "notifier_first":
            assert in_response[1] == 0, tally.encode
        elif not proof:
            assert in_response[1] <= COHORT, tally.encode
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# a committed cohort is answered in one pass, in the per-entry path's bytes
# ---------------------------------------------------------------------------

def one_pass_payload(node, kp) -> list:
    """A batch around a 64-tx cohort: a query first, a notification, a
    malformed hex, another group's entry, a second copy of a cohort frame,
    a require_proof entry and a transaction admission refuses."""
    txs = cohort_txs(node, kp, "onepass")

    def send(i, tx, group="group0", proof=False):
        wire = "0x" + tx.encode().hex() if isinstance(tx, Transaction) \
            else tx
        entry = {"jsonrpc": "2.0", "method": "sendTransaction",
                 "params": [group, "", wire, proof, True]}
        if i is not None:
            entry["id"] = i
        return entry

    def extra(nonce, **kw):
        return Transaction(to=pc.BALANCE_ADDRESS, nonce=nonce,
                           block_limit=400, input=pc.encode_call(
                               "register", lambda w: w.blob(
                                   nonce.encode()).u64(1)),
                           **kw).sign(node.suite, kp)

    payload = [{"jsonrpc": "2.0", "id": "head", "method": "getBlockNumber",
                "params": ["group0", ""]},
               send(None, extra("onepass-notify"))]
    for i, tx in enumerate(txs):
        payload.append(send(i, tx))
        if i == 5:
            payload.append(send(1005, tx))  # the second copy
        elif i == 10:
            payload.append(send(1010, "0xzz"))
        elif i == 20:
            payload.append(send(1020, extra("onepass-other"), "group9"))
        elif i == 30:
            payload.append(send(1030, extra("onepass-proof"), proof=True))
        elif i == 40:
            payload.append(send(1040, extra("onepass-refused",
                                            chain_id="other-chain")))
    return payload


def post_body(node, payload, ws: bool) -> bytes:
    if not ws:
        return _post_raw(node, json.dumps(payload).encode())
    conn = ws_connect(node.config.rpc_host, node.ws.port)
    try:
        conn.send_text(json.dumps(payload))
        return conn.recv()[1]
    finally:
        conn.close()


def edge_counts(node) -> dict:
    counters = node.system_status()["trace"]["counters"]
    return {k: counters[k] for k in ("cohort_receipts",
                                     "cohort_receipts_shared",
                                     "cohort_receipts_onepass")}


@pytest.mark.parametrize("sm", [False, True], ids=["secp", "sm"])
@pytest.mark.parametrize("ws", [False, True], ids=["http", "ws"])
def test_cohort_one_pass_answers_the_per_entry_bytes(sm, ws, monkeypatch):
    """Two chains alike take the same mixed batch, whose cohort seals
    over two blocks (40 transactions a block): one answers it in one
    pass, the other with the pass made to answer nothing, so every entry
    goes through `handle`. The bodies are the same bytes; the pass
    answered the 64 plain members and left the notification's and the
    proof's member to their entries, which count as before."""
    nodes = [cohort_node(sm, tx_count_limit=40) for _ in range(2)]
    try:
        payload = one_pass_payload(nodes[0][0], nodes[0][1])
        bodies, counts = [], []
        for k, (node, _kp, _impl) in enumerate(nodes):
            if k == 1:
                monkeypatch.setattr(rpc_server.JsonRpcImpl,
                                    "_answer_cohort",
                                    lambda self, payload, plan: {})
            before = edge_counts(node)
            bodies.append(post_body(node, payload, ws))
            after = edge_counts(node)
            counts.append({k: after[k] - before[k] for k in after})
        out = json.loads(bodies[0])
        assert [r["id"] for r in out] == [e["id"] for e in payload
                                          if "id" in e]
        assert out[0]["result"] == 1
        errors = {r["id"]: r["error"]["code"] for r in out if "error" in r}
        assert set(errors) == {1010, 1020, 1040}, errors
        receipts = [r["result"] for r in out
                    if isinstance(r["id"], int) and r["id"] < COHORT]
        assert {rc["blockNumber"] for rc in receipts} == {2, 3}
        assert bodies[0] == bodies[1]
        assert counts == [
            {"cohort_receipts": COHORT + 2,
             "cohort_receipts_shared": COHORT + 2,
             "cohort_receipts_onepass": COHORT},
            {"cohort_receipts": COHORT + 2,
             "cohort_receipts_shared": COHORT + 2,
             "cohort_receipts_onepass": 0}]
    finally:
        for node, _kp, _impl in nodes:
            node.stop()


@pytest.mark.parametrize("sm", [False, True], ids=["secp", "sm"])
def test_receipt_template_bytes_equal_the_encoders(sm):
    """The shared table's receipt bytes come from a template: for receipts
    with and without logs, a non-zero status, a contract address and an
    empty output they are `encode_compact` of `_receipt_json` with the
    message the stored receipt does not keep."""
    from fisco_bcos_tpu.crypto.suite import CryptoSuite
    from fisco_bcos_tpu.protocol import Block, LogEntry, TransactionStatus
    from fisco_bcos_tpu.rpc.cache import encode_compact

    suite = CryptoSuite("sm" if sm else "ecdsa", backend="host")
    digest = lambda tag: suite.hash(tag.encode())  # noqa: E731
    receipts = [
        Receipt(block_number=7, gas_used=21000),
        Receipt(block_number=7, gas_used=123456789, output=b"\x00\x01",
                logs=[LogEntry(address=digest("a")[:20],
                               topics=[digest("t1"), digest("t2")],
                               data=b"payload"),
                      LogEntry(address=digest("b")[:20])]),
        Receipt(block_number=7, status=int(TransactionStatus.REVERT),
                message="insufficient balance", output=b"\x08\xc3\x79"),
        Receipt(version=1, block_number=7, gas_used=0,
                contract_address=digest("created")[:20],
                logs=[LogEntry(address=digest("c")[:20],
                               topics=[digest("t3")], data=b"")]),
    ]
    hashes = [digest(f"tx-{i}") for i in range(len(receipts))]
    frags = rpc_server._receipt_fragments(
        Block(receipts=receipts, tx_hashes=hashes))
    assert list(frags) == hashes
    for rc, h in zip(receipts, hashes):
        want = dict(rpc_server._receipt_json(rc, h), message="")
        assert frags[h] == want
        assert frags[h].raw == encode_compact(want).encode()
    assert rpc_server._receipt_fragments(None) == {}
