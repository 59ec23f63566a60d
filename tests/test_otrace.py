"""Tracing plane tests: otrace core, W3C context propagation across a
4-node chain, slow-span capture at sample_rate=0, the /metrics //trace
//status ops routes on the event-loop edge, and getTrace/getSystemStatus
RPC (HTTP + WS parity)."""

import http.client
import json
import time

import pytest

from fisco_bcos_tpu.utils import otrace
from fisco_bcos_tpu.utils.otrace import (SpanContext, Tracer,
                                         parse_traceparent, unpack_ctx)


# -- core ------------------------------------------------------------------
def test_traceparent_roundtrip():
    ctx = SpanContext(bytes(range(16)), bytes(range(8)), True)
    tp = ctx.traceparent()
    assert tp == ("00-000102030405060708090a0b0c0d0e0f-"
                  "0001020304050607-01")
    back = parse_traceparent(tp)
    assert back is not None
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.sampled is True
    # unsampled flag honored
    assert parse_traceparent(tp[:-2] + "00").sampled is False
    # malformed inputs -> None, never an exception
    for bad in (None, "", "garbage", "00-zz-xx-01", "00-" + "0" * 32 +
                "-" + "0" * 16 + "-01", 42, "00-abc-def-01"):
        assert parse_traceparent(bad) is None


def test_wire_context_roundtrip():
    ctx = SpanContext(b"\x11" * 16, b"\x22" * 8, True)
    back = unpack_ctx(ctx.pack())
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id and back.sampled
    assert unpack_ctx(b"short") is None
    assert unpack_ctx(bytes(25)) is None  # all-zero ids invalid


def test_ctx_scope_stack():
    assert otrace.current() is None
    a = SpanContext(b"\xaa" * 16, b"\x01" * 8, True)
    b = SpanContext(b"\xbb" * 16, b"\x02" * 8, True)
    with otrace.ctx_scope(a):
        assert otrace.current() is a
        with otrace.ctx_scope(None):  # no-op scope
            assert otrace.current() is a
        with otrace.ctx_scope(b):
            assert otrace.current() is b
        assert otrace.current() is a
    assert otrace.current() is None


def test_sampling_ring_and_queries():
    tr = Tracer(sample_rate=1.0, ring_size=64, slow_ms=0.0)
    roots = []
    for i in range(3):
        root = tr.new_root()
        assert root.sampled
        roots.append(root)
        with tr.span("outer", parent=root, attrs={"i": i}) as sp:
            # the span scopes its context: children nest automatically
            with tr.span("inner"):
                pass
            sp.set_attr("extra", True)
    spans = tr.get_trace(roots[0].trace_id.hex())
    assert {s["name"] for s in spans} == {"outer", "inner"}
    outer = next(s for s in spans if s["name"] == "outer")
    inner = next(s for s in spans if s["name"] == "inner")
    assert inner["parentSpanId"] == outer["spanId"]
    assert outer["attrs"] == {"i": 0, "extra": True}
    summaries = tr.list_traces()
    assert len(summaries) == 3
    assert all(t["spans"] == 2 for t in summaries)
    # ring stays bounded
    for _ in range(200):
        tr.record("x", tr.new_root(), time.monotonic())
    assert tr.stats()["ring_spans"] == 64
    assert tr.stats()["dropped_total"] > 0


def test_sample_rate_zero_is_empty_but_slow_capture_fires():
    tr = Tracer(sample_rate=0.0, ring_size=64, slow_ms=5.0)
    root = tr.new_root()
    assert not root.sampled
    with tr.span("fast", parent=root):
        pass
    with tr.span("slow-one", parent=root):
        time.sleep(0.02)
    st = tr.stats()
    assert st["ring_spans"] == 0  # nothing sampled into the main ring
    assert st["slow_spans"] == 1  # the slow span was retained anyway
    spans = tr.get_trace(root.trace_id.hex())
    assert [s["name"] for s in spans] == ["slow-one"]
    assert spans[0]["slow"] is True
    # observe_slow (the no-context seam) also lands in the slow ring only
    tr.observe_slow("stage.commit", 0.5, attrs={"number": 9})
    assert tr.stats()["slow_spans"] == 2
    assert tr.stats()["ring_spans"] == 0
    # fully idle tracer short-circuits to the null span
    idle = Tracer(sample_rate=0.0, ring_size=64, slow_ms=0.0)
    assert idle.idle()
    assert idle.span("anything") is otrace._NULL_SPAN


# -- ops server (satellite: /metrics off the event-loop edge) --------------
def test_ops_server_routes():
    from fisco_bcos_tpu.utils.metrics import MetricsRegistry, MetricsServer

    reg = MetricsRegistry()
    reg.inc("up")
    tr = Tracer(sample_rate=1.0, ring_size=64, slow_ms=0.0)
    root = tr.new_root()
    tr.record("hello", root, time.monotonic() - 0.01)
    srv = MetricsServer(reg, port=0, tracer=tr,
                        status_fn=lambda: {"blockNumber": 7})
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        assert r.status == 200
        assert "version=0.0.4" in r.getheader("Content-Type")
        assert "up 1.0" in r.read().decode()
        # keep-alive: same connection serves every route
        conn.request("GET", "/status")
        st = json.loads(conn.getresponse().read())
        assert st["blockNumber"] == 7
        conn.request("GET", f"/trace?id={root.trace_id.hex()}")
        doc = json.loads(conn.getresponse().read())
        assert [s["name"] for s in doc["spans"]] == ["hello"]
        conn.request("GET", "/traces?limit=10")
        lst = json.loads(conn.getresponse().read())
        assert lst["traces"][0]["traceId"] == root.trace_id.hex()
        conn.request("GET", "/nope")
        r = conn.getresponse()
        assert r.status == 404
        r.read()
        # POST on an ops-only server is refused, session survives
        conn.request("POST", "/metrics", body=b"{}")
        r = conn.getresponse()
        assert r.status == 405
        r.read()
        conn.close()
    finally:
        srv.stop()


# -- label escaping (satellite: Prometheus exposition validity) ------------
def _parse_exposition(text: str) -> dict:
    """Minimal Prometheus text-format parser: {(name, (label kv...)):
    value}. Raises on any malformed line — the round-trip assertion."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        if "{" in line:
            name, rest = line.split("{", 1)
            labelpart, value = rest.rsplit("} ", 1)
            labels = []
            i = 0
            while i < len(labelpart):
                eq = labelpart.index('="', i)
                key = labelpart[i:eq]
                j = eq + 2
                val = []
                while labelpart[j] != '"':
                    if labelpart[j] == "\\":
                        nxt = labelpart[j + 1]
                        val.append({"\\": "\\", '"': '"',
                                    "n": "\n"}[nxt])
                        j += 2
                    else:
                        val.append(labelpart[j])
                        j += 1
                labels.append((key, "".join(val)))
                i = j + 2 if j + 1 < len(labelpart) and \
                    labelpart[j + 1] == "," else j + 1
        else:
            name, value = line.rsplit(" ", 1)
            labels = []
        out[(name, tuple(labels))] = float(value)
    return out


def test_label_value_escaping_round_trips():
    from fisco_bcos_tpu.utils.metrics import MetricsRegistry

    reg = MetricsRegistry()
    nasty = 'gr"oup\\one\nline'
    reg.inc("bcos_evil_total", labels={"group": nasty})
    reg.set_gauge("bcos_plain", 1.0, labels={"group": "g0"})
    reg.observe("bcos_evil_seconds", 0.25, labels={"group": nasty})
    text = reg.prometheus_text()
    assert "\n\n" not in text.strip()  # raw newline would split a line
    parsed = _parse_exposition(text)
    assert parsed[("bcos_evil_total", (("group", nasty),))] == 1.0
    assert parsed[("bcos_plain", (("group", "g0"),))] == 1.0
    # histogram series carry the escaped label too
    assert any(n == "bcos_evil_seconds_count" and dict(ls)["group"] == nasty
               for n, ls in parsed)


# -- chain fixtures --------------------------------------------------------
def _chain(sample_rate: float, slow_ms: float = 0.0, n: int = 4,
           rpc_on_first: bool = False, ws_on_first: bool = False):
    from fisco_bcos_tpu.crypto.suite import make_suite
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.ledger.ledger import ConsensusNode
    from fisco_bcos_tpu.net.gateway import FakeGateway

    suite = make_suite(False, backend="host")
    kps = [suite.generate_keypair(bytes([i + 1]) * 16) for i in range(n)]
    gw = FakeGateway()
    sealers = [ConsensusNode(kp.pub_bytes) for kp in kps]
    nodes = []
    for i, kp in enumerate(kps):
        node = Node(NodeConfig(
            consensus="pbft", crypto_backend="host", min_seal_time=0.0,
            view_timeout=30.0, trace_sample_rate=sample_rate,
            trace_slow_ms=slow_ms,
            rpc_port=0 if rpc_on_first and i == 0 else None,
            ws_port=0 if ws_on_first and i == 0 else None),
            keypair=kp, gateway=gw)
        node.build_genesis(sealers)
        nodes.append(node)
    otrace.TRACER.reset()
    for node in nodes:
        node.start()
    return nodes, gw


def _stop(nodes, gw):
    for node in nodes:
        node.stop()
    gw.stop()


def _signed_tx(suite, i: int):
    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.protocol import Transaction

    kp = suite.generate_keypair(b"otrace-client")
    return Transaction(
        to=pc.BALANCE_ADDRESS,
        input=pc.encode_call("register",
                             lambda w: w.blob(b"ot%d" % i).u64(10 + i)),
        nonce=f"ot{i}", block_limit=400).sign(suite, kp)


# -- end-to-end propagation (satellite: 4-node trace coverage) -------------
def test_chain_trace_propagation_4node():
    """One submitted tx yields ONE trace whose spans cover admission ->
    receipt, with PBFT spans from follower nodes carrying the leader's
    trace context via the p2p envelope."""
    nodes, gw = _chain(sample_rate=1.0)
    try:
        tx = _signed_tx(nodes[0].suite, 0)
        root = otrace.TRACER.new_root()
        assert root.sampled
        tx._otrace = root
        res = nodes[0].send_transaction(tx)
        rc = nodes[0].txpool.wait_for_receipt(res.tx_hash, 30)
        assert rc is not None and rc.status == 0
        deadline = time.monotonic() + 5
        names: set = set()
        while time.monotonic() < deadline:
            spans = otrace.TRACER.get_trace(root.trace_id.hex())
            names = {s["name"] for s in spans}
            if {"pbft.consensus", "stage.notify"} <= names and len(
                    [s for s in spans
                     if s["name"] == "pbft.consensus"]) >= 3:
                break
            time.sleep(0.05)
        # ONE trace id covering admission -> seal -> consensus ->
        # execute -> commit -> receipt notify
        assert len({s["traceId"] for s in spans}) == 1
        for expected in ("ingest.admit", "txpool.admit", "stage.seal_wait",
                         "pbft.consensus", "stage.execute", "stage.commit",
                         "stage.notify"):
            assert expected in names, (expected, sorted(names))
        # consensus spans from >= 2 DISTINCT nodes, stitched by the p2p
        # envelope (followers adopted the leader's context)
        pbft_nodes = {s["attrs"]["node_idx"] for s in spans
                      if s["name"] == "pbft.consensus"}
        assert len(pbft_nodes) >= 2, pbft_nodes
        stage_nodes = {s["attrs"]["node"] for s in spans
                       if s["name"] == "stage.commit"}
        assert len(stage_nodes) >= 2, stage_nodes
        # parent chain: every span's trace matches the client root
        assert all(s["traceId"] == root.trace_id.hex() for s in spans)
    finally:
        _stop(nodes, gw)


def test_chain_sample_rate_zero_empty_ring_slow_fires():
    """[trace] sample_rate=0 leaves ZERO entries in the span ring while
    slow-span capture still fires (threshold set below a block stage)."""
    nodes, gw = _chain(sample_rate=0.0, slow_ms=0.0001)
    try:
        tx = _signed_tx(nodes[0].suite, 1)
        res = nodes[0].send_transaction(tx)
        rc = nodes[0].txpool.wait_for_receipt(res.tx_hash, 30)
        assert rc is not None and rc.status == 0
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                otrace.TRACER.stats()["slow_spans"] == 0:
            time.sleep(0.05)
        st = otrace.TRACER.stats()
        assert st["ring_spans"] == 0, st  # nothing sampled
        assert st["slow_spans"] > 0, st   # slow capture still fired
        assert otrace.TRACER.list_traces(slow_only=True)
    finally:
        _stop(nodes, gw)


# -- RPC/ops surface on a live node ---------------------------------------
@pytest.fixture(scope="module")
def rpc_node():
    nodes, gw = _chain(sample_rate=1.0, rpc_on_first=True,
                       ws_on_first=True)
    yield nodes
    _stop(nodes, gw)


def _http_rpc(node, payload, headers=None):
    conn = http.client.HTTPConnection(node.config.rpc_host, node.rpc.port,
                                      timeout=15)
    try:
        conn.request("POST", "/", body=json.dumps(payload).encode(),
                     headers=headers or {})
        r = conn.getresponse()
        return json.loads(r.read()), dict(r.getheaders())
    finally:
        conn.close()


def test_traceparent_http_e2e_get_trace(rpc_node):
    """Client-supplied traceparent: the submission's spans join the
    client's trace (sampled flag honored), the response echoes the
    header, and getTrace returns the stitched spans by id."""
    nodes = rpc_node
    node = nodes[0]
    otrace.TRACER.reset()
    tid = "11d1c0de" * 4
    tp = f"00-{tid}-00f067aa0ba902b7-01"
    tx = _signed_tx(node.suite, 2)
    resp, headers = _http_rpc(
        node,
        {"jsonrpc": "2.0", "id": 1, "method": "sendTransaction",
         "params": ["group0", "", "0x" + tx.encode().hex()]},
        headers={"traceparent": tp})
    assert "result" in resp, resp
    assert headers.get("traceparent") == tp  # echoed for correlation
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        got, _ = _http_rpc(node, {
            "jsonrpc": "2.0", "id": 2, "method": "getTrace",
            "params": ["group0", "", tid]})
        names = {s["name"] for s in got["result"]["spans"]}
        if "stage.notify" in names and "rpc.sendTransaction" in names:
            break
        time.sleep(0.05)
    assert got["result"]["traceId"] == tid
    assert "rpc.sendTransaction" in names, names
    assert "pbft.consensus" in names, names
    # listTraces sees the same trace
    lst, _ = _http_rpc(node, {"jsonrpc": "2.0", "id": 3,
                              "method": "listTraces",
                              "params": ["group0", "", 10]})
    assert any(t["traceId"] == tid for t in lst["result"]["traces"])


def test_rpc_edge_serves_ops_routes(rpc_node):
    """GET /metrics, /status and /trace come from the SAME event-loop
    edge that serves JSON-RPC POSTs (no dedicated scrape thread)."""
    node = rpc_node[0]
    conn = http.client.HTTPConnection(node.config.rpc_host, node.rpc.port,
                                      timeout=15)
    try:
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        assert r.status == 200
        body = r.read().decode()
        assert "bcos_tx_stage_seconds" in body
        # a POST on the same keep-alive connection still serves RPC
        conn.request("POST", "/", body=json.dumps(
            {"jsonrpc": "2.0", "id": 1, "method": "getBlockNumber",
             "params": ["group0", ""]}).encode())
        assert "result" in json.loads(conn.getresponse().read())
        conn.request("GET", "/status")
        st = json.loads(conn.getresponse().read())
        assert st["group"] == "group0" and "pipeline" in st
    finally:
        conn.close()


def test_get_system_status_http_ws_parity(rpc_node):
    """getSystemStatus aggregates the scattered operational state into
    one group-labeled document, identically shaped over HTTP and WS."""
    node = rpc_node[0]
    http_resp, _ = _http_rpc(node, {
        "jsonrpc": "2.0", "id": 1, "method": "getSystemStatus",
        "params": ["group0", ""]})
    doc = http_resp["result"]
    for key in ("group", "node", "blockNumber", "syncMode", "txpool",
                "ingest", "pipeline", "storage", "snapshot", "groups",
                "trace", "consensus"):
        assert key in doc, key
    assert doc["group"] == "group0"
    assert doc["groups"] == ["group0"]
    assert doc["pipeline"]["stages"] is not None
    assert doc["trace"]["ring_size"] > 0

    from fisco_bcos_tpu.net.websocket import ws_connect
    conn = ws_connect(node.config.rpc_host, node.ws.port)
    try:
        conn.send_text(json.dumps({
            "jsonrpc": "2.0", "id": 9, "method": "getSystemStatus",
            "params": ["group0", ""]}))
        _op, payload = conn.recv()
        ws_doc = json.loads(payload)["result"]
    finally:
        conn.close()
    # parity: same shape and same identity over both transports
    assert set(ws_doc) == set(doc)
    assert ws_doc["group"] == doc["group"]
    assert ws_doc["node"] == doc["node"]


def test_ws_traceparent_member(rpc_node):
    """WS has no per-message headers: a `traceparent` MEMBER on the
    request object carries the context instead."""
    node = rpc_node[0]
    otrace.TRACER.reset()
    tid = "22d1c0de" * 4
    from fisco_bcos_tpu.net.websocket import ws_connect
    conn = ws_connect(node.config.rpc_host, node.ws.port)
    try:
        conn.send_text(json.dumps({
            "jsonrpc": "2.0", "id": 4, "method": "getBlockNumber",
            "params": ["group0", ""],
            "traceparent": f"00-{tid}-00f067aa0ba902b7-01"}))
        _op, payload = conn.recv()
        assert "result" in json.loads(payload)
    finally:
        conn.close()
    spans = otrace.TRACER.get_trace(tid)
    assert any(s["name"] == "rpc.getBlockNumber" for s in spans), spans
