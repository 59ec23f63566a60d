"""Continuous-profiling plane (analysis/profiler.py + tools/perf_gate.py).

Covers the ISSUE-15 test checklist: disarmed-cost structure (no sampler
thread, otrace's stage labels), folded-stack correctness against a
synthetic known-shape workload, per-thread role classification, CPU
attribution, burst-on-slow-span on a live node, the /profile route on
both the RPC edge and the [monitor] ops server, ring boundedness, the
host-weather sampler, and the perf gate's injected-regression /
identical-rerun behaviour.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

import pytest

from fisco_bcos_tpu.analysis import hostweather, profiler
from fisco_bcos_tpu.utils import otrace


# -- structure / disarmed contract ----------------------------------------
def test_disarmed_has_no_sampler_thread():
    p = profiler.SamplingProfiler()
    assert not p.armed and p._thread is None
    p.configure(hz=50)
    assert p.armed and p._thread is not None and p._thread.is_alive()
    t = p._thread
    p.configure(hz=0)
    # disarm joins the thread: the disarmed state has NO thread, not a
    # parked one
    assert not p.armed and p._thread is None
    t.join(timeout=5)
    assert not t.is_alive()


def test_stage_marker_scopes_and_restores():
    """The sampler's stage label is the otrace stage the thread is inside,
    under the stage table's own name."""
    table = otrace.StageTable("labels")
    ident = threading.get_ident()
    assert profiler.current_stage(ident) is None
    with table.stage("execute"):
        assert profiler.current_stage(ident) == "execute"
        with table.stage("roots"):
            assert profiler.current_stage(ident) == "roots"
        assert profiler.current_stage(ident) == "execute"
    # fully unwound: no residue in the stage map (bounded by live scopes)
    assert profiler.current_stage(ident) is None
    assert ident not in otrace.THREAD_STAGES


def test_stage_marker_never_left_stale():
    """A stage that crosses threads labels nothing; one stopped out of
    order, cancelled, or stopped on another thread leaves no label."""
    table = otrace.StageTable("stale")
    ident = threading.get_ident()
    waiting = table.stage("round_wait")
    assert profiler.current_stage(ident) is None
    outer, inner = table.stage("execute"), table.stage("roots")
    outer.stop()  # out of order: the inner one is still open
    assert profiler.current_stage(ident) == "roots"
    inner.stop()
    assert profiler.current_stage(ident) is None
    table.stage("fill").cancel()
    assert profiler.current_stage(ident) is None
    elsewhere = table.stage("commit")
    for st in (elsewhere, waiting):
        t = threading.Thread(target=st.stop)
        t.start()
        t.join(5)
        assert not t.is_alive()
    assert ident not in otrace.THREAD_STAGES
    snap = table.snapshot()
    assert snap["commit"]["count"] == snap["round_wait"]["count"] == 1


@pytest.mark.parametrize("name,role", [
    ("tx-ingest", "ingest"), ("sched-commit", "commit"),
    ("sched-notify", "notify"), ("pbft", "pbft"),
    ("pbft-exec_0", "execute"), ("dmc-worker_0", "execute"),
    ("exec-pump-0", "execute"), ("sealer", "seal"),
    ("crypto-lane", "crypto"), ("crypto-lane-w_1", "crypto"),
    ("nativeec_2", "crypto"), ("storage-compact", "compaction"),
    ("block-sync-dl", "sync"), ("snapshot", "sync"),
    ("rpc-worker-3", "edge"), ("ops-http", "edge"), ("sub-fanout", "edge"),
    ("tx-sync", "net"), ("gw-ab12", "net"), ("p2p-read-ab12", "net"),
    ("health-probe", "control"), ("profile-sampler", "profiler"),
    ("MainThread", "main"), ("never-heard-of-it", "other")])
def test_role_classification(name, role):
    assert profiler.classify(name) == role
    assert role in profiler.ROLES


def test_every_thread_a_node_spawns_has_a_role():
    """No thread of a serving node falls to `other`: the role map is the
    operator's view of where the process's CPU goes."""
    from fisco_bcos_tpu.init.node import Node, NodeConfig

    before = {t.name for t in threading.enumerate()}
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0, ws_port=0, metrics_port=0))
    node.start()
    try:
        _commit_one(node, 7)
        names = {t.name for t in threading.enumerate()} - before
    finally:
        node.stop()
    assert {"tx-ingest", "sched-notify", "sealer", "jsonrpc-http"} <= names
    assert not [n for n in names if profiler.classify(n) == "other"], names


def _burn(seconds: float) -> None:
    t_end = time.thread_time() + seconds
    while time.thread_time() < t_end:
        pass


def test_cpu_by_role_reads_each_threads_own_clock():
    """A `sched-notify` thread that burns ~50 ms shows under `notify` at
    once, no sampler armed; the roles sum to the process's CPU."""
    before = profiler.cpu_by_role()
    assert tuple(before) == profiler.ROLES
    done = threading.Event()
    t = threading.Thread(target=lambda: (_burn(0.05), done.wait(10)),
                         name="sched-notify", daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            during = profiler.cpu_by_role()
            if during["notify"] - before["notify"] >= 0.04:
                break
            time.sleep(0.01)
        total = time.process_time()
        assert during["notify"] - before["notify"] >= 0.04, during
        assert abs(sum(during.values()) - total) <= 0.02 * total
        assert min(during.values()) >= 0.0
    finally:
        done.set()
        t.join(5)
    assert not t.is_alive()
    # a thread that has exited leaves its CPU in `native`, not lost
    after = profiler.cpu_by_role()
    assert after["native"] >= during["native"] + 0.03


@pytest.mark.parametrize("thread,stages,head", [
    ("sched-notify", ("prime",), "notify;stage.prime;"),
    ("pbft-exec_0", ("roots", "state_root"),
     "execute;stage.roots;stage.state_root;")])
def test_sampler_labels_a_thread_with_its_otrace_stage(thread, stages, head):
    """The flamegraph's stage split is the stage table's names, the open
    stages outermost first."""
    table = otrace.StageTable("sampled")
    p = profiler.SamplingProfiler()
    stop = threading.Event()

    def staged(names=stages):
        if not names:
            return _known_shape_root(stop)
        with table.stage(names[0]):
            staged(names[1:])

    t = threading.Thread(target=staged, name=thread, daemon=True)
    t.start()
    try:
        p.configure(hz=150, ring=1024)
        _wait(lambda: head in p.folded())
        folded = p.folded()
        p.configure(hz=0)
    finally:
        stop.set()
        t.join(5)
    assert not t.is_alive()
    assert any(ln.startswith(head) and "_known_shape_leaf"
               in ln for ln in folded.splitlines()), folded[:800]


def test_ring_bounded():
    fold = profiler._Folded(cap=64)
    for i in range(1000):
        fold.add(f"main;mod.py:f{i}")
    assert len(fold.counts) <= 64
    assert fold.overflow == 1000 - len(fold.counts)
    assert fold.samples == 1000
    text = profiler._folded_text(fold.counts, fold.overflow)
    assert "(overflow)" in text
    assert len(text.splitlines()) <= 65


# -- folded-stack correctness against a known-shape workload --------------
def _known_shape_leaf(stop):
    x = 1
    while not stop.is_set():
        # burn in a long inner chunk so samples land in THIS frame, not
        # in the Event.is_set call
        for _ in range(20000):
            x = (x * 31 + 7) & 0xFFFFFFFF


def _known_shape_mid(stop):
    _known_shape_leaf(stop)


def _known_shape_root(stop):
    _known_shape_mid(stop)


def _leaf_line(p):
    """(line, its sample count) of the synthetic burner's folded stack."""
    line = next((ln for ln in p.folded().splitlines()
                 if "_known_shape_leaf" in ln), None)
    return line, int(line.rsplit(" ", 1)[1]) if line else 0


def _wait(predicate, seconds: float = 30.0) -> None:
    """Until the sampler has seen enough, not for a fixed time: beside five
    xdist workers a 150 Hz sampler may get a tenth of its samples."""
    deadline = time.monotonic() + seconds
    while not predicate() and time.monotonic() < deadline:
        time.sleep(0.05)


def test_folded_stacks_synthetic_shape():
    p = profiler.SamplingProfiler()
    stop = threading.Event()
    t = threading.Thread(target=_known_shape_root, args=(stop,),
                         name="synthetic-burn", daemon=True)
    t.start()
    try:
        p.configure(hz=150, ring=1024)
        _wait(lambda: _leaf_line(p)[1] >= 10)
        p.configure(hz=0)
    finally:
        stop.set()
        t.join(5)
    line, _count = _leaf_line(p)
    assert line is not None, p.folded()[:800]
    # root-first order with the full call chain intact
    i_root = line.index("_known_shape_root")
    i_mid = line.index("_known_shape_mid")
    i_leaf = line.index("_known_shape_leaf")
    assert i_root < i_mid < i_leaf
    # the unknown-prefix thread classifies as `other` at the stack root
    assert line.startswith("other;")
    # the spinning leaf dominates the synthetic thread's samples
    count = int(line.rsplit(" ", 1)[1])
    assert count >= 10


def test_cpu_attribution_names_the_burner():
    p = profiler.SamplingProfiler()
    stop = threading.Event()
    t = threading.Thread(target=_known_shape_root, args=(stop,),
                         name="synthetic-burn", daemon=True)
    t.start()
    try:
        p.configure(hz=100, ring=1024)
        _wait(lambda: _leaf_line(p)[1] >= 20
              and p.attribution()["total_cpu_seconds"] > 0.1)
        attrib = p.attribution()
        p.configure(hz=0)
    finally:
        stop.set()
        t.join(5)
    assert attrib["total_cpu_seconds"] > 0.1, attrib
    # the burner's CPU lands on a named function, and coverage of the
    # process total is high (only CPU on never-sampled threads escapes)
    assert attrib["attributed_pct"] is not None
    assert attrib["attributed_pct"] > 50.0, attrib
    # the burner is named among the top holders (the Event.is_set leaf is
    # an acceptable alias for the same loop)
    top2 = list(attrib["by_func"])[:2]
    assert any("test_profiler.py" in f for f in top2), attrib["by_func"]


# -- burst mode ------------------------------------------------------------
def test_burst_on_slow_span_and_trace_linking():
    p = profiler.PROFILER
    old = (p.hz, p.ring, p.burst_hz, p.burst_s)
    tr_stats = otrace.TRACER.stats()
    try:
        p.configure(hz=50, ring=1024, burst_hz=97, burst_s=0.2)
        p._burst_next_ok = 0.0  # the storm guard is not under test
        otrace.TRACER.configure(sample_rate=1.0, slow_ms=1.0)
        root = otrace.TRACER.new_root()
        with otrace.TRACER.span("slow.unit", parent=root):
            time.sleep(0.01)
        tid = root.trace_id.hex()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and tid not in p.burst_ids():
            time.sleep(0.02)
        rec = p.burst_profile(tid)
        assert rec is not None, p.burst_ids()
        assert rec["traceId"] == tid and rec["reason"] == "slow.unit"
        assert rec["samples"] > 0 and rec["folded"].strip()
        # bounded retention: the burst dict never outgrows its keep
        for i in range(profiler.SamplingProfiler._BURST_KEEP + 4):
            with p._lock:
                p._bursts[f"{i:032x}"] = {"traceId": f"{i:032x}",
                                          "folded": ""}
                while len(p._bursts) > p._BURST_KEEP:
                    p._bursts.popitem(last=False)
        assert len(p.burst_ids()) <= profiler.SamplingProfiler._BURST_KEEP
    finally:
        with p._lock:
            p._bursts.clear()
        p.configure(hz=old[0], ring=old[1], burst_hz=old[2],
                    burst_s=old[3])
        otrace.TRACER.configure(sample_rate=tr_stats["sample_rate"],
                                slow_ms=tr_stats["slow_ms"])


# -- live node: /profile on both edges + getTrace profile member ----------
@pytest.fixture
def solo_node():
    from fisco_bcos_tpu.init.node import Node, NodeConfig

    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0, metrics_port=0,
                           trace_sample_rate=1.0, trace_slow_ms=2.0,
                           profile_hz=47.0, profile_burst_hz=97.0,
                           profile_burst_s=0.2))
    node.start()
    yield node
    node.stop()


def _commit_one(node, i: int):
    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.protocol import Transaction

    tx = Transaction(to=pc.BALANCE_ADDRESS,
                     input=pc.encode_call(
                         "register",
                         lambda w: w.blob(b"pf%d" % i).u64(10 + i)),
                     nonce=f"pf{i}", block_limit=100).sign(
        node.suite, node.suite.generate_keypair(b"prof-test"))
    res = node.send_transaction(tx)
    rc = node.txpool.wait_for_receipt(res.tx_hash, 30)
    assert rc is not None and rc.status == 0
    return res


def test_profile_route_on_rpc_edge_and_monitor_server(solo_node):
    node = solo_node
    _commit_one(node, 0)
    for host, port in ((node.rpc.host, node.rpc.port),
                      ("127.0.0.1", node.metrics.port)):
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("GET", "/profile?seconds=0.3")
        r = conn.getresponse()
        body = r.read().decode()
        assert r.status == 200, (port, r.status, body[:200])
        assert body.strip(), "empty folded capture"
        # role-classified roots from the node's own threads
        assert any(ln.split(";")[0] in
                   ("ingest", "commit", "seal", "edge", "main", "other",
                    "control", "net", "execute")
                   for ln in body.splitlines()), body[:400]
        conn.request("GET", "/profile?fmt=flame")
        r = conn.getresponse()
        html = r.read().decode()
        assert r.status == 200 and "<html" in html and "FOLDED" in html
        conn.close()


def test_burst_linked_via_get_trace_on_live_node(solo_node):
    node = solo_node
    root = otrace.TRACER.new_root()
    tid = root.trace_id.hex()
    with otrace.ctx_scope(root):
        _commit_one(node, 1)  # well over the 2 ms slow threshold
    # the live node's OWN pipeline spans compete for the single burst
    # slot; keep firing genuine slow spans under OUR root (the storm
    # guard is reset each try) until the burst lands on this trace
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline \
            and tid not in profiler.PROFILER.burst_ids():
        profiler.PROFILER._burst_next_ok = 0.0
        with otrace.TRACER.span("slow.retry", parent=root):
            time.sleep(0.005)
        time.sleep(0.05)
    impl = node.make_rpc_impl()
    doc = impl.get_trace("group0", "", tid)
    assert doc.get("profile"), profiler.PROFILER.burst_ids()
    assert doc["profile"]["traceId"] == tid
    assert doc["profile"]["folded"].strip()
    lst = impl.list_traces("group0", "")
    ours = [t for t in lst["traces"] if t["traceId"] == tid]
    assert ours and ours[0]["profiled"] is True
    # getSystemStatus aggregates the plane
    st = node.system_status()
    assert st["profile"]["armed"] and tid in st["profile"]["bursts"]


def test_system_status_has_profile_when_disarmed():
    from fisco_bcos_tpu.init.node import Node, NodeConfig

    node = Node(NodeConfig(crypto_backend="host", profile_hz=0.0))
    try:
        st = node.system_status()
        assert st["profile"]["armed"] is False
    finally:
        node.stop()


# -- host weather ----------------------------------------------------------
def test_host_weather_sample_shape():
    w = hostweather.sample(spin_ms=20)
    assert w["spin_score"] > 0
    assert w["cores"] >= 1
    # PSI/steal may be unavailable on exotic kernels, but the keys exist
    assert "psi_cpu" in w and "steal_pct" in w
    # PSI alone must NOT trip the predicate: a saturating bench elevates
    # /proc/pressure/cpu with its own load (the stamp keeps it for humans)
    noisy, _why = hostweather.noisy(
        {"psi_cpu": {"avg10": 50.0, "avg60": 0.0}, "steal_pct": 0.0})
    assert not noisy
    # hypervisor steal — the signal our own process cannot fake — does
    noisy, _why = hostweather.noisy(
        {"psi_cpu": {"avg10": 0.0, "avg60": 0.0}, "steal_pct": 5.0})
    assert noisy
    noisy, _why = hostweather.noisy(
        {"psi_cpu": {"avg10": 0.0, "avg60": 0.0}, "steal_pct": 0.0,
         "spin_score": 100}, reference_spin=1000)
    assert noisy
    noisy, _why = hostweather.noisy(
        {"psi_cpu": {"avg10": 0.0, "avg60": 0.0}, "steal_pct": 0.0,
         "spin_score": 1000}, reference_spin=1000)
    assert not noisy


# -- perf gate -------------------------------------------------------------
def _gate():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "perf_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_BASE = {"metric": "chain_tps", "chain_tps_4node_host": 1000.0,
         "rpc_read_qps": 5000.0, "trace_e2e_p50_ms": 30.0}


def _jitter(line, f):
    out = dict(line)
    for k in ("chain_tps_4node_host", "rpc_read_qps", "trace_e2e_p50_ms"):
        out[k] = round(out[k] * f, 2)
    return out


def test_perf_gate_passes_identical_rerun_and_catches_2x():
    pg = _gate()
    # history reflecting the documented 1.45x run-to-run swings: the
    # derived band must absorb a dip INSIDE that recorded spread
    history = [_jitter(_BASE, f) for f in (0.76, 1.0, 1.1)]
    # identical rerun: candidate == a recorded run -> PASS
    rep = pg.gate([dict(_BASE)], history, min_runs=3)
    assert rep["ok"], rep
    # a dip within the recorded noise: still PASS (bands from spread)
    rep = pg.gate([_jitter(_BASE, 0.80)], history, min_runs=3)
    assert rep["ok"], rep
    # injected 2x regression on a chain row: FAIL, named
    rep = pg.gate([_jitter(_BASE, 0.5)], history, min_runs=3)
    assert not rep["ok"]
    assert "chain_tps_4node_host" in rep["failed"]
    # lower-better direction: a 2x slowdown in latency also FAILs
    bad = dict(_BASE)
    bad["trace_e2e_p50_ms"] = _BASE["trace_e2e_p50_ms"] * 2.1
    rep = pg.gate([bad], history, min_runs=3)
    assert "trace_e2e_p50_ms" in rep["failed"]


def test_perf_gate_catastrophic_trips_thin_history():
    pg = _gate()
    history = [dict(_BASE)]  # ONE recorded run: everything is advisory...
    rep = pg.gate([_jitter(_BASE, 0.85)], history, min_runs=3)
    assert rep["ok"], rep  # ...so a marginal dip stays advisory
    rep = pg.gate([_jitter(_BASE, 0.5)], history, min_runs=3)
    assert not rep["ok"]  # ...but a halved metric is fatal regardless


def test_perf_gate_noise_widens_bands():
    pg = _gate()
    history = [_jitter(_BASE, f) for f in (0.98, 1.0, 1.02)]
    cand = _jitter(_BASE, 0.84)  # just under the quiet-host band (12%)
    quiet = pg.gate([cand], history, min_runs=3, weather_now=None)
    assert not quiet["ok"]
    noisy_weather = {"psi_cpu": {"avg10": 30.0, "avg60": 10.0},
                     "steal_pct": 5.0, "spin_score": 1}
    loud = pg.gate([cand], history, min_runs=3,
                   weather_now=noisy_weather)
    assert loud["ok"], loud  # the widened band absorbs the dip
    assert loud["noisy"]


def test_perf_gate_interleaved_medians():
    pg = _gate()
    history = [_jitter(_BASE, f) for f in (0.95, 1.0, 1.05)]
    # 3 interleaved candidate runs: one noisy outlier must not fail the
    # gate when the median is healthy
    cands = [_jitter(_BASE, 0.55), _jitter(_BASE, 1.0),
             _jitter(_BASE, 1.02)]
    rep = pg.gate(cands, history, min_runs=3)
    assert rep["ok"], rep
    assert rep["candidate_runs"] == 3
