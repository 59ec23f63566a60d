"""The stage plane (utils/otrace.py `stages`): one stamp per stage of a
cohort's round trip through a node, read three ways — the always-on
aggregate in getSystemStatus, the profiler's `/host:CPU` plane, and the
crypto seam's host seconds per device call."""

import glob
import re
import sys
import threading
import time

import pytest

from fisco_bcos_tpu.crypto.suite import CryptoSuite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.sdk.client import SdkClient
from fisco_bcos_tpu.utils import otrace

from test_otrace import _chain, _stop

# a block's own stages, stamped once per block on every node
BLOCK_STAGES = ("consensus_pre", "fill", "execute", "roots",
                "consensus_wait", "commit", "notify")
# the chain of one batch through its ingress node (`gossip`, `crypto` and
# `seal_wait` lie inside others)
CHAIN = ("rpc_no_request", "rpc_decode", "lane_wait", "admit", "round_wait",
         *BLOCK_STAGES, "rpc_respond")
# inside `roots`, once a block each
ROOTS_PARTS = ("txs_root", "receipts_root", "prewrite", "state_root")


@pytest.fixture(scope="module")
def chain():
    nodes, gw = _chain(sample_rate=0.0, rpc_on_first=True)
    yield nodes
    _stop(nodes, gw)


def _wires(suite, tag: str, n: int) -> list:
    kp = suite.generate_keypair(b"stages-client")
    return ["0x" + Transaction(
        to=pc.BALANCE_ADDRESS, nonce=f"{tag}-{i}", block_limit=400,
        input=pc.encode_call("register", lambda w, i=i: w.blob(
            f"{tag}-{i}".encode()).u64(1))).sign(suite, kp).encode().hex()
        for i in range(n)]


def _send_batch(node, tag: str, n: int) -> float:
    """One JSON-RPC batch of n sendTransaction (wait=true) over HTTP; ->
    its wall seconds on the client's clock."""
    cli = SdkClient(f"http://127.0.0.1:{node.rpc.port}")
    t0 = time.monotonic()
    out = cli.request_batch([("sendTransaction",
                              ["group0", "", w, False, True])
                             for w in _wires(node.suite, tag, n)])
    wall = time.monotonic() - t0
    assert all(r["result"]["status"] == 0 for r in out), out[:2]
    return wall


def _stages(node) -> dict:
    return node.system_status()["trace"]["stages"]


def _settled(node, before: dict) -> dict:
    """The stage table once the batch's last stamps are in (`notify` and
    `rpc_respond` end a moment after the client has its answer)."""
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        now = _stages(node)
        if all(now[k]["count"] > before[k]["count"]
               for k in ("notify", "rpc_respond")):
            return now
        time.sleep(0.01)
    return _stages(node)


def test_every_stage_is_there_from_the_start():
    table = otrace.StageTable("fresh")
    snap = table.snapshot()
    assert set(snap) == set(otrace.STAGES) >= set(CHAIN)
    assert all(re.fullmatch(r"[a-z_]+", k) for k in snap), sorted(snap)
    assert all(v == {"count": 0, "seconds": 0.0, "cpu_seconds": 0.0,
                     "cpu_wall_seconds": 0.0} for v in snap.values())


def _burn(seconds: float) -> None:
    t_end = time.thread_time() + seconds
    while time.thread_time() < t_end:
        pass


def test_stage_cpu_on_its_own_thread():
    """Work against wait: a busy stage reads its thread on a core nearly
    all its time, a sleeping one nearly none; a stop on another thread
    adds to neither CPU field. It runs before the module's `chain` is up:
    the four nodes' threads take the interpreter lock during the burn."""
    table = otrace.StageTable("cpu")
    with table.stage("execute"):
        _burn(0.05)
    with table.stage("prime"):
        time.sleep(0.05)
    moved = table.stage("commit")
    t = threading.Thread(target=moved.stop)
    t.start()
    t.join(5)
    assert not t.is_alive()
    snap = table.snapshot()
    for name in ("execute", "prime"):
        row = snap[name]
        assert row["count"] == 1
        assert 0.0 <= row["cpu_seconds"] <= row["cpu_wall_seconds"]
        assert row["cpu_wall_seconds"] == pytest.approx(row["seconds"])
    busy, idle = snap["execute"], snap["prime"]
    assert busy["cpu_seconds"] >= 0.05
    assert busy["cpu_seconds"] / busy["cpu_wall_seconds"] > 0.8, busy
    assert idle["cpu_seconds"] / idle["cpu_wall_seconds"] < 0.2, idle
    assert snap["commit"] == {"count": 1, "seconds": snap["commit"]["seconds"],
                              "cpu_seconds": 0.0, "cpu_wall_seconds": 0.0}


def test_one_stamp_per_stage_per_block(chain):
    node = chain[0]
    seen = _stages(node)
    for k in range(3):
        before = seen
        height = node.ledger.current_number()
        wall = _send_batch(node, f"blk{k}", 24)
        seen = _settled(node, before)
        blocks = node.ledger.current_number() - height
        assert blocks >= 1
        delta = {n: (seen[n]["count"] - before[n]["count"],
                     seen[n]["seconds"] - before[n]["seconds"])
                 for n in seen}
        assert all(re.fullmatch(r"[a-z_]+", n) for n in seen)
        # one stamp per block for the block's stages, one per batch for
        # the cohort's; nothing is stamped per transaction
        for n in ("consensus_pre", "consensus_wait", "commit", "notify"):
            assert delta[n][0] == blocks, (n, delta)
        # (a node starved of CPU can have block sync abort a speculative
        # result that consensus then executes again: stamped each time)
        for n in ("fill", "execute", "roots"):
            assert blocks <= delta[n][0] <= blocks + 1, (n, delta)
        for n in ("rpc_decode", "lane_wait", "admit", "gossip", "crypto",
                  "rpc_respond"):
            assert delta[n][0] == 1, (n, delta)
        assert 1 <= delta["round_wait"][0] <= blocks, delta
        # seconds only grow, and the chain's stages are sequential with one
        # batch in flight: they sum to no more than the client's wall time
        assert all(d[1] >= 0.0 for d in delta.values()), delta
        chain_s = sum(delta[n][1] for n in CHAIN if n != "rpc_no_request")
        assert 0.0 < chain_s <= wall + 0.05, (chain_s, wall, delta)
        # what lies inside another stage is no longer than it
        assert delta["gossip"][1] <= delta["admit"][1]
        assert delta["crypto"][1] <= delta["admit"][1]
    # the client's turn: from the second batch on, the edge saw the gap
    assert seen["rpc_no_request"]["count"] >= 2


def test_roots_is_stamped_in_its_parts(chain):
    """`roots` holds four stages of its own, each once a block: what is
    left of it is the header hash."""
    node = chain[0]
    before = _stages(node)
    _send_batch(node, "parts", 16)
    after = _settled(node, before)
    d = {n: {k: after[n][k] - before[n][k] for k in after[n]}
         for n in ROOTS_PARTS + ("roots",)}
    assert d["roots"]["count"] >= 1
    for n in ROOTS_PARTS:
        assert d[n]["count"] == d["roots"]["count"], (n, d)
        assert 0.0 < d[n]["seconds"]
        # a part runs on the thread that opened it: its CPU is read
        assert 0.0 < d[n]["cpu_wall_seconds"]
    assert sum(d[n]["seconds"] for n in ROOTS_PARTS) <= d["roots"]["seconds"]
    # a wait that crosses threads reads no CPU clock, on any node
    for n in otrace.CROSS_THREAD:
        assert after[n]["cpu_seconds"] == after[n]["cpu_wall_seconds"] == 0.0


def test_replicas_stamp_their_own_tables(chain):
    """The tracer is process-wide; the tables are per node."""
    labels = {n.trace_label for n in chain}
    assert len(labels) == len(chain)
    before = [_stages(n) for n in chain]
    _send_batch(chain[0], "own", 8)
    _settled(chain[0], before[0])
    for node, b in zip(chain[1:], before[1:]):
        deadline = time.monotonic() + 5  # a replica may commit a moment on
        while _stages(node)["notify"]["count"] == b["notify"]["count"] \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        now = _stages(node)
        assert now["rpc_decode"]["count"] == b["rpc_decode"]["count"]
        assert now["execute"]["count"] > b["execute"]["count"]


def test_pipeline_stages_come_from_the_one_aggregate(chain):
    node = chain[0]
    _send_batch(node, "pipe", 8)
    time.sleep(0.2)  # idle chain: both reads see the same table
    st = node.system_status()
    for name in ("fill", "execute", "roots", "consensus_wait", "commit"):
        assert st["pipeline"]["stages"][name] == st["trace"]["stages"][name]
        assert st["pipeline"]["stages"][name]["count"] >= 1
    assert not hasattr(node.scheduler, "_stage_s")


def test_stage_aggregates_without_jax_profiler(monkeypatch):
    monkeypatch.setattr(otrace, "_annotation", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # ImportError
    table = otrace.StageTable("no-profiler")
    with table.stage("admit"):
        time.sleep(0.002)
    open_stage = table.stage("round_wait")
    open_stage.stop()
    assert otrace._annotation is False
    snap = table.snapshot()
    assert snap["admit"]["count"] == 1 and snap["admit"]["seconds"] >= 0.002
    assert snap["round_wait"]["count"] == 1


def test_cancelled_stage_stamps_nothing():
    table = otrace.StageTable("cancel")
    st = table.stage("fill")
    st.cancel()
    st.stop()
    assert table.snapshot()["fill"]["count"] == 0


def test_stages_reach_the_profilers_host_plane(chain, tmp_path):
    """A profiler session around a 24-tx block sees the stages under their
    own names on `/host:CPU`, the plane the benchmark's reducer reads."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        before = _stages(chain[0])
        _send_batch(chain[0], "prof", 24)
        _settled(chain[0], before)
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(str(
        tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))
    if not found:
        pytest.skip("the CPU profiler wrote no trace")
    planes = [p for p in ProfileData.from_file(found[-1]).planes
              if p.name == "/host:CPU"]
    if not planes:
        pytest.skip("the CPU profiler gave no host plane")
    names = {e.name for p in planes for ln in p.lines for e in ln.events}
    # every stage that starts and ends inside the session (round_wait and
    # rpc_no_request may have started before it)
    want = set(CHAIN) - {"rpc_no_request", "round_wait"}
    assert want <= names, sorted(want - names)


def test_seam_seconds_grow_only_on_device_calls():
    dev = CryptoSuite("ecdsa", backend="device", allow_cpu=True)
    host = CryptoSuite("ecdsa", backend="host")
    msgs = [bytes([i % 251]) * (i % 300 + 1) for i in range(70)]
    keys = ("packSeconds", "callSeconds", "unpackSeconds")
    for op in ("recover", "verify", "hash", "merkle"):
        assert all(dev.status()["ops"][op][k] == 0.0 for k in keys)
    t0 = time.monotonic()
    digests = dev.hash_batch(msgs)
    root = dev.merkle_root(digests)
    wall = time.monotonic() - t0
    assert digests == host.hash_batch(msgs)
    assert root == host.merkle_root(digests)
    ops = dev.status()["ops"]
    for op in ("hash", "merkle"):
        assert ops[op]["deviceCalls"] == 1
        assert all(ops[op][k] > 0.0 for k in keys), ops[op]
    total = sum(ops[op][k] for op in ("hash", "merkle") for k in keys)
    assert total <= wall, (total, wall)
    # a second call adds to the same counters
    dev.hash_batch(msgs)
    again = dev.status()["ops"]["hash"]
    assert again["deviceCalls"] == 2
    assert all(again[k] > ops["hash"][k] for k in keys)
    # the host path counts its calls and no seconds
    hops = host.status()["ops"]
    assert hops["hash"]["hostCalls"] == 1 and hops["merkle"]["hostCalls"] == 1
    for op in ("recover", "verify", "hash", "merkle"):
        assert all(hops[op][k] == 0.0 for k in keys), hops[op]
