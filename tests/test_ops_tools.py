"""Ops tooling: archive-tool, storage-tool, light-monitor, trace recorder.

Reference: tools/archive-tool, tools/storage-tool,
tools/BcosAirBuilder/light_monitor.sh, bcos-scheduler DmcStepRecorder.cpp.
"""

import json
import subprocess
import sys
import time

from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.protocol import Transaction

TOOLS = "tools"


def _run_tool(script, *args):
    r = subprocess.run([sys.executable, f"{TOOLS}/{script}", *args],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (script, args, r.stdout, r.stderr)
    return r.stdout


def _chain_with_blocks(path, n_tx=3):
    node = Node(NodeConfig(crypto_backend="host", storage_path=path,
                           min_seal_time=0.0, tx_count_limit=1))
    node.start()
    kp = node.suite.generate_keypair(b"ops-user")
    hashes = []
    for i in range(n_tx):
        tx = Transaction(to=pc.BALANCE_ADDRESS,
                         input=pc.encode_call(
                             "register",
                             lambda w, i=i: w.blob(b"op%d" % i).u64(1)),
                         nonce=f"op{i}", block_limit=100
                         ).sign(node.suite, kp)
        res = node.send_transaction(tx)
        rc = node.txpool.wait_for_receipt(res.tx_hash, 15)
        assert rc is not None and rc.status == 0
        hashes.append(res.tx_hash)
    height = node.ledger.current_number()
    assert height >= n_tx  # tx_count_limit=1 -> one block per tx
    node.stop()
    node.storage.close()
    return hashes, height


def test_storage_tool_inspects_and_repairs(tmp_path):
    path = str(tmp_path / "chain")
    _chain_with_blocks(path)
    tables = json.loads(_run_tool("storage_tool.py", "tables", path))
    assert "s_number_2_header" in tables
    stats = json.loads(_run_tool("storage_tool.py", "stats", path))
    assert stats["s_number_2_header"]["rows"] >= 4  # genesis + 3
    # get the genesis header; write and read back a repair key
    out = _run_tool("storage_tool.py", "get", path, "s_number_2_header",
                    (0).to_bytes(8, "big").hex())
    assert len(out.strip()) > 0
    _run_tool("storage_tool.py", "set", path, "t_repair", "aa", "bb")
    out = _run_tool("storage_tool.py", "get", path, "t_repair", "aa")
    assert out.strip() == "bb"
    _run_tool("storage_tool.py", "compact", path)
    out = _run_tool("storage_tool.py", "get", path, "t_repair", "aa")
    assert out.strip() == "bb"


def test_archive_tool_roundtrip(tmp_path):
    path = str(tmp_path / "chain")
    archive = str(tmp_path / "blocks.archive")
    hashes, height = _chain_with_blocks(path)
    cut = height  # archive blocks [1, height)
    out = json.loads(_run_tool("archive_tool.py", "archive", path, archive,
                               "--until", str(cut)))
    assert out["archived_blocks"] == cut - 1

    # archived tx bodies are gone from hot storage, headers remain
    node = Node(NodeConfig(crypto_backend="host", storage_path=path))
    assert node.ledger.transaction(hashes[0]) is None
    assert node.ledger.header_by_number(1) is not None
    assert node.ledger.current_number() == height
    node.storage.close()

    info = json.loads(_run_tool("archive_tool.py", "info", archive))
    assert info["s_hash_2_tx"] == cut - 1

    json.loads(_run_tool("archive_tool.py", "restore", path, archive))
    node = Node(NodeConfig(crypto_backend="host", storage_path=path))
    assert node.ledger.transaction(hashes[0]) is not None
    assert node.ledger.receipt(hashes[0]) is not None
    node.storage.close()


def test_light_monitor_flags_lag_and_down(tmp_path):
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0))
    node.start()
    try:
        url = f"http://127.0.0.1:{node.rpc.port}"
        out = subprocess.run(
            [sys.executable, f"{TOOLS}/light_monitor.py", url, "--json"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stdout + out.stderr
        report = json.loads(out.stdout)
        assert report["nodes"][0]["ok"]
        # an unreachable node must flip the exit code
        out = subprocess.run(
            [sys.executable, f"{TOOLS}/light_monitor.py", url,
             "http://127.0.0.1:1", "--json"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 1
        report = json.loads(out.stdout)
        assert report["nodes"][1]["alarm"] == "unreachable"
    finally:
        node.stop()


def test_dmc_step_recorder_matches_across_replicas():
    from fisco_bcos_tpu.scheduler.dmc_rounds import DmcStepRecorder
    from fisco_bcos_tpu.utils import otrace

    def run(messages):
        rec = DmcStepRecorder()
        for round_msgs in messages:
            for m in round_msgs:
                rec.record_message(*m)
            rec.next_round()
        return rec

    msgs = [[(0, 0, b"\xaa" * 20, b"x"), (1, 0, b"\xbb" * 20, b"y")],
            [(0, 1, b"\xbb" * 20, b"z")]]
    a, b = run(msgs), run(msgs)
    assert a.checksums() == b.checksums()
    assert a.summary() == b.summary()
    # intra-round order must NOT matter (parallel executors)
    swapped = [list(reversed(msgs[0])), msgs[1]]
    assert run(swapped).summary() == a.summary()
    # a differing message MUST show up, in the right round
    bad = [msgs[0], [(0, 1, b"\xbb" * 20, b"DIVERGED")]]
    c = run(bad)
    assert c.checksums()[0] == a.checksums()[0]
    assert c.checksums()[1] != a.checksums()[1]

    # the per-block stage holder (otrace.BlockStages): a stage carried by
    # name from one handler to the next, one scoped where it runs
    table = otrace.stages("ops-tools-test")
    table.reset()
    blk = table.block(7)
    blk.open("consensus_pre")
    with blk.stage("execute"):
        time.sleep(0.01)
    blk.close("consensus_pre")
    blk.close("consensus_pre")  # closed once: a second close stamps nothing
    stages = table.snapshot(("consensus_pre", "execute", "commit"))
    assert {k: v["count"] for k, v in stages.items()} == {
        "consensus_pre": 1, "execute": 1, "commit": 0}
    assert stages["consensus_pre"]["seconds"] \
        >= stages["execute"]["seconds"] >= 0.01


def test_storage_tool_cluster_mode(tmp_path):
    """storage_tool inspects a LIVE Max shard cluster via max_cluster.json
    (stats/tables/scan/get through the sharded coordinator)."""
    import json as _json
    import subprocess
    import sys as _sys

    from fisco_bcos_tpu.storage.sharded import (
        DurablePrepareStorage, ShardServer, ShardedStorage,
        make_shard_client)
    from fisco_bcos_tpu.storage.wal import WalStorage

    servers = []
    for i in range(3):
        backend = DurablePrepareStorage(
            WalStorage(str(tmp_path / f"s{i}" / "wal")),
            str(tmp_path / f"s{i}" / "prep"))
        srv = ShardServer(backend)
        srv.start()
        servers.append(srv)
    st = ShardedStorage([make_shard_client("127.0.0.1", s.port)
                         for s in servers])
    st.set_batch("t_demo", [(b"k%d" % i, b"v%d" % i) for i in range(8)])

    cluster = {"shards": [{"host": "127.0.0.1", "port": s.port}
                          for s in servers]}
    cpath = tmp_path / "max_cluster.json"
    cpath.write_text(_json.dumps(cluster))

    def run(*args):
        import os as _os
        repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
        r = subprocess.run(
            [_sys.executable, _os.path.join(repo, "tools",
                                            "storage_tool.py"), *args],
            capture_output=True, text=True, timeout=60, cwd=repo)
        assert r.returncode == 0, r.stderr
        return r.stdout

    tables = _json.loads(run("tables", str(cpath)))
    assert "t_demo" in tables
    stats = _json.loads(run("stats", str(cpath)))
    assert stats["t_demo"]["rows"] == 8
    keys = run("scan", str(cpath), "t_demo").split()
    assert len(keys) == 8
    v = run("get", str(cpath), "t_demo", b"k3".hex()).strip()
    assert bytes.fromhex(v) == b"v3"

    st.close()
    for s in servers:
        s.stop()
        s.backend.close()


def test_storage_tool_leveled_disk_and_keypage(tmp_path):
    """storage_tool on a leveled disk-engine directory written through
    the default key-page layout: stats reports per-level segment/byte/
    debt, scan/get address LOGICAL rows through the page layer, and
    `compact` drains all debt offline (operator catch-up)."""
    from fisco_bcos_tpu.storage import make_storage

    path = str(tmp_path / "disk")
    st = make_storage("disk", path, memtable_mb=0, compact_segments=2)
    assert type(st).__name__ == "KeyPageStorage"  # auto default for disk
    engine = st.backend
    engine._compactor.pause()       # leave debt for the tool to drain
    # one backend write (so, at memtable_mb=0, one L0 segment) a row: the
    # tool opens with the default trigger of 8 segments, so leave more
    for i in range(12):
        st.set("t_wide", b"row%04d" % i, b"v%d" % i)
    assert engine.compaction_debt_bytes() > 0
    st.close()

    stats = json.loads(_run_tool("storage_tool.py", "stats", path))
    assert stats["t_wide"]["rows"] == 12  # logical rows, not _kp_ pages
    eng = stats["_engine"]
    assert "backend_reads" in eng        # page layer detected
    levels = eng["backend_stats"]["levels"]
    assert levels and all(
        set(lv) >= {"level", "segments", "bytes", "debt_bytes"}
        for lv in levels)
    assert eng["backend_stats"]["compaction_debt_bytes"] > 0

    out = _run_tool("storage_tool.py", "get", path, "t_wide",
                    b"row0003".hex())
    assert out.strip() == b"v3".hex()
    out = _run_tool("storage_tool.py", "compact", path)
    drained = json.loads(out.strip().splitlines()[0])
    assert drained["debt_bytes_before"] > 0
    assert drained["debt_bytes_after"] == 0
    stats = json.loads(_run_tool("storage_tool.py", "stats", path))
    assert stats["_engine"]["backend_stats"]["compaction_debt_bytes"] == 0
    assert stats["t_wide"]["rows"] == 12
