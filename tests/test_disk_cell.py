"""The configuration `air4-transfer-disk` and its cell, as the benchmark
finds them: data files alone. The configuration names only what
`tools/build_chain.py` takes, the cell reports every metric this chain's
storage stamps, and its rehearsal on the CPU ends correct."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
CONFIG = "air4-transfer-disk"
CELL = CONFIG + ".batch1k-serial"
NEW_METRICS = (
    "storage_prepare_ms_per_block", "storage_commit_ms_per_block",
    "state_read_ms_per_block", "page_cache_hit_share",
    "page_write_kb_per_block", "flushes_per_100_blocks",
    "compaction_busy_share", "storage_stall_ms_per_block",
    "compaction_debt_mb", "page_evictions_per_block", "page_cache_mb",
    "flush_ms_per_block", "merges_per_100_blocks", "page_packed_share")


def _doc() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name: str = CONFIG) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_configuration_is_the_transfer_chain_on_disk():
    entry = next(c for c in _doc()["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["reduced"] == ["hosts", "device_nodes", "p2p_codec",
                                "state_scale"]
    cfg, base = _config(), _config("air4-transfer")
    assert all(k in cfg and k in cfg["reduced_why"] for k in entry["reduced"])
    assert cfg["guarantees"] == base["guarantees"]     # word for word
    assert cfg["accounts"] == cfg["state_scale"] == 2_000_000
    assert cfg["build_chain"] == base["build_chain"] + [
        "--storage", "disk", "--key-page-size", "10240"]
    for key in ("accounts", "storage", "page_cache", "unpaged_tables",
                "pages"):
        assert key in cfg["assumed"]
    # every node merges L0 at RocksDB's default trigger, and says so
    assert [cfg["config_ini"][n]["storage"] for n in "0123"] == [
        {"compact_segments": "4"}] * 4
    assert "compact_segments 4" in cfg["assumed"]["storage"]
    assert cfg["config_ini"]["0"]["rpc"] == base["config_ini"]["0"]["rpc"]
    from fisco_bcos_tpu.storage import keypage
    assert f"{keypage.PAGE_CACHE_BYTES >> 20} MiB" \
        in cfg["assumed"]["page_cache"]
    for table in keypage.UNPAGED_TABLES:
        assert table in cfg["assumed"]["unpaged_tables"]


def test_configuration_names_only_flags_build_chain_has():
    usage = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "build_chain.py"),
         "--help"], capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu")).stdout
    known = set(re.findall(r"(?<![\w-])(--?[a-z][a-z-]*)", usage))
    flags = [a for a in _config()["build_chain"] if a.startswith("-")]
    assert "--key-page-size" in flags and set(flags) <= known, (flags, known)


def test_build_chain_writes_the_page_size_into_every_node(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from build_chain import build_chain
    from fisco_bcos_tpu.tool.config import _load_node_parts
    info = build_chain(str(tmp_path / "chain"), 2, storage_backend="disk",
                       key_page_size=10240)
    for n in info["nodes"]:
        with open(os.path.join(n["dir"], "config.ini")) as f:
            assert re.search(r"key_page_size\s*=\s*10240", f.read())
        cfg = _load_node_parts(n["dir"], None)[0]
        assert cfg.storage_backend == "disk"
        assert cfg.storage_key_page_size == 10240
        assert cfg.storage_compact_segments == 8   # the program's default
    with pytest.raises(ValueError):
        build_chain(str(tmp_path / "small"), 1, key_page_size=100)


def test_cell_and_its_metrics_are_listed():
    doc = _doc()
    cell = next(w for w in doc["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="batch1k-serial",
                        chips=1)
    names = [w["name"] for w in doc["workloads"]]
    assert names[3] == CELL                    # appended, not inserted
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["layer"] == "storage"
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            assert json.load(f)["reader"] == "status_ratio"
    # whatever the transfer cell reports, this one reports, after it
    for m in doc["per_layer"]:
        ws = m["workloads"]
        if "air4-transfer.batch1k-serial" in ws:
            assert ws.index(CELL) > ws.index("air4-transfer.batch1k-serial"), \
                m["name"]
    assert CELL not in by_name["sm2_verify_roofline"]["workloads"]


def test_rehearsal_ends_correct_and_reads_every_storage_metric():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 34), "--seconds", "3", "--trace", "1",
         "--rehearse-cpu"], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] is True and out["correct"] is True, p.stderr[-3000:]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(v == [0, 0] for v in out["compared"].values()), out["compared"]
    layers = {k: v["value"] for k, v in out["per_layer"].items()}
    for name in NEW_METRICS:
        assert isinstance(layers.get(name), float), (name, layers)
    assert layers["storage_prepare_ms_per_block"] > 0.0
    assert layers["storage_commit_ms_per_block"] > 0.0
    assert layers["storage_prepare_ms_per_block"] \
        + layers["storage_commit_ms_per_block"] \
        <= layers["commit_ms_per_block"]
    assert layers["page_write_kb_per_block"] > 0.0
    assert 0.0 < layers["page_cache_mb"] <= 16.0
    # the account ledger's pages are of one width: none parsed into a dict
    assert layers["page_packed_share"] >= 95.0
