"""The cell `air4-transfer-blk10k.batch10k-serial`: found by name through
the manifest, its metrics readable, and its control flow sound at a tiny
size on the CPU (a chain whose genesis says 10,000 a block)."""

import json
import os

from conftest import BENCH, ROOT
from manifest import Manifest
from test_chipbench_rehearsal import rehearse

CELL = "air4-transfer-blk10k.batch10k-serial"
NEW_METRICS = ("ec_lane_fill_share", "ec_calls_per_block",
               "cohort_whole_share", "replica_admit_ms_per_batch")


def test_the_cell_loads_through_the_manifest():
    man = Manifest()
    cell = man.cell(CELL)
    assert cell["chips"] == 1
    config, traffic = man.config(cell["config"]), man.traffic(cell["traffic"])
    assert config["block_tx_count_limit"] == 10000
    assert config["build_chain"][-2:] == ["--block-tx-count-limit", "10000"]
    assert config["config_ini"]["0"]["rpc"]["max_batch"] == "10000"
    assert traffic["kind"] == "closed-batch" and traffic["batch"] == 10000
    assert traffic["senders"] == 1
    # what the 1,000-tx transfer cell is but for the block size: shapes,
    # guarantees and cuts are the same
    base = man.config("air4-transfer")
    for key in ("sm_crypto", "sealers", "consensus", "hosts", "device_nodes",
                "p2p_codec", "guarantees", "accounts", "prefund_balance"):
        assert config[key] == base[key], key
    assert [m["name"] for m in man.end_to_end(CELL)] == [
        "committed_tps", "receipt_p50_ms", "setup_s"]
    mine = {m["name"] for m in man.per_layer(CELL)}
    theirs = {m["name"] for m in man.per_layer("air4-transfer.batch1k-serial")}
    assert mine == theirs and set(NEW_METRICS) <= mine
    entry = next(c for c in man.doc["configs"]
                 if c["name"] == "air4-transfer-blk10k")
    assert len(entry["source"]) <= 200 and entry["source"] == config["source"]


def test_new_metrics_read_nothing_where_the_program_counts_nothing():
    """On the parent the seam has no `deviceLanes` and the edge no
    `cohorts`: the readers return nothing and do not raise."""
    man = Manifest()
    status = {"blockNumber": 3, "crypto": {"ops": {
        "recover": {"deviceItems": 10, "deviceCalls": 1},
        "verify": {"deviceItems": 0, "deviceCalls": 0}}},
        "trace": {"counters": {}, "stages": {}}}
    after = json.loads(json.dumps(status))
    after["blockNumber"] = 5
    after["crypto"]["ops"]["recover"].update(deviceItems=30, deviceCalls=3)
    ev = {"status": {"before": {"0": status, "1": status},
                     "after": {"0": after, "1": after}}}
    got = {m["name"]: m["read"](ev, m["spec"])
           for m in man.per_layer(CELL) if m["name"] in NEW_METRICS}
    assert got == {"ec_lane_fill_share": None, "ec_calls_per_block": 1.0,
                   "cohort_whole_share": None,
                   "replica_admit_ms_per_batch": None}
    for name in NEW_METRICS:
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{name}.json"))


def test_sound_rehearsal_of_the_10k_chain():
    out, err = rehearse(ROOT, CELL, 2**31 + 28, trace=1,
                        extra=("--controls", "1"), seconds=3)
    assert out["rehearsal"] is True and "no_result" in out
    assert out["correct"] is True and out["failed"] == 0, err[-3000:]
    assert out["attempted"] > 0
    assert all(v == [0, 0] for v in out["compared"].values()), out["compared"]
    assert all(out["controls"].values()), out["controls"]
    layers = {k: v["value"] for k, v in out["per_layer"].items()}
    assert layers["cohort_whole_share"] == 100.0
    assert layers["lane_mean_batch"] == layers["txs_per_block"] == 24.0
    assert layers["view_changes_per_100_blocks"] == 0.0
    assert layers["replica_admit_ms_per_batch"] > 0.0
    # host crypto here: nothing was issued to a device, so no share of it
    assert "ec_lane_fill_share" not in layers
