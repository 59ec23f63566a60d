"""The harness's own ceiling: how many batches a closed-batch cell signs
ahead for its window, what happens when a window outruns them, and the
gauge that says how much of them a run took (`presign_used_share`)."""

import json
import os
import subprocess
import sys
import time

import pytest
import traffic as traffic_mod
from conftest import BENCH, ROOT
from manifest import Manifest

MAN = Manifest()
RUN_SECONDS = MAN.doc["run_seconds"]
CELLS = list(MAN.cells)
CLOSED = sorted({w["traffic"] for w in MAN.doc["workloads"]
                 if MAN.traffic(w["traffic"])["kind"] == "closed-batch"})
# the least a listed cell signs for its window: lowering a traffic file's
# presign_tx_per_s brings the ceiling back down on the chain (PERF.md,
# section 3, `presign_used_share`)
LEAST_BATCHES = {1000: 200, 10000: 30}


class _Cluster:
    group = "group0"

    def port(self, _k):
        return 0


class _Maker:
    def make(self, i, block_limit):
        return f"0x{i:x}", f"0x{i:064x}", (b"a", b"b", 1)


class _Rpc:
    """Answers every batch at once with a receipt each."""

    def __init__(self, *_a):
        self.height = 0

    def batch(self, calls):
        self.height += 1
        return [{"result": {"status": 0, "blockNumber": self.height}}
                for _ in calls]

    def close(self):
        pass


def _load(p: dict, seconds: float):
    return traffic_mod.make_load(p, _Cluster(), _Maker(), 7, seconds)


@pytest.mark.parametrize("name", CLOSED)
def test_presign_signs_the_windows_batches_and_the_warm_up(name):
    p = MAN.traffic(name)
    load = _load(p, RUN_SECONDS)
    load.presign()
    window = -(-int(p["presign_tx_per_s"] * RUN_SECONDS) // p["batch"])
    warm = p["senders"] * p["warmup_batches_per_sender"]
    assert len(load.requests) == (window + warm) * p["batch"]
    assert (load.window_signed, load.window_taken) == (window, 0)
    assert window >= LEAST_BATCHES[p["batch"]], (
        f"traffic/{name}.json signs {window} batches of {p['batch']} for a "
        f"{RUN_SECONDS} s window")


def _warmed_small_load(monkeypatch):
    """5 batches of 10 signed for a 0.5 s window, the 2 of warm-up sent."""
    monkeypatch.setattr(traffic_mod, "Rpc", _Rpc)
    p = dict(MAN.traffic("batch1k-serial"), batch=10, presign_tx_per_s=100)
    load = _load(p, 0.5)
    load.presign()
    load.warm_up()
    assert (load.window_signed, load.window_taken) == (5, 0)
    assert not load.exhausted
    return load


def test_a_window_that_outruns_the_signed_batches_is_exhausted(monkeypatch):
    load = _warmed_small_load(monkeypatch)
    load.run(time.monotonic())
    assert load.exhausted
    assert (load.window_signed, load.window_taken) == (5, 5)
    assert [r.measured for r in load.requests] == [False] * 20 + [True] * 50


def test_a_window_with_room_left_counts_what_it_took(monkeypatch):
    load = _warmed_small_load(monkeypatch)
    n = iter(range(100))
    monkeypatch.setattr(load, "_threads", lambda measured, until, rounds:
                        load._loop(measured, lambda: next(n) >= 3, rounds))
    load.run(time.monotonic())
    assert not load.exhausted
    assert (load.window_signed, load.window_taken) == (5, 3)


def test_a_loop_that_signs_as_it_goes_has_no_counts():
    load = _load(MAN.traffic("singles-halfknee"), 1.0)
    load.presign()
    assert load.window_signed is None and load.window_taken is None


def test_presign_share_by_hand():
    read = MAN.reader("presign_share")
    assert read({"presign_batches": {"signed": 204, "taken": 102}}, {}) == 50.0
    assert read({"presign_batches": {"signed": 30, "taken": 30}}, {}) == 100.0
    assert read({"presign_batches": {"signed": 204, "taken": 0}}, {}) == 0.0
    # a traffic kind that signs as it goes, or evidence without the counts
    assert read({"presign_batches": {"signed": None, "taken": None}},
                {}) is None
    assert read({}, {}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_two_metrics_load_for_every_cell(cell):
    by_name = {m["name"]: m for m in MAN.per_layer(cell)}
    gauge, crypto = (by_name["presign_used_share"],
                     by_name["replica_crypto_ms_per_batch"])
    assert gauge["spec"]["reader"] == "presign_share"
    assert (gauge["unit"], gauge["layer"], gauge["moves"]) == (
        "%", "client", "committed_tps")
    assert gauge["workloads"] == CELLS and crypto["workloads"] == CELLS
    assert crypto["spec"]["reader"] == "status_ratio"
    assert crypto["spec"]["node"] == 1 and crypto["moves"] == "receipt_p50_ms"
    # the replica's crypto stage, read as the window's delta of node1
    stages = {"crypto": {"count": 3, "seconds": 0.3},
              "admit": {"count": 3, "seconds": 0.6}}
    after = {"crypto": {"count": 7, "seconds": 0.74},
             "admit": {"count": 7, "seconds": 1.2}}
    ev = {"status": {"before": {"1": {"trace": {"stages": stages}}},
                     "after": {"1": {"trace": {"stages": after}}}}}
    assert crypto["read"](ev, crypto["spec"]) == pytest.approx(110.0)
    assert by_name["replica_admit_ms_per_batch"]["read"](
        ev, by_name["replica_admit_ms_per_batch"]["spec"]) \
        == pytest.approx(150.0)
    ev["status"]["before"]["1"] = {"trace": {"stages": {}}}
    assert crypto["read"](ev, crypto["spec"]) is None


def test_run_fails_with_no_result_when_the_senders_run_out():
    """The rehearsal with 3 batches signed for a 3 s window: the chain
    takes them in well under a second, so the run is no result."""
    script = (
        "import sys; sys.path.insert(0, sys.argv.pop(1)); import run; "
        "run.REHEARSAL['traffic']['presign_tx_per_s'] = 20; "
        "sys.exit(run.main())")
    p = subprocess.run(
        [sys.executable, "-c", script, BENCH, "--workload", CELLS[0],
         "--seed", "29", "--seconds", "3", "--trace", "0", "--rehearse-cpu"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 1, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "the senders ran out of signed transactions" in p.stderr


def test_rehearsal_reports_the_gauge():
    from test_chipbench_rehearsal import rehearse
    out, err = rehearse(ROOT, CELLS[0], 2**31 + 29, trace=1)
    assert out["correct"] is True, err[-3000:]
    layers = out["per_layer"]
    # 2,500 tx/s x 3 s in batches of 24: 313 signed for the window
    share = layers["presign_used_share"]["value"]
    batches = out["attempted"] // 24
    assert share == pytest.approx(100.0 * batches / 313)
    assert 0 < share < 100
    assert layers["replica_crypto_ms_per_batch"]["value"] > 0
    assert layers["replica_crypto_ms_per_batch"]["value"] \
        < layers["replica_admit_ms_per_batch"]["value"]
    line = next(ln for ln in err.splitlines()
                if ln.startswith("chipbench: layers "))
    assert "presign_used_share" in json.loads(line.split("layers ", 1)[1])
