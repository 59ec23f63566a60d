"""The two per-layer metrics the shared-fragment prime brings
(`prime_ms_per_block`, `respond_shared_share`): their data files, read
through the benchmark's own `status_ratio` reader from a solo node's
getSystemStatus around one committed cohort block."""

import importlib.util
import json
import os
import sys

import pytest

from test_rpc_batch import COHORT, cohort_node, cohort_txs, send_cohort, \
    wait_until

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
CELLS = ["air4-transfer.batch1k-serial", "air4-sm.batch1k-serial"]
METRICS = {
    "prime_ms_per_block": ("ms", "lower", "program_span"),
    "respond_shared_share": ("%", "higher", "program_counter"),
}


@pytest.fixture(scope="module")
def status_ratio():
    sys.path.insert(0, BENCH)  # the reader imports readers_util
    try:
        spec = importlib.util.spec_from_file_location(
            "chipbench_status_ratio",
            os.path.join(BENCH, "readers", "status_ratio.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        yield mod.read
    finally:
        sys.path.remove(BENCH)


def _spec(name: str) -> dict:
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_is_listed_for_both_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = next(m for m in doc["per_layer"] if m["name"] == name)
    unit, better, source = METRICS[name]
    # a later cell is appended to the list, never put before these
    assert entry["workloads"][:len(CELLS)] == CELLS
    assert dict(entry, workloads=CELLS) == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": "RPC edge", "moves": "receipt_p50_ms", "workloads": CELLS}
    spec = _spec(name)
    assert spec["reader"] == "status_ratio" and spec["node"] == 0


@pytest.mark.parametrize("sm", [False, True], ids=["secp", "sm"])
def test_both_specs_read_a_number_from_one_committed_block(sm, status_ratio):
    node, kp, _impl = cohort_node(sm)
    try:
        primes = lambda: node.system_status()[  # noqa: E731
            "trace"]["stages"]["prime"]["count"]
        assert wait_until(lambda: primes() >= 1)  # the funding block's
        before = node.rpc.impl.get_system_status("group0")
        send_cohort(node, cohort_txs(node, kp, "metric"))
        count = before["trace"]["stages"]["prime"]["count"]
        assert wait_until(lambda: primes() > count)
        after = node.rpc.impl.get_system_status("group0")
        ev = {"status": {"before": {"0": before}, "after": {"0": after}}}
        prime_ms = status_ratio(ev, _spec("prime_ms_per_block"))
        assert isinstance(prime_ms, float) and 0.0 < prime_ms < 5000.0
        assert status_ratio(ev, _spec("respond_shared_share")) == 100.0
        counters = after["trace"]["counters"]
        assert counters["cohort_receipts"] == COHORT
        # a program without the stage and the counters (the parent) reads
        # as nothing, and does not raise
        for doc in (before, after):
            del doc["trace"]["counters"], doc["trace"]["stages"]["prime"]
        assert status_ratio(ev, _spec("prime_ms_per_block")) is None
        assert status_ratio(ev, _spec("respond_shared_share")) is None
    finally:
        node.stop()


SIX_CELLS = CELLS + ["air4-transfer-blk10k.batch10k-serial",
                     "air4-transfer-disk.batch1k-serial",
                     "air4-parallelok.batch1k-serial",
                     "air4-smallbank.batch1k-serial"]


def test_onepass_share_is_listed_for_the_six_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    assert [w["name"] for w in doc["workloads"]][:6] == SIX_CELLS
    entry = next(m for m in doc["per_layer"]
                 if m["name"] == "respond_onepass_share")
    assert entry == {
        "name": "respond_onepass_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "RPC edge",
        "moves": "receipt_p50_ms", "workloads": SIX_CELLS}
    spec = _spec("respond_onepass_share")
    assert spec["reader"] == "status_ratio" and spec["node"] == 0
    assert spec["scale"] == 100
    assert spec["numerator"] == ["trace.counters.cohort_receipts_onepass"]
    assert spec["denominator"] == ["trace.counters.cohort_receipts"]


@pytest.mark.parametrize("sm", [False, True], ids=["secp", "sm"])
def test_onepass_share_reads_a_number_from_one_committed_block(
        sm, status_ratio):
    node, kp, _impl = cohort_node(sm)
    try:
        before = node.rpc.impl.get_system_status("group0")
        send_cohort(node, cohort_txs(node, kp, "onepass-metric"))
        after = node.rpc.impl.get_system_status("group0")
        ev = {"status": {"before": {"0": before}, "after": {"0": after}}}
        spec = _spec("respond_onepass_share")
        assert status_ratio(ev, spec) == 100.0
        assert after["trace"]["counters"]["cohort_receipts_onepass"] \
            - before["trace"]["counters"]["cohort_receipts_onepass"] \
            == COHORT
        # a program without the counter (the parent) reads as nothing
        for doc in (before, after):
            del doc["trace"]["counters"]["cohort_receipts_onepass"]
        assert status_ratio(ev, spec) is None
    finally:
        node.stop()
