"""The reduction from a profiler trace to busy time, programs and idle
gaps: on a trace made by hand, whose numbers can be worked out on paper,
and on the head of a trace recorded on the chip (tests/data)."""

import json
import os

import pytest
import trace_reduce
from conftest import BENCH

MS = 1_000_000


def _trace():
    ops = [["fusion.1", 10 * MS, 2 * MS], ["fusion.2", 11 * MS, 3 * MS],
           ["copy.3", 30 * MS, 1 * MS]]
    mods = [["jit_keccak256_varlen_fused(123)", 10 * MS, 4 * MS],
            ["jit__merkle_root_bucketed(77)", 30 * MS, 1 * MS],
            ["jit_keccak256_varlen_fused(123)", 60 * MS, 0]]
    host = [["np.asarray(jax.Array)", 14 * MS, 15 * MS],
            ["tiny", 0, MS // 2]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/device:TPU:0 custom", "lines": [
            {"name": "XLA Ops", "events": [["x", 0, 50 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "t1", "events": host}]}]}


def test_reduce_by_hand():
    r = trace_reduce.reduce(_trace())
    # ops: [10,14) after merging [10,12) and [11,14), and [30,31): 5 ms
    assert r["busy_s"] == pytest.approx(0.005)
    assert r["devices"] == 1
    # the span runs from the first event (0) to the last end (60 ms)
    assert r["span_s"] == pytest.approx(0.060)
    assert r["programs"] == {"jit_keccak256_varlen_fused": 0.004,
                             "jit__merkle_root_bucketed": 0.001}
    assert r["device_ops"][0] == ["jit_keccak256_varlen_fused", 0.004]
    gaps = dict(r["idle_gaps"])
    # [14,30) lies under the host's np.asarray span for 15 of its 16 ms;
    # [0,10) has half a ms of `tiny` and [31,60) nothing: both unattributed
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(0.016)
    assert gaps["unattributed"] == pytest.approx(0.010 + 0.029)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["span_s"])


def test_no_device_plane_reads_as_nothing():
    t = _trace()
    t["planes"] = t["planes"][2:]
    assert trace_reduce.reduce(t) is None
    assert trace_reduce.reduce({"planes": []}) is None


def test_program_name():
    assert trace_reduce.program_name("jit_ecdsa_recover_batch(9876543)") \
        == "jit_ecdsa_recover_batch"
    assert trace_reduce.program_name("plain") == "plain"


def test_recorded_trace():
    """The head of every line of a trace node0 wrote on the v5e."""
    path = os.path.join(BENCH, "tests", "data", "small_trace.json")
    doc = json.load(open(path))
    r = trace_reduce.reduce(doc["trace"])
    want = doc["expected"]
    assert r["devices"] == want["devices"]
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["programs"] == pytest.approx(want["programs"])
    assert 0 < r["busy_s"] < r["span_s"]
    assert [n for n, _s in r["device_ops"]] == want["device_ops_order"]
