"""The stage and seam metrics of PR 25: the two readers they bring on
hand-made evidence, every metric file through the manifest for both cells,
and the CPU rehearsal printing the span metrics."""

import json

import pytest
from conftest import ROOT
from manifest import Manifest
from test_chipbench_rehearsal import rehearse

CELLS = ("air4-transfer.batch1k-serial", "air4-sm.batch1k-serial")
STAGE_METRICS = {
    "rpc_decode_ms_per_batch", "lane_wait_ms_per_batch",
    "admit_ms_per_batch", "gossip_ms_per_batch", "seal_wait_ms_per_block",
    "consensus_pre_ms_per_block", "consensus_wait_ms_per_block",
    "notify_ms_per_block", "rpc_respond_ms_per_batch",
    "rpc_no_request_ms_per_block", "round_wait_ms_per_block",
    "stage_coverage"}
SEAM_METRICS = {
    "ec_seam_pack_ms_per_call", "ec_seam_call_ms_per_call",
    "ec_seam_unpack_ms_per_call", "hash_seam_ms_per_call",
    "merkle_seam_ms_per_call"}
NEW = STAGE_METRICS | SEAM_METRICS | {"idle_unattributed_share"}


def _status(**seconds):
    return {"trace": {"stages": {k: {"count": 1, "seconds": v}
                                 for k, v in seconds.items()}}}


def test_stage_sum_by_hand():
    read = Manifest().reader("stage_sum")
    spec = {"node": 0, "scale": 100, "stages": ["admit", "execute"]}
    ev = {"window_s": 10.0, "status": {
        "before": {"0": _status(admit=1.0, execute=2.0, roots=5.0)},
        "after": {"0": _status(admit=3.0, execute=5.5, roots=9.0)}}}
    assert read(ev, spec) == pytest.approx(100 * (2.0 + 3.5) / 10.0)
    # a stage that one snapshot lacks (the parent has none) reads as nothing
    assert read(ev, {**spec, "stages": ["admit", "notify"]}) is None
    ev["status"]["before"]["0"] = {"pipeline": {}}
    assert read(ev, spec) is None
    assert read({**ev, "window_s": 0}, spec) is None


def test_trace_gap_share_by_hand():
    read = Manifest().reader("trace_gap_share")
    spec = {"names": "^(unattributed|gaps_not_looked_at)$"}
    trace = {"window_s": 10.0, "busy_s": 2.0, "idle_gaps": [
        ["round_wait", 4.0], ["unattributed", 1.5], ["admit", 1.0],
        ["gaps_not_looked_at", 0.5], ["not_unattributed_at_all", 1.0]]}
    assert read({"trace": trace}, spec) == pytest.approx(100 * 2.0 / 8.0)
    assert read({"trace": {**trace, "idle_gaps": [["admit", 8.0]]}},
                spec) == 0.0
    # nothing without a trace, or where the device never idled
    assert read({"trace": None}, spec) is None
    assert read({}, spec) is None
    assert read({"trace": {**trace, "busy_s": 10.0}}, spec) is None


@pytest.mark.parametrize("cell", CELLS)
def test_new_metrics_load_for_both_cells(cell):
    man = Manifest()
    by_name = {m["name"]: m for m in man.per_layer(cell)}
    assert NEW <= set(by_name)
    for name in NEW:
        m = by_name[name]
        assert callable(m["read"]) and m["spec"]["reader"] in (
            "status_ratio", "stage_sum", "trace_gap_share")
        # a later cell is appended to the list, never put before these
        assert m["workloads"][:2] == list(CELLS)
        # no stage name carries a dot: status_ratio splits paths on them
        for path in m["spec"].get("numerator", []):
            if path.startswith("trace.stages."):
                assert len(path.split(".")) == 4, path
    # a status without the stages (the parent's) reads as nothing, quietly
    old = {"blockNumber": 3, "pipeline": {"stages": {}}, "crypto": {"ops": {
        op: {"deviceCalls": 2, "deviceItems": 9} for op in
        ("recover", "verify", "hash", "merkle")}}, "trace": {"ring_size": 1}}
    ev = {"window_s": 5.0, "trace": None,
          "status": {"before": {"0": old}, "after": {"0": old}}}
    for name in NEW:
        assert by_name[name]["read"](ev, by_name[name]["spec"]) is None


def test_rehearsal_prints_the_span_metrics():
    out, err = rehearse(ROOT, CELLS[0], 2**31 + 25, trace=1)
    assert out["correct"] is True, err[-3000:]
    layers = out["per_layer"]
    # seal_wait needs a block node0 led; the seam's seconds need a device
    # call, and the rehearsal's crypto runs on the host
    expected = STAGE_METRICS - {"seal_wait_ms_per_block"}
    assert expected <= set(layers), sorted(expected - set(layers))
    assert not SEAM_METRICS & set(layers)
    assert "idle_unattributed_share" not in layers
    assert all(layers[k]["value"] >= 0 for k in expected)
    assert 0 < layers["stage_coverage"]["value"] < 150
    line = next(ln for ln in err.splitlines()
                if ln.startswith("chipbench: layers "))
    assert expected <= set(json.loads(line.split("layers ", 1)[1]))
