"""`replica_ec_split_share`: how much of a host replica's signature
checking the host door issued as more than one native call (PERF.md,
section 3, crypto seam)."""

import pytest
from manifest import Manifest

MAN = Manifest()
CELLS = list(MAN.cells)


def _status(recover, verify):
    return {"1": {"crypto": {"ops": {"recover": recover, "verify": verify}}}}


@pytest.mark.parametrize("cell", CELLS)
def test_the_split_share_loads_for_every_cell(cell):
    m = {m["name"]: m for m in MAN.per_layer(cell)}["replica_ec_split_share"]
    assert m["spec"]["reader"] == "status_ratio" and m["spec"]["node"] == 1
    assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
        "%", "higher", "crypto seam", "receipt_p50_ms")
    assert m["workloads"] == CELLS
    small = {"hostItems": 7, "hostSplitItems": 0}
    ev = {"status": {
        "before": _status({"hostItems": 5000, "hostSplitItems": 5000}, small),
        "after": _status({"hostItems": 6000, "hostSplitItems": 6000}, small)}}
    assert m["read"](ev, m["spec"]) == 100.0
    # PBFT's checks of a few signatures ride beside the cohorts, unsplit
    ev["status"]["after"] = _status(
        {"hostItems": 6000, "hostSplitItems": 6000},
        {"hostItems": 47, "hostSplitItems": 0})
    assert m["read"](ev, m["spec"]) == pytest.approx(100 * 1000 / 1040)
    # the parent's status has no such counter: nothing, and no raise
    ev["status"]["before"] = _status({"hostItems": 5000}, {"hostItems": 7})
    ev["status"]["after"] = _status({"hostItems": 6000}, {"hostItems": 47})
    assert m["read"](ev, m["spec"]) is None


def test_rehearsal_reports_no_split_for_cohorts_of_one_chunk():
    """The CPU rehearsal's cohorts of 24 fit one native chunk: the metric
    is in the traced line, and the host door split none of them."""
    from conftest import ROOT
    from test_chipbench_rehearsal import rehearse
    out, err = rehearse(ROOT, CELLS[0], 2**31 + 31, trace=1)
    assert out["correct"] is True, err[-3000:]
    assert out["per_layer"]["replica_ec_split_share"]["value"] == 0.0
