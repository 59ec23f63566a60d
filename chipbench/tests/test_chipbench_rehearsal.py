"""The whole control flow at a tiny size on the CPU: a sound run is
correct and says it is no result; with the timed path broken underneath,
`correct` comes out false, once for each fault a cell can have."""

import json
import os
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT, checkout_with_kept_cells

FAULTY = os.path.join(BENCH, "tests", "faulty_node.py")
LISTED = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.fixture(scope="module")
def kept(tmp_path_factory):
    return checkout_with_kept_cells(tmp_path_factory.mktemp("kept"))


def rehearse(root, cell, seed, trace=0, fault=None, extra=(), seconds=3):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(root, "chipbench", "run.py"),
           "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace), "--rehearse-cpu", *extra]
    if fault:
        env["CHIPBENCH_FAULT"] = fault
        cmd += ["--node-launcher", FAULTY]
    p = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


@pytest.mark.parametrize("cell,trace", [
    ("air4-transfer.batch1k-serial", 1),
    ("air4-transfer.batch1k-backlog", 0),
    ("air4-sm.batch1k-backlog", 0),
    ("air4-transfer.singles-halfknee", 1),
])
def test_sound_rehearsal(kept, cell, trace):
    # the listed cell runs from the checkout itself, the kept ones from the
    # copy that lists them. SM2 on the host costs ~13 ms of CPU a
    # transaction at these tiny blocks: its batches need a longer window
    out, err = rehearse(ROOT if cell in LISTED else kept, cell, 2**31 + 11,
                        trace, extra=("--controls", "1"),
                        seconds=8 if "-sm." in cell else 3)
    assert out["rehearsal"] is True and "no_result" in out
    assert "metrics" not in out and "device" not in out
    assert out["correct"] is True and out["failed"] == 0, err[-3000:]
    assert out["attempted"] > 0 and out["client"]["committed_tps"] > 0
    assert not any("roofline" in k or "idle" in k for k in out["per_layer"])
    assert all(out["controls"].values()), out["controls"]
    # each number compared is printed beside its limit
    assert "compared balances_off_replay: 0 (limit 0)" in err


@pytest.mark.parametrize("cell,fault,number", [
    ("air4-transfer.batch1k-serial", "state_unchanged",
     "balances_off_replay"),
    ("air4-transfer.batch1k-serial", "answer_altered",
     "balances_off_replay"),
    # only a traffic that sends JSON-RPC batches can lose half of one
    ("air4-transfer.batch1k-serial", "half_batch", "acked_not_in_chain"),
    ("air4-transfer.singles-halfknee", "state_unchanged",
     "balances_off_replay"),
    ("air4-transfer.singles-halfknee", "answer_altered",
     "balances_off_replay"),
])
def test_broken_timed_path_is_not_correct(kept, cell, fault, number):
    out, _err = rehearse(ROOT if cell in LISTED else kept, cell, 5,
                         fault=fault)
    assert out["correct"] is False
    value, limit = out["compared"][number]
    assert value > limit


def test_no_checkout_no_result(tmp_path):
    """BENCHMARK.json and chipbench/ alone: non-zero, nothing on stdout."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         LISTED[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
