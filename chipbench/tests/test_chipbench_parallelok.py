"""The `parallelok` kind and its cell, `air4-parallelok.batch1k-serial`
(PR 37): every listed configuration's kind loads, the CPU rehearsal of the
cell is `correct` with every compared number 0, and each control of the
chain and of the kind fails at least one count."""

import json
import os
import subprocess
import sys

import pytest
from conftest import ROOT

import reference
from manifest import Manifest, workload

CELL = "air4-parallelok.batch1k-serial"
MAN = Manifest()
CONFIGS = [c["name"] for c in MAN.doc["configs"]]


@pytest.mark.parametrize("name", CONFIGS)
def test_every_listed_configurations_kind_loads(name):
    config = MAN.config(name)
    MAN.workload(config)
    client, ref = workload(config), workload(config, "reference")
    for fn in ("prefund", "op", "call", "touched", "read_call", "decode"):
        assert callable(getattr(client, fn)), fn
    assert callable(ref.expected) and callable(ref.receipt_says)
    assert ref.CONTROLS and all(map(callable, ref.CONTROLS.values()))
    move = client.op(config, 2**31 + 37, 5)
    assert client.op(config, 2**31 + 37, 5) == move  # from the seed
    assert set(client.touched(move)) <= set(move)


def test_the_cell_is_listed_as_the_issue_gives_it():
    cell = MAN.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "air4-parallelok", "batch1k-serial", 1)
    config = MAN.config("air4-parallelok")
    base = MAN.config("air4-transfer")
    assert config["workload"] == "parallelok"
    for key in ("accounts", "prefund_balance", "build_chain", "config_ini",
                "sm_crypto", "block_tx_count_limit", "reduced_why"):
        assert config[key] == base[key], key
    names = {m["name"] for m in MAN.per_layer(CELL)}
    assert {"dag_plan_ms_per_block", "dag_txs_per_wave", "dag_pooled_share",
            "evm_native_share", "recover_roofline", "merkle_roofline"} <= names
    assert "sm2_verify_roofline" not in names
    assert not any(n.startswith(("page_", "storage_", "flush"))
                   for n in names)


def test_the_kind_refuses_an_sm_chain():
    kind = workload(MAN.config("air4-parallelok"))
    with pytest.raises(ValueError, match="SM"):
        kind.prefund(None, {"sm_crypto": True})


def test_rehearsal_is_correct_and_every_control_fails():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 3701), "--seconds", "3", "--trace", "1",
         "--rehearse-cpu", "--controls", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0, p.stderr[-3000:]
    assert out["attempted"] > 0
    assert all(v == [0, 0] for v in out["compared"].values()), out["compared"]
    assert list(out["controls"]) == [
        *reference.FRAME_CONTROLS, "lost_update", "reported_reverted",
        "receipt_with_output"]
    assert all(out["controls"].values()), out["controls"]
    assert out["controls"]["lost_update"] == ["balances_off_replay"]
    layers = {k: v["value"] for k, v in out["per_layer"].items()}
    assert layers["evm_native_share"] == 100.0
    assert layers["dag_txs_per_wave"] > 1
    assert layers["dag_plan_ms_per_block"] > 0
    assert 0 <= layers["dag_pooled_share"] <= 100
