"""The `smallbank` kind and its cell, `air4-smallbank.batch1k-serial`:
the configuration keeps the source's shape, the CPU rehearsal of the cell
is `correct` with every compared number 0 and every control of the chain
and of the kind failing a count, and a chain whose SendPayment skips its
refusal reads `correct` false. That `smallbank_reference.py` imports
nothing is `test_chipbench_workload.py`'s, which reads every
`*_reference.py`."""

import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

import reference
from manifest import Manifest, workload

CELL = "air4-smallbank.batch1k-serial"
MAN = Manifest()
FAULTY = os.path.join(BENCH, "tests", "smallbank_faulty_node.py")


def test_the_cell_and_its_configuration_are_the_issues():
    cell = MAN.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "air4-smallbank", "batch1k-serial", 1)
    config = MAN.config("air4-smallbank")
    base = MAN.config("air4-transfer")
    assert config["workload"] == "smallbank"
    for key in ("build_chain", "config_ini", "sm_crypto", "sealers",
                "consensus", "block_tx_count_limit", "reduced_why"):
        assert config[key] == base[key], key
    assert {k: v for k, v in config["guarantees"].items()
            if k != "answers"} == {k: v for k, v in base["guarantees"].items()
                                   if k != "answers"}
    # the source's shape: the OLTPBench mix and amounts in cents, the hot
    # set, 1,000,000 customers
    assert config["mix"] == {"amalgamate": 15, "getBalance": 15,
                             "updateBalance": 15, "sendPayment": 25,
                             "updateSaving": 15, "writeCheck": 15}
    assert config["amounts_cents"] == {"sendPayment": 500,
                                       "updateBalance": 130,
                                       "updateSaving": 2020,
                                       "writeCheck": 500}
    assert (config["hot_accounts"], config["hot_share_pct"],
            config["accounts"], config["penalty_cents"]) == (
        100, 90, 1_000_000, 100)
    for key in ("address", "tables", "methods", "cents", "penalty_cents",
                "balance_as_transaction", "hot_set", "balance_draw",
                "read_back"):
        assert key in config["assumed"], key
    names = {m["name"] for m in MAN.per_layer(CELL)}
    assert {"dag_plan_ms_per_block", "dag_txs_per_wave", "recover_roofline",
            "merkle_roofline", "smallbank_refused_share",
            "state_leaves_per_tx"} <= names
    assert not names & {"dag_pooled_share", "evm_native_share",
                        "sm2_verify_roofline"}
    assert not any(n.startswith(("page_", "storage_", "flush"))
                   for n in names)


def test_the_draw_is_skewed_and_never_names_one_customer_twice():
    config = MAN.config("air4-smallbank")
    kind = workload(config)
    ops = [kind.op(config, 2**31 + 4207, i) for i in range(20000)]
    names = [n for op in ops for n in op[1:3] if n]
    hot = sum(int(n[3:]) < 100 for n in names) / len(names)
    assert 0.88 < hot < 0.92
    assert all(op[1] != op[2] for op in ops)
    share = {m: sum(op[0] == m for op in ops) / len(ops)
             for m in config["mix"]}
    assert all(abs(share[m] - w / 100) < 0.015
               for m, w in config["mix"].items()), share


def _rehearse(seed, *extra):
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "3", "--trace", "1", "--rehearse-cpu",
         *extra],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_rehearsal_is_correct_and_every_control_fails():
    out, err = _rehearse(2**31 + 4211, "--controls", "1")
    assert out["correct"] is True and out["failed"] == 0, err[-3000:]
    assert out["attempted"] > 0
    assert all(v == [0, 0] for v in out["compared"].values()), out["compared"]
    assert list(out["controls"]) == [
        *reference.FRAME_CONTROLS, "refusal_ignored", "stale_balance",
        "lost_saving", "overdraft_floored"]
    assert all(out["controls"].values()), out["controls"]
    assert "receipts_wrong" in out["controls"]["refusal_ignored"]
    layers = {k: v["value"] for k, v in out["per_layer"].items()}
    assert 0 < layers["smallbank_refused_share"] < 50
    assert layers["state_leaves_per_tx"] > 0
    assert 1 < layers["dag_txs_per_wave"] < 24


def test_a_payment_that_skips_its_refusal_reads_incorrect():
    out, err = _rehearse(2**31 + 4211, "--node-launcher", FAULTY)
    assert out["correct"] is False, err[-3000:]
    assert out["compared"]["receipts_wrong"][0] > 0
