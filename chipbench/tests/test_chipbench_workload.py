"""A transaction kind is a pair of files under `workloads/`, found by the
name a configuration gives: the four listed configurations send and
compare through the door what they sent and compared before it was cut
(digests taken from the parent's code), and a second kind is added to a
copy of the tree as files and entries alone."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT

import answers
import reference
import txgen
from manifest import Manifest, ManifestError
from test_chipbench_reference import CONFIG, _world

DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIGS = [c["name"] for c in DOC["configs"]]
SEEDS = (7, 2147484229)
DATA = os.path.join(BENCH, "tests", "data", "workloads")


def _digest(obj) -> str:
    return hashlib.sha256(repr(obj).encode()).hexdigest()[:16]


# -- (a) golden identity -------------------------------------------------------

class _FakeRpc:
    """Answers in the shape the nodes answer, says nothing true, and keeps
    every call it was asked."""

    def __init__(self, asked: list):
        self.asked = asked

    def _one(self, method: str, params: list):
        self.asked.append((method, params))
        if method == "getBlockNumber":
            return 2
        if method == "getBlockHashByNumber":
            return "0x%064x" % params[2]
        if method == "getBlockByNumber":
            return {"number": params[2], "hash": "0x%064x" % params[2],
                    "transactions": [], "signatureList": [1, 2, 3]}
        if method == "getTransactionReceipt":
            return {"transactionHash": params[2]}
        return {"output": "0x%016x" % len(self.asked)}

    def call(self, method, params):
        return self._one(method, params)

    def results(self, calls, chunk=256):
        return [self._one(m, p) for m, p in calls]

    def close(self):
        pass


class _FakeCluster:
    group = "group0"
    procs = [None] * 4

    def __init__(self):
        self.asked = {k: [] for k in range(4)}

    def rpc(self, k, timeout=0.0):
        return _FakeRpc(self.asked[k])


def _sent_digest(config: dict, seed: int) -> str:
    """What requests 0-255 of a seed are: operation and transaction hash,
    and the wire bytes where the signer is deterministic (secp256k1)."""
    maker = txgen.TxMaker(config, seed)
    made = [maker.make(i, 500) for i in range(256)]
    assert [maker.move(i) for i in range(256)] == [m[2] for m in made]
    return _digest([(mv, h, None if config["sm_crypto"] else wire)
                    for wire, h, mv in made])


def _gather_digest(config: dict, seed: int) -> str:
    """What `answers.gather` asks the nodes for after a window of 3,000
    requests, two never acknowledged: every call of every node in order
    (the sampled receipt hashes, the sampled keys' read-back calls) and
    what it makes of the answers."""
    maker = txgen.TxMaker(config, seed)
    sent = [{"hash": "0x%064x" % (i + 1), "move": maker.move(i),
             "receipt": None if i in (11, 2999) else {"blockNumber": 1}}
            for i in range(3000)]
    cluster = _FakeCluster()
    got = answers.gather(cluster, maker, sent, seed)
    assert len(got["receipts"]) in (answers.SAMPLE_RECEIPTS,
                                    answers.SAMPLE_RECEIPTS + 1)
    assert all(len(v) == answers.SAMPLE_ACCOUNTS
               for v in got["balances"].values())
    return _digest((cluster.asked, got))


# (sent digest, gather digest) as the parent's txgen.py, answers.py and
# reference.py gave them (commit 1f8d61f, before the move)
GOLDEN = {
    ("air4-transfer", 7): ("9c7a1377fda09f4f", "80e950579ee7790b"),
    ("air4-transfer", 2147484229): ("bee65649b32543e0", "41f23c293ad3fed7"),
    ("air4-sm", 7): ("d7a441ce0232eb2f", "80e950579ee7790b"),
    ("air4-sm", 2147484229): ("2fd8a80722d9e7b1", "41f23c293ad3fed7"),
    ("air4-transfer-blk10k", 7): ("9c7a1377fda09f4f", "80e950579ee7790b"),
    ("air4-transfer-blk10k", 2147484229): ("bee65649b32543e0",
                                           "41f23c293ad3fed7"),
    ("air4-transfer-disk", 7): ("1fb02ec775673f3f", "bd2f003096026904"),
    ("air4-transfer-disk", 2147484229): ("ec9dcc58f8e8f97e",
                                         "d0161686ec3a013f"),
}
NUMBERS = ["never_answered", "receipts_wrong", "acked_not_in_chain",
           "chain_txs_twice_or_foreign", "blocks_over_tx_limit",
           "blocks_under_quorum_seals", "heights_replicas_differ",
           "receipts_under_quorum_reads", "balances_off_replay"]
# judge's nine values on the canned answers with each control applied, and
# with a transaction held twice: the parent's
GOLDEN_JUDGED = {
    "lost_acknowledged_write": [0, 0, 1, 0, 0, 0, 0, 0, 4],
    "replica_diverged": [0, 0, 0, 0, 0, 0, 1, 0, 0],
    "read_from_two_only": [0, 0, 0, 0, 0, 0, 0, 1, 0],
    "one_seal_short": [0, 0, 0, 0, 0, 1, 0, 0, 0],
    "lost_update": [0, 0, 0, 0, 0, 0, 0, 0, 1],
    "wrong_receipt": [0, 1, 0, 0, 0, 0, 0, 0, 0],
    "held_twice": [0, 0, 0, 1, 0, 0, 0, 0, 0],
}
# a transfer that the replay refuses, acknowledged as done: the parent
# counted it under balances_off_replay ([0, 0, 0, 0, 0, 0, 0, 1, 4]); the
# kind's own receipt verdict now says the receipt is wrong. No seed of the
# four configurations overdraws (1,000,000 prefunded, amounts 1-7).
OVERDRAWN = [0, 1, 0, 0, 0, 0, 0, 1, 3]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", SEEDS)
def test_requests_and_samples_are_the_parents(name, seed):
    config = Manifest().config(name)
    assert (_sent_digest(config, seed),
            _gather_digest(config, seed)) == GOLDEN[name, seed]


def test_judge_and_controls_count_what_the_parent_counted():
    sent, ans = _world()
    assert reference.judge(CONFIG, sent, ans) == [
        {"name": n, "value": 0, "limit": 0} for n in NUMBERS]
    assert list(reference.CONTROLS) == list(GOLDEN_JUDGED)[:6]
    assert reference.run_controls(CONFIG, sent, ans) == {
        name: [n for n, v in zip(NUMBERS, GOLDEN_JUDGED[name]) if v]
        for name in reference.CONTROLS}
    judged = {}
    for name, breaker in reference.CONTROLS.items():
        sent, ans = _world()
        breaker(sent, ans)
        judged[name] = reference.judge(CONFIG, sent, ans)
    sent, ans = _world()
    ans["blocks"][1]["tx_hashes"].append(sent[1]["hash"])
    judged["held_twice"] = reference.judge(CONFIG, sent, ans)
    assert {k: [x["value"] for x in v]
            for k, v in judged.items()} == GOLDEN_JUDGED


def test_a_transfer_the_replay_refuses_is_a_wrong_receipt():
    sent, ans = _world()
    sent[0]["move"] = (b"a", b"b", 500)
    sent[0]["receipt"]["logEntries"][0]["data"] = reference.transfer_log(
        sent[0]["move"])
    assert [x["value"] for x in reference.judge(CONFIG, sent, ans)] \
        == OVERDRAWN
    assert sent[0]["hash"] not in reference.receipts_right(CONFIG, sent, ans)
    # ... and so is the refusal itself: this kind's traffic draws none
    sent[0]["receipt"] = dict(sent[0]["receipt"], status=16, logEntries=[])
    assert [x["value"] for x in reference.judge(CONFIG, sent, ans)] \
        == OVERDRAWN


# -- (b) a second kind is files and entries alone ------------------------------

CELL = "air4-dagadd.batch1k-serial"


def _tree_digests(top) -> dict:
    out = {}
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def with_dagadd(tmp_path_factory):
    """A checkout as a later PR would leave it: chipbench/ copied, the
    kind's two files and its configuration added, BENCHMARK.json with one
    more configuration, one more cell and that cell's name in the metrics
    its traffic's sibling reports."""
    root = tmp_path_factory.mktemp("dagadd") / "checkout"
    bench = root / "chipbench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _tree_digests(bench)
    for name in ("fisco_bcos_tpu", "tools", "native"):
        os.symlink(os.path.join(ROOT, name), root / name)
    for name in ("dagadd.py", "dagadd_reference.py"):
        shutil.copy(os.path.join(DATA, name), bench / "workloads" / name)
    shutil.copy(os.path.join(DATA, "air4-dagadd.json"), bench / "configs")
    doc = json.loads(json.dumps(DOC))
    doc["configs"].append({
        "name": "air4-dagadd", "source": "see the file",
        "file": "chipbench/configs/air4-dagadd.json",
        "reduced": ["hosts", "device_nodes", "p2p_codec"], "why": "test"})
    doc["workloads"].append({
        "name": CELL, "config": "air4-dagadd", "traffic": "batch1k-serial",
        "chips": 1, "why": "test"})
    for m in doc["per_layer"]:
        if "air4-transfer.batch1k-serial" in m["workloads"]:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    after = _tree_digests(bench)
    assert {k: after[k] for k in before} == before  # no file edited
    assert sorted(set(after) - set(before)) == [
        "configs/air4-dagadd.json", "workloads/dagadd.py",
        "workloads/dagadd_reference.py"]
    return str(root)


def _rehearse(root, seed, extra=()):
    p = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "3",
         "--trace", "0", "--rehearse-cpu", *extra],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_a_second_kind_runs_as_added_files(with_dagadd):
    out, err = _rehearse(with_dagadd, 2**31 + 36, ("--controls", "1"))
    assert out["correct"] is True and out["failed"] == 0, err[-3000:]
    assert out["attempted"] > 0 and out["client"]["committed_tps"] > 0
    assert set(out["compared"]) == set(NUMBERS) | {"platform_moved"}
    assert list(out["controls"]) == [
        *reference.FRAME_CONTROLS, "lost_registration",
        "receipt_of_another_call", "registered_twice"]
    assert all(out["controls"].values()), out["controls"]
    assert out["controls"]["lost_registration"] == ["balances_off_replay"]
    assert "receipts_wrong" in out["controls"]["receipt_of_another_call"]
    # one request in eight is refused, as the semantics demand: an answer,
    # not a failure (the control finds such a receipt and calls it done)
    assert out["controls"]["registered_twice"] == ["receipts_wrong"]


def test_a_second_kinds_planted_fault_is_not_correct(with_dagadd):
    out, _err = _rehearse(with_dagadd, 36, (
        "--node-launcher", os.path.join(DATA, "dagadd_faulty_node.py")))
    assert out["correct"] is False
    value, limit = out["compared"]["balances_off_replay"]
    assert value > limit


# -- (c) a plain reference imports nothing of the program ----------------------

def _reference_files():
    for top in (os.path.join(BENCH, "workloads"), DATA):
        for f in sorted(os.listdir(top)):
            if f.endswith("_reference.py"):
                yield os.path.join(top, f)


@pytest.mark.parametrize("path", list(_reference_files()),
                         ids=os.path.basename)
def test_a_reference_imports_nothing_of_the_program(path):
    client = os.path.basename(path)[:-len("_reference.py")]
    assert os.path.exists(os.path.join(os.path.dirname(path),
                                       client + ".py"))
    src = open(path).read()
    names = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", src, re.M)
    assert set(names) <= {"__future__"}, names
    for word in ("fisco_bcos_tpu", "txgen", "importlib", "__import__",
                 client + "."):
        assert word not in src, word


# -- (d) an unknown kind fails before anything starts ---------------------------

def _checkout_with(tmp_path, **keys) -> str:
    """A copy whose first configuration carries more keys (a configuration
    a later PR would add, under the listed name for short)."""
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in ("fisco_bcos_tpu", "tools", "native"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    path = tmp_path / DOC["configs"][0]["file"]
    path.write_text(json.dumps({**json.loads(path.read_text()), **keys}))
    return str(tmp_path)


@pytest.mark.parametrize("keys,said", [
    ({"workload": "no-such-kind"}, "workloads/no-such-kind.py"),
    ({"workload": "../rpc"}, "bad workload name"),
    ({"workload": 7}, "bad workload name"),
])
def test_unknown_kind_starts_nothing(tmp_path, keys, said):
    root = _checkout_with(tmp_path, **keys)
    man = Manifest(root)
    config = man.config(DOC["configs"][0]["name"])
    with pytest.raises(ManifestError, match=re.escape(said)):
        man.workload(config)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         DOC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"], cwd=root, capture_output=True,
        text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert said in p.stderr
    assert "build_chain" not in p.stderr  # no chain was built


def test_a_kind_with_one_file_names_the_missing_path(tmp_path):
    root = _checkout_with(tmp_path, workload="half")
    (tmp_path / "chipbench" / "workloads" / "half.py").write_text(
        '"""A client\'s side without its plain reference."""\n')
    man = Manifest(root)
    with pytest.raises(ManifestError, match="half_reference.py"):
        man.workload(man.config(DOC["configs"][0]["name"]))
