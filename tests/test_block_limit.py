"""A chain whose `block_tx_count_limit` is not 1000: the number is written
once (genesis), the ingest lane and the shapes a device node compiles
follow from it, a client's batch of one block reaches the pool, the seam
and the sealer as one piece, and the answers equal the plain sequential
reference (`chipbench/reference.py`, read back as `chipbench/answers.py`
reads a cluster)."""

import importlib.util
import json
import logging
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from fisco_bcos_tpu.consensus.pbft.engine import round_allowance
from fisco_bcos_tpu.crypto.suite import CryptoSuite, make_suite
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.ledger.ledger import ConsensusNode
from fisco_bcos_tpu.net.gateway import FakeGateway
from fisco_bcos_tpu.ops import ec
from fisco_bcos_tpu.rpc.server import encode_jsonrpc
from fisco_bcos_tpu.testing.scenario import ScenarioSpec, prefund_storage
from fisco_bcos_tpu.tool.config import ChainConfig, load_node
from fisco_bcos_tpu.txpool.ingest import lane_limits

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
sys.path.insert(0, os.path.join(ROOT, "tools"))

from build_chain import build_chain  # noqa: E402
GROUP = "group0"


def bench_module(name: str):
    """A module of chipbench/ under a name of its own: the benchmark's
    files are not a package and share names with none of the tests'."""
    spec = importlib.util.spec_from_file_location(
        f"blk_{name}", os.path.join(BENCH, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = bench_module("reference")
answers = bench_module("answers")
txgen = bench_module("txgen")


def bench_config(limit: int, accounts: int) -> dict:
    with open(os.path.join(BENCH, "configs",
                           "air4-transfer-blk10k.json")) as f:
        cfg = json.load(f)
    cfg.update(block_tx_count_limit=limit, accounts=accounts)
    return cfg


# -- (a) the one road: build_chain -> genesis -> the ledger --------------------

def test_build_chain_writes_the_limit_into_genesis(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "build_chain.py"),
         "-o", str(tmp_path / "chain"), "-n", "4", "--consensus", "pbft",
         "--crypto-backend", "host", "--block-tx-count-limit", "300"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    info = json.loads(out.stdout)
    assert len(info["nodes"]) == 4
    for n in info["nodes"]:
        with open(os.path.join(n["dir"], "genesis")) as f:
            assert ChainConfig.from_ini(f.read()).block_tx_count_limit == 300
    node = load_node(info["nodes"][0]["dir"], gateway=FakeGateway())
    try:
        assert node.config.tx_count_limit == 300
        assert node.ledger.ledger_config().block_tx_count_limit == 300
        # [txpool] limit stays 15000: small blocks coalesce as ever
        assert node.ingest.max_batch == 4096
    finally:
        node.storage.close()


def test_build_chain_default_and_help(tmp_path):
    info = build_chain(str(tmp_path / "c"), 1, consensus="solo",
                       crypto_backend="host")
    with open(os.path.join(info["nodes"][0]["dir"], "genesis")) as f:
        assert ChainConfig.from_ini(f.read()).block_tx_count_limit == 1000
    with pytest.raises(ValueError):
        build_chain(str(tmp_path / "d"), 1, block_tx_count_limit=0)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "build_chain.py"),
         "--help"], capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert "--block-tx-count-limit" in out.stdout


# -- (d) what follows from the limit --------------------------------------------

@pytest.mark.parametrize("limit,pool,want", [
    (1000, 15000, (4096, 8192)),     # the constants these were
    (10000, 15000, (10000, 20000)),  # one block is one dispatch
    (300, 450, (300, 600)),          # no room beside a sealed block
    (5000, 7500, (5000, 10000)),
    (10, 15000, (4096, 8192)),       # small blocks coalesce as ever
])
def test_lane_limits_follow_from_chain_and_pool(limit, pool, want):
    assert lane_limits(limit, pool) == want


def test_default_node_keeps_lane_and_prepared_shapes(monkeypatch):
    asked = []
    monkeypatch.setattr(CryptoSuite, "prepare",
                        lambda self, max_batch=0: asked.append(max_batch))
    for limit, batch in ((1000, 4096), (10000, 10000)):
        node = Node(NodeConfig(crypto_backend="host", tx_count_limit=limit))
        try:
            node.start()
            assert (node.ingest.max_batch, node.ingest.queue_cap) == \
                lane_limits(limit, 15000)
            assert asked[-1] == batch
        finally:
            node.stop()
    fields = {f.name for f in NodeConfig.__dataclass_fields__.values()}
    assert not {"ingest_max_batch", "ingest_queue_cap"} & fields


@pytest.mark.parametrize("limit,ec_buckets", [
    (1000, [512, 4096]), (10000, [512, 4096, 16384])])
def test_prepare_warms_ec_buckets_up_to_the_lane_batch(monkeypatch, limit,
                                                       ec_buckets):
    suite = CryptoSuite("ecdsa", backend="auto", allow_cpu=True)
    seen = {"recover": [], "hash": [], "merkle": []}
    monkeypatch.setattr(suite, "recover_batch",
                        lambda d, s: seen["recover"].append(len(d)))
    monkeypatch.setattr(suite, "hash_batch",
                        lambda m: seen["hash"].append(len(m)))
    monkeypatch.setattr(suite, "merkle_root",
                        lambda lv: seen["merkle"].append(len(lv)))
    suite.prepare(lane_limits(limit, 15000)[0])
    assert seen["recover"] == ec_buckets
    assert sorted(set(seen["hash"])) == ec_buckets
    assert seen["merkle"] == [512, 4096, 16384, 65536]


def test_round_allowance_follows_the_block_size():
    assert round_allowance(3.0, 1000) == 3.0
    assert round_allowance(3.0, 10) == 3.0
    assert round_allowance(3.0, 10000) == 30.0
    assert round_allowance(30.0, 5000) == 150.0


# -- (e) lanes issued ------------------------------------------------------------

def test_device_lanes_are_the_buckets_issued(monkeypatch):
    dev = CryptoSuite("ecdsa", backend="device", allow_cpu=True)
    msgs = [bytes([i % 251]) * 40 for i in range(70)]
    digests = dev.hash_batch(msgs)             # 70 -> bucket 512
    dev.hash_batch(msgs[:9])                   # 9 -> bucket 64
    dev.merkle_root(digests[:20])              # 20 -> bucket 64
    ops = dev.status()["ops"]
    assert (ops["hash"]["deviceItems"], ops["hash"]["deviceLanes"]) == \
        (79, 512 + 64)
    assert (ops["merkle"]["deviceItems"], ops["merkle"]["deviceLanes"]) == \
        (20, 64)

    def fake_recover(curve, e, r, s, v):  # the kernel's shapes, no curve
        b = e.shape[0]
        return np.zeros_like(e), np.zeros_like(e), np.zeros(b, bool)

    monkeypatch.setattr(ec, "ecdsa_recover_batch", fake_recover)
    monkeypatch.setattr(ec, "ecdsa_verify_batch",
                        lambda curve, e, r, s, x, y: np.zeros(e.shape[0],
                                                              bool))
    want = 0
    for n, lanes in ((1000, 4096), (10000, 16384), (20000, 2 * 16384),
                     (3, 8)):
        dev.recover_batch([bytes(32)] * n, [bytes(65)] * n)
        want += lanes
        row = dev.status()["ops"]["recover"]
        assert row["deviceLanes"] == want, (n, row)
    assert row["deviceItems"] == 31003 and row["deviceCalls"] == 4
    dev.verify_batch([bytes(32)] * 600, [bytes(65)] * 600, [bytes(64)] * 600)
    assert dev.status()["ops"]["verify"]["deviceLanes"] == 4096
    host = CryptoSuite("ecdsa", backend="host")
    host.hash_batch(msgs)
    assert host.status()["ops"]["hash"]["deviceLanes"] == 0


# -- (b), (c), (f): a four-node chain at another limit -------------------------

class _InProc:
    """`chipbench/rpc.py`'s surface over a node's JsonRpcImpl, no socket."""

    def __init__(self, node):
        self.impl = node.rpc.impl if node.rpc is not None else None
        if self.impl is None:
            from fisco_bcos_tpu.rpc.server import JsonRpcImpl
            self.impl = JsonRpcImpl(node)

    def batch(self, calls: list) -> list:
        return [json.loads(encode_jsonrpc(self.impl.handle(
            {"jsonrpc": "2.0", "id": i, "method": m, "params": p})))
            for i, (m, p) in enumerate(calls)]

    def results(self, calls: list, chunk: int = 256) -> list:
        out = self.batch(calls)
        assert all("result" in r for r in out), [r for r in out
                                                 if "result" not in r][:2]
        return [r["result"] for r in out]

    def call(self, method: str, params: list):
        return self.results([(method, params)])[0]

    def close(self) -> None:
        pass


class Chain:
    """Four in-process PBFT nodes with host crypto over a FakeGateway,
    prefunded like a chipbench cluster; node0 has the RPC edge."""

    def __init__(self, limit: int, pool: int, accounts: int = 512,
                 per_node=None, **cfg):
        """`per_node(i)` -> more NodeConfig fields of node i (a data path
        of its own); nodes that open a chain they find there are neither
        prefunded nor given a genesis again."""
        suite = make_suite(False, backend="host")
        kps = [suite.generate_keypair(bytes([i + 1]) * 16) for i in range(4)]
        self.gw = FakeGateway()
        self.group = GROUP
        self.config = bench_config(limit, accounts)
        self.nodes = []
        for i, kp in enumerate(kps):
            node = Node(NodeConfig(
                consensus="pbft", crypto_backend="host",
                tx_count_limit=limit, txpool_limit=pool,
                rpc_max_batch=2 * limit, trace_sample_rate=0.0,
                rpc_port=0 if i == 0 else None, **cfg,
                **(per_node(i) if per_node else {})),
                keypair=kp, gateway=self.gw)
            if node.ledger.current_number() < 0:
                prefund_storage(node.storage,
                                ScenarioSpec("hot-key", accounts=accounts))
                node.build_genesis([ConsensusNode(k.pub_bytes) for k in kps])
            self.nodes.append(node)
        for node in self.nodes:
            node.start()
        self.procs = self.nodes  # answers.gather counts them
        self.maker = txgen.TxMaker(self.config, seed=28)
        self.sent: list[dict] = []

    def rpc(self, k: int, timeout: float = 0.0) -> _InProc:
        return _InProc(self.nodes[k])

    def stop(self) -> None:
        for node in self.nodes:
            node.stop()
        self.gw.stop()

    def send_cohort(self, n: int) -> list[dict]:
        """One JSON-RPC batch of n sendTransaction (wait=true) through
        node0's JsonRpcImpl -> what was sent, with the receipts."""
        first = len(self.sent)
        made = [self.maker.make(first + i, 400) for i in range(n)]
        payload = [{"jsonrpc": "2.0", "id": i, "method": "sendTransaction",
                    "params": [GROUP, "", wire, False, True]}
                   for i, (wire, _h, _mv) in enumerate(made)]
        out = json.loads(encode_jsonrpc(
            self.nodes[0].rpc.impl.handle_payload(payload)))
        assert [r["id"] for r in out] == list(range(n))
        sent = [{"hash": h, "move": mv, "receipt": r.get("result")}
                for (_w, h, mv), r in zip(made, out)]
        self.sent += sent
        return sent

    def judged(self) -> dict:
        """chipbench's comparison of everything sent so far -> the numbers
        that are not 0."""
        ans = answers.gather(self, self.maker, self.sent, seed=28)
        return {x["name"]: x["value"]
                for x in reference.judge(self.config, self.sent, ans)
                if x["value"] > x["limit"]}

    def counters(self) -> dict:
        n0 = self.nodes[0]
        st = n0.system_status()
        return {"lane": n0.ingest.stats(),
                "recover": st["crypto"]["ops"]["recover"],
                "edge": st["trace"]["counters"],
                "heights": [n.ledger.current_number() for n in self.nodes],
                "views": [n.consensus.view for n in self.nodes]}

    def block_sizes(self, lo: int, hi: int) -> list[list[int]]:
        """Per node, the sizes of blocks lo+1..hi."""
        return [[len(n.ledger.block_by_number(k).tx_hashes)
                 for k in range(lo + 1, hi + 1)] for n in self.nodes]

    def settle(self, height: int) -> None:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and any(
                n.ledger.current_number() < height for n in self.nodes):
            time.sleep(0.02)


@pytest.fixture(scope="module")
def chain300():
    c = Chain(limit=300, pool=450)
    yield c
    c.stop()


def test_a_cohort_of_one_block_is_one_piece_everywhere(chain300):
    c = chain300
    before = c.counters()
    sent = c.send_cohort(300)
    assert all(s["receipt"] and s["receipt"]["status"] == 0 for s in sent)
    h0 = before["heights"][0]
    c.settle(h0 + 1)
    after = c.counters()
    # one lane batch, one seam call, one block of 300 on every node
    assert after["lane"]["batches_total"] - before["lane"]["batches_total"] \
        == 1
    assert after["lane"]["txs_total"] - before["lane"]["txs_total"] == 300
    assert after["recover"]["hostCalls"] - before["recover"]["hostCalls"] \
        + after["recover"]["deviceCalls"] \
        - before["recover"]["deviceCalls"] == 1
    assert after["heights"] == [h0 + 1] * 4
    assert c.block_sizes(h0, h0 + 1) == [[300]] * 4
    assert after["views"] == before["views"]
    assert after["edge"]["cohorts"] - before["edge"]["cohorts"] == 1
    assert after["edge"]["cohorts_whole"] \
        - before["edge"]["cohorts_whole"] == 1
    assert {s["receipt"]["blockNumber"] for s in sent} == {h0 + 1}
    # receipts, order, seals, agreement, balances: chipbench's reference
    assert c.judged() == {}


def test_a_cohort_over_the_lane_batch_is_counted_not_whole(chain300, caplog):
    c = chain300
    before = c.counters()
    with caplog.at_level(logging.WARNING, logger="bcos-tpu"):
        sent = c.send_cohort(301)
    assert all(s["receipt"] and s["receipt"]["status"] == 0 for s in sent)
    h0 = before["heights"][0]
    c.settle(h0 + 2)
    after = c.counters()
    assert after["edge"]["cohorts"] - before["edge"]["cohorts"] == 1
    assert after["edge"]["cohorts_whole"] \
        == before["edge"]["cohorts_whole"]
    assert any("cohort-split" in r.getMessage() for r in caplog.records)
    # taken in two dispatches of the lane, not entry by entry
    assert after["lane"]["batches_total"] - before["lane"]["batches_total"] \
        == 2
    assert after["lane"]["txs_total"] - before["lane"]["txs_total"] == 301
    sizes = c.block_sizes(h0, after["heights"][0])
    assert sizes[0] == sizes[1] == sizes[2] == sizes[3]
    assert sum(sizes[0]) == 301 and max(sizes[0]) <= 300
    assert c.judged() == {}


def test_a_refused_cohort_is_counted_and_logged(chain300, caplog):
    c = chain300
    lane = c.nodes[0].ingest
    before = c.counters()
    cap, lane.queue_cap = lane.queue_cap, 4
    try:
        with caplog.at_level(logging.WARNING, logger="bcos-tpu"):
            sent = c.send_cohort(6)
    finally:
        lane.queue_cap = cap
    # the entries went in one by one, each meeting the lane on its own
    assert all(s["receipt"] and s["receipt"]["status"] == 0 for s in sent)
    after = c.counters()
    assert after["edge"]["cohorts"] - before["edge"]["cohorts"] == 1
    assert after["edge"]["cohorts_whole"] \
        == before["edge"]["cohorts_whole"]
    assert after["lane"]["rejected_total"] \
        - before["lane"]["rejected_total"] == 6
    refused = [r.getMessage() for r in caplog.records
               if "cohort-refused" in r.getMessage()]
    assert len(refused) == 1 and "n=6" in refused[0]
    c.settle(max(s["receipt"]["blockNumber"] for s in sent))
    assert c.judged() == {}


ONE_PIECE_SECONDS = 120.0  # this test's own limit; it takes ~15 s


def test_a_block_past_the_old_lane_batch_commits_in_one_piece():
    """5,000 > 4096: the old dispatcher handed admission 4096 + 904 and
    the sealer sealed what the first put in the pool. Default
    view_timeout (3 s): the round's allowance follows the block size."""
    c = Chain(limit=5000, pool=7500, accounts=4096)
    try:
        assert all(n.consensus.base_timeout == 15.0 for n in c.nodes)
        before = c.counters()
        got: list = []
        worker = threading.Thread(
            target=lambda: got.append(c.send_cohort(5000)), daemon=True)
        worker.start()
        worker.join(ONE_PIECE_SECONDS)
        assert got, f"no answer in {ONE_PIECE_SECONDS:.0f} s"
        sent = got[0]
        assert all(s["receipt"] and s["receipt"]["status"] == 0
                   for s in sent)
        c.settle(1)
        after = c.counters()
        assert after["heights"] == [1] * 4
        assert c.block_sizes(0, 1) == [[5000]] * 4
        assert after["views"] == [0] * 4
        assert after["lane"]["batches_total"] \
            - before["lane"]["batches_total"] == 1
        assert after["recover"]["hostCalls"] \
            - before["recover"]["hostCalls"] == 1
        assert after["edge"]["cohorts_whole"] == 1
        assert c.judged() == {}
    finally:
        c.stop()


# -- the anti-entropy sweep leaves fresh transactions to ordinary gossip ------

def test_sweep_readvertises_only_what_waited_a_whole_interval():
    """A sweep that re-advertised a cohort admitted a moment ago sent 256
    of its 10,000 in a small frame that overtook the cohort's own; the
    leader sealed them as a block of 256 (chip and CPU, PR 28)."""
    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.net import txsync
    from fisco_bcos_tpu.protocol import Transaction

    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"sweep")
    txs = [Transaction(to=pc.BALANCE_ADDRESS, nonce=f"sw-{i}",
                       block_limit=100, input=b"x").sign(suite, kp)
           for i in range(300)]

    class Front:
        sent: list = []

        def register_module(self, *_a): pass
        def peers(self): return [b"peer"]
        def send(self, _mod, _peer, data): self.sent.append(data)

    class Pool:
        unsealed: list = []

        def register_broadcast_hook(self, _fn): pass
        def pending_txs(self, max_txs=0): return list(self.unsealed)

    front, pool = Front(), Pool()
    sync = txsync.TransactionSync(front, pool, suite,
                                  anti_entropy_interval=0.0)

    def swept() -> list:
        front.sent.clear()
        sync.execute_worker()
        return [h for d in front.sent for h, _raw in txsync._unpack_txs(d)]

    pool.unsealed = txs[:10]
    assert swept() == []                      # fresh: gossip's to deliver
    pool.unsealed = txs[:10] + txs[10:20]
    assert swept() == [t.hash(suite) for t in txs[:10]]
    pool.unsealed = txs[5:20]                 # 0-4 were sealed meanwhile
    assert swept() == [t.hash(suite) for t in txs[5:20]]
    pool.unsealed = txs                       # a stranded backlog: capped
    swept()
    assert len(swept()) == sync.ANTI_ENTROPY_MAX
    pool.unsealed = []
    assert swept() == []
