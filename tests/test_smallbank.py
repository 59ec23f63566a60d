"""SmallBank (`executor/precompiled.py` `SmallBankPrecompile`): each method
against the plain reference of the `air4-smallbank` cell
(`chipbench/workloads/smallbank_reference.py`, loaded by path), refusals and
signed overdrafts included; a skewed block through the DAG path against the
serial path and the replay; the conflict keys against the rows each call
reads or writes; the counters, once a block; and a four-node PBFT chain that
commits REVERT beside status 0 under one root on every replica."""

import collections
import importlib.util
import json
import os
import time

import pytest

from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.executor.executor import TransactionExecutor
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.ledger.ledger import ConsensusNode
from fisco_bcos_tpu.net.gateway import FakeGateway
from fisco_bcos_tpu.protocol import Transaction, TransactionStatus
from fisco_bcos_tpu.rpc.server import JsonRpcImpl, encode_jsonrpc
from fisco_bcos_tpu.storage.memory import MemoryStorage
from fisco_bcos_tpu.storage.state import StateStorage

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
SUITE = make_suite(backend="host")
GROUP = "group0"
SB_TABLES = (pc.T_SB_SAVINGS, pc.T_SB_CHECKING)


def _bench_module(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_module("workloads/smallbank_reference.py", "sb_test_reference")
KIND = _bench_module("workloads/smallbank.py", "sb_test_kind")
REFERENCE = _bench_module("reference.py", "sb_test_judge")
ANSWERS = _bench_module("answers.py", "sb_test_answers")
with open(os.path.join(BENCH, "configs", "air4-smallbank.json")) as _f:
    CONFIG = dict(json.load(_f), accounts=1000)
UNKNOWN = KIND.customer(CONFIG["accounts"])


def _name(i: int) -> bytes:
    return KIND.customer(i)


def _state() -> StateStorage:
    backend = MemoryStorage()
    KIND.prefund(backend, CONFIG)
    return StateStorage(backend)


def _tx(op, nonce: str) -> Transaction:
    to, data = KIND.call(op)
    return Transaction(to=to, input=data, nonce=nonce, block_limit=100)


def _account(st, name: bytes):
    vals = [st.get(t, name) for t in SB_TABLES]
    if None in vals:
        return None
    return tuple(int.from_bytes(v, "big", signed=True) for v in vals)


def _said(rc) -> dict:
    """A receipt as the RPC renders the parts the reference reads."""
    return {"status": rc.status, "output": "0x" + rc.output.hex(),
            "logEntries": [{} for _ in rc.logs]}


# -- (a) every method against the plain reference -----------------------------

S1, C1 = KIND.opening(CONFIG, 1)
CASES = {
    "getBalance": ("getBalance", _name(1), b"", 0),
    "getBalance_unknown": ("getBalance", UNKNOWN, b"", 0),
    "updateBalance": ("updateBalance", _name(1), b"", 130),
    "updateBalance_negative": ("updateBalance", _name(1), b"", -1),
    "updateSaving": ("updateSaving", _name(1), b"", 2020),
    "updateSaving_to_zero": ("updateSaving", _name(1), b"", -S1),
    "updateSaving_below_zero": ("updateSaving", _name(1), b"", -S1 - 1),
    "sendPayment": ("sendPayment", _name(1), _name(2), C1),
    "sendPayment_short": ("sendPayment", _name(1), _name(2), C1 + 1),
    "sendPayment_to_self": ("sendPayment", _name(1), _name(1), 5),
    "sendPayment_to_unknown": ("sendPayment", _name(1), UNKNOWN, 5),
    "writeCheck": ("writeCheck", _name(1), b"", 500),
    "writeCheck_overdraft": ("writeCheck", _name(1), b"", S1 + C1 + 1),
    "amalgamate": ("amalgamate", _name(1), _name(2), 0),
    "amalgamate_self": ("amalgamate", _name(2), _name(2), 0),
    "amalgamate_from_unknown": ("amalgamate", UNKNOWN, _name(2), 0),
}
REFUSED = {"getBalance_unknown", "updateBalance_negative",
           "updateSaving_below_zero", "sendPayment_short",
           "sendPayment_to_self", "sendPayment_to_unknown",
           "amalgamate_self", "amalgamate_from_unknown"}


@pytest.mark.parametrize("case", list(CASES))
def test_method_against_the_reference(case):
    op = CASES[case]
    st = _state()
    rc = TransactionExecutor(SUITE).execute_transaction(
        _tx(op, case), st, 1, 0)
    want, untouched, refused = REF.expected([op], CONFIG)
    assert untouched is None and bool(refused) is (case in REFUSED)
    assert REF.receipt_says(_said(rc), op, bool(refused)), (
        rc.status, rc.output, rc.message)
    if refused:
        assert rc.status == TransactionStatus.REVERT
        assert st.changeset() == {}  # a refusal changes no row
    for name in (_name(1), _name(2), UNKNOWN):
        assert _account(st, name) == want.get(name), name


def test_signed_overdraft_and_balance_by_hand():
    st = _state()
    ex = TransactionExecutor(SUITE)
    v = S1 + C1 + 1
    rc = ex.execute_transaction(
        _tx(("writeCheck", _name(1), b"", v), "wc"), st, 1, 0)
    assert rc.status == 0 and rc.output == b""
    assert _account(st, _name(1)) == (S1, C1 - v - pc.SMALLBANK_PENALTY)
    rc = ex.execute_transaction(
        _tx(("getBalance", _name(1), b"", 0), "gb"), st, 1, 0)
    assert int.from_bytes(rc.output, "big", signed=True) == \
        S1 + C1 - v - pc.SMALLBANK_PENALTY == -1 - pc.SMALLBANK_PENALTY
    # the read the harness uses answers both rows, signed
    rc = ex.execute_transaction(Transaction(
        to=pc.SMALLBANK_ADDRESS, input=pc.encode_call(
            "getAccount", lambda w: w.blob(_name(1))), nonce="ga",
        block_limit=100), st, 1, 0)
    assert KIND.decode({"status": rc.status,
                        "output": "0x" + rc.output.hex()}) == \
        (S1, C1 - v - pc.SMALLBANK_PENALTY)


# -- (b) a skewed block: DAG path = serial path = the replay -------------------

SEED = 2**31 + 4201


def test_skewed_block_dag_equals_serial_and_replay():
    ops = [KIND.op(CONFIG, SEED, i) for i in range(1000)]
    txs = [_tx(op, f"b-{i}") for i, op in enumerate(ops)]
    runs = []
    for path in ("execute_block_dag", "execute_block_serial"):
        ex = TransactionExecutor(SUITE, trace_label=f"sb-{path}")
        st = _state()
        receipts = getattr(ex, path)(txs, st, 1, 0)
        changes = st.changeset()
        runs.append(([(r.status, r.output) for r in receipts],
                     sorted((k, e.value) for k, e in changes.items()),
                     ex.state_root(changes), st))
    assert runs[0][:3] == runs[1][:3]

    waves = TransactionExecutor(SUITE).plan_dag(txs)
    assert len(waves) > 20  # narrow waves, where uniform keys make ~2
    want, _, refused = REF.expected(ops, CONFIG)
    said = [REF.receipt_says({"status": s, "output": "0x" + o.hex()}, op,
                             i in set(refused))
            for i, ((s, o), op) in enumerate(zip(runs[0][0], ops))]
    assert all(said), said.index(False)
    st = runs[0][3]
    named = {n for op in ops for n in op[1:3] if n}
    assert all(_account(st, n) == want.get(n) for n in named)
    # the skew reaches every kind of answer: refusals, signed overdrafts
    assert 10 < len(refused) < 500
    assert any(c < 0 for _s, c in (want[n] for n in named))


# -- (c) conflict keys: the rows a call reads or writes ------------------------

class _Recording(StateStorage):
    def __init__(self, backend):
        super().__init__(backend)
        self.rows: set = set()

    def get(self, table, key):
        if table in SB_TABLES:
            self.rows.add(table.encode() + key)
        return super().get(table, key)

    def set(self, table, key, value):
        if table in SB_TABLES:
            self.rows.add(table.encode() + key)
        super().set(table, key, value)


@pytest.mark.parametrize("case", [c for c in CASES if "unknown" not in c]
                         + ["getAccount"])
def test_conflict_keys_are_the_rows_touched(case):
    if case == "getAccount":
        data = pc.encode_call("getAccount", lambda w: w.blob(_name(3)))
    else:
        data = KIND.call(CASES[case])[1]
    backend = MemoryStorage()
    KIND.prefund(backend, CONFIG)
    st = _Recording(backend)
    TransactionExecutor(SUITE).execute_transaction(Transaction(
        to=pc.SMALLBANK_ADDRESS, input=data, nonce=case, block_limit=100),
        st, 1, 0)
    keys = pc.PRECOMPILED_REGISTRY[pc.SMALLBANK_ADDRESS].conflict_keys(data)
    assert st.rows and set(keys) == st.rows


def test_conflict_keys_of_what_cannot_be_parsed():
    sb = pc.SmallBankPrecompile()
    assert sb.conflict_keys(b"") is None
    assert sb.conflict_keys(pc.encode_call("nope")) is None
    assert sb.conflict_keys(pc.encode_call("sendPayment",
                                           lambda w: w.blob(b"a"))) is None


# -- (d) the counters: once a block ---------------------------------------------

def test_counters_count_once_a_block(monkeypatch):
    ex = TransactionExecutor(SUITE, trace_label=f"sb-counters-{time.time_ns()}")
    calls = collections.Counter()
    real = ex.stages.count

    def count(name, n):
        calls[name] += 1
        real(name, n)

    monkeypatch.setattr(ex.stages, "count", count)
    other = Transaction(to=pc.BALANCE_ADDRESS, input=pc.encode_call(
        "register", lambda w: w.blob(b"someone").u64(1)), nonce="o",
        block_limit=100)
    refused = leaves = 0
    for block in (1, 2):
        st = _state()
        ops = [KIND.op(CONFIG, SEED + block, i) for i in range(60)]
        ops.append(("updateBalance", _name(1), b"", -1))
        txs = [_tx(op, f"c-{block}-{i}") for i, op in enumerate(ops)]
        receipts = ex.execute_block_dag(txs + [other], st, block, 0)
        assert receipts[-1].status == 0
        refused += sum(r.status != 0 for r in receipts[:-1])
        changes = st.changeset()
        leaves += len(changes)
        assert len(ex.state_root_with_leaves(changes)[1]) == len(changes)
    got = ex.stages.counters()
    assert refused >= 2
    assert (got["smallbank_calls"], got["smallbank_refused"],
            got["state_leaves"]) == (2 * 61, refused, leaves)
    assert all(calls[n] == 2 for n in (
        "dag_blocks", "dag_txs", "smallbank_calls", "smallbank_refused",
        "state_leaves")), calls


# -- (e) the served path: REVERT beside status 0, one root on every replica ---

class _InProc:
    """`chipbench/rpc.py`'s surface over a node's JsonRpcImpl, no socket."""

    def __init__(self, node):
        self.impl = node.rpc.impl if node.rpc is not None else \
            JsonRpcImpl(node)

    def results(self, calls: list, chunk: int = 256) -> list:
        out = [json.loads(encode_jsonrpc(self.impl.handle(
            {"jsonrpc": "2.0", "id": i, "method": m, "params": p})))
            for i, (m, p) in enumerate(calls)]
        assert all("result" in r for r in out), out[:2]
        return [r["result"] for r in out]

    def call(self, method: str, params: list):
        return self.results([(method, params)])[0]

    def close(self) -> None:
        pass


class _Chain:
    """Four PBFT nodes over the in-process gateway, every one prefunded
    with the kind's customers; node0 serves JSON-RPC."""

    group = GROUP

    def __init__(self):
        kps = [SUITE.generate_keypair(bytes([i + 1]) * 16) for i in range(4)]
        self.gw = FakeGateway()
        self.procs = []
        for i, kp in enumerate(kps):
            node = Node(NodeConfig(
                consensus="pbft", crypto_backend="host",
                trace_sample_rate=0.0, rpc_port=0 if i == 0 else None),
                keypair=kp, gateway=self.gw)
            KIND.prefund(node.storage, CONFIG)
            node.build_genesis([ConsensusNode(k.pub_bytes) for k in kps])
            self.procs.append(node)
        for node in self.procs:
            node.start()

    def rpc(self, k: int, timeout: float = 0.0) -> _InProc:
        return _InProc(self.procs[k])

    def stop(self) -> None:
        for node in self.procs:
            node.stop()
        self.gw.stop()


def test_served_chain_commits_refusals_with_one_root_everywhere():
    c = [_name(i) for i in range(8)]
    ops = [("sendPayment", c[1], c[2], 10**12),    # short: REVERT
           ("updateBalance", c[3], b"", 130),
           ("getBalance", c[3], b"", 0),
           ("amalgamate", c[4], c[5], 0),
           ("sendPayment", c[4], c[6], 500),       # drained: REVERT
           ("writeCheck", c[4], b"", 500),         # signed overdraft
           ("getBalance", c[4], b"", 0),
           ("updateBalance", c[7], b"", -5),       # bad amount: REVERT
           ("amalgamate", c[6], c[6], 0),          # one customer: REVERT
           ("updateSaving", c[5], b"", 2020)]
    kp = SUITE.generate_keypair(b"smallbank-client")
    txs = [Transaction(to=KIND.call(op)[0], input=KIND.call(op)[1],
                       nonce=f"s-{i}", block_limit=100).sign(SUITE, kp)
           for i, op in enumerate(ops)]
    chain = _Chain()
    try:
        payload = [{"jsonrpc": "2.0", "id": i, "method": "sendTransaction",
                    "params": [GROUP, "", "0x" + tx.encode().hex(), False,
                               True]} for i, tx in enumerate(txs)]
        out = json.loads(encode_jsonrpc(
            chain.procs[0].rpc.impl.handle_payload(payload)))
        receipts = [r["result"] for r in sorted(out, key=lambda r: r["id"])]
        statuses = [rc["status"] for rc in receipts]
        assert statuses == [14, 0, 0, 0, 14, 0, 0, 14, 14, 0], receipts
        height = max(rc["blockNumber"] for rc in receipts)
        deadline = time.monotonic() + 30
        while any(n.ledger.current_number() < height for n in chain.procs) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        # a block holds REVERT and status 0 side by side
        assert any({rc["status"] for rc in receipts
                    if rc["blockNumber"] == b} == {0, 14}
                   for b in range(1, height + 1))
        for b in range(1, height + 1):
            heads = [n.ledger.header_by_number(b) for n in chain.procs]
            assert len({(h.receipts_root, h.state_root, h.hash(SUITE))
                        for h in heads}) == 1, b
        sent = [{"hash": "0x" + tx.hash(SUITE).hex(), "move": op,
                 "receipt": rc} for tx, op, rc in zip(txs, ops, receipts)]
        # every replica answers the same receipts, REVERT included
        for k in range(4):
            got = chain.rpc(k).results([("getTransactionReceipt",
                                         [GROUP, "", s["hash"]])
                                        for s in sent])
            assert [g["status"] for g in got] == statuses, k

        class _Maker:
            kind = KIND

        ans = ANSWERS.gather(chain, _Maker, sent, seed=42)
        numbers = REFERENCE.judge(CONFIG, sent, ans)
        assert all(x["value"] == 0 for x in numbers), numbers
        assert ans["balances"][0][c[4]][1] == -600  # 0 - 500 - the penalty
    finally:
        chain.stop()
