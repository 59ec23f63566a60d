"""ParallelOk on the EVM path (`fisco_bcos_tpu/testing/parallelok.py`): the
hand-assembled contract on both interpreters, the DAG planner's decoded
string criticals, and a block of transfers through `execute_block_dag`
against the plain reference of the `air4-parallelok` cell
(`chipbench/workloads/parallelok_reference.py`, loaded by path)."""

import importlib.util
import json
import os
import random
import sys

import pytest

from fisco_bcos_tpu.codec import abi as abi_mod
from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import nevm
from fisco_bcos_tpu.executor.evm import EVM, T_STORE, TxEnv
from fisco_bcos_tpu.executor.executor import TransactionExecutor
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.storage.memory import MemoryStorage
from fisco_bcos_tpu.storage.state import StateStorage
from fisco_bcos_tpu.testing import parallelok as po
from fisco_bcos_tpu.utils import otrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
SUITE = make_suite(backend="host")
H = SUITE.hash
ENV = TxEnv(origin=b"\x0a" * 20, gas_price=0, block_number=7,
            timestamp=1700000000000, gas_limit=10_000_000)
CALLER = b"\x22" * 20
M256 = 1 << 256
START = 1_000_000
NAMES = [b"acct-%07d" % i for i in range(16)]


def _bench_module(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REFERENCE = _bench_module("workloads/parallelok_reference.py",
                          "po_test_reference")


def _state(balances=None) -> StateStorage:
    st = StateStorage(MemoryStorage())
    po.deploy(st, NAMES, START, H)
    for name, v in (balances or {}).items():
        st.set(T_STORE, po.slot_key(name, H), v.to_bytes(32, "big"))
    return st


def _balance(st, name: bytes) -> int:
    v = st.get(T_STORE, po.slot_key(name, H))
    return int.from_bytes(v, "big") if v else 0


def _dump(st) -> list:
    return sorted((k, e.value, e.deleted) for k, e in st.changeset().items())


# -- (a) the contract on both interpreters ------------------------------------

def _call(method, *args):
    return po.encode(method, *args, hash_fn=H)


def _bad_offset() -> bytes:
    data = bytearray(_call("transfer", b"a", b"b", 1))
    data[4:36] = (1 << 40).to_bytes(32, "big")  # `from` points past the end
    return bytes(data)


def _bad_length() -> bytes:
    data = bytearray(_call("set", NAMES[1], 9))
    data[68:100] = (1000).to_bytes(32, "big")  # longer than the calldata
    return bytes(data)


# (calldata, value, success, output, balances after, by name)
CASES = {
    "transfer": (_call("transfer", NAMES[1], NAMES[2], 5), 0, True, b"",
                 {NAMES[1]: START - 5, NAMES[2]: START + 5}),
    "transfer_wraps": (_call("transfer", b"nobody", NAMES[2], 7), 0, True,
                       b"", {b"nobody": M256 - 7, NAMES[2]: START + 7}),
    "transfer_to_self": (_call("transfer", NAMES[3], NAMES[3], 4), 0, True,
                         b"", {NAMES[3]: START}),
    "long_names": (_call("transfer", b"x" * 33, b"y" * 64, 2), 0, True, b"",
                   {b"x" * 33: M256 - 2, b"y" * 64: 2}),
    "set": (_call("set", b"fresh", 77), 0, True, b"", {b"fresh": 77}),
    "balanceOf": (_call("balanceOf", NAMES[4]), 0, True,
                  START.to_bytes(32, "big"), {NAMES[4]: START}),
    "balanceOf_unknown": (_call("balanceOf", b"nobody"), 0, True,
                          bytes(32), {}),
    "unknown_selector": (b"\x12\x34\x56\x78" + bytes(64), 0, False, b"", {}),
    "empty_calldata": (b"", 0, False, b"", {}),
    "short_calldata": (_call("transfer", NAMES[1], NAMES[2], 5)[:100], 0,
                       False, b"", {NAMES[1]: START}),
    "offset_outside": (_bad_offset(), 0, False, b"", {}),
    "length_outside": (_bad_length(), 0, False, b"", {NAMES[1]: START}),
    "paid_call": (_call("transfer", NAMES[1], NAMES[2], 5), 1, False, b"",
                  {NAMES[1]: START}),
}


@pytest.mark.skipif(not nevm.available(), reason="libnevm.so not built")
@pytest.mark.parametrize("case", list(CASES))
def test_contract_on_both_interpreters(case):
    data, value, success, output, after = CASES[case]
    code = po.runtime_code(H)
    got = []
    for native in (True, False):
        st = _state()
        evm = EVM(SUITE, native=native)
        res = evm._run(st, ENV, code, CALLER, po.ADDRESS, value, data,
                       1_000_000, 0, False)
        got.append((res.success, res.output, res.gas_left, res.logs,
                    _dump(st)))
        assert res.success is success, (native, res.error)
        assert res.output == output and res.logs == []
        if not success:
            assert res.error == "revert"
        for name, v in after.items():
            assert _balance(st, name) == v, (native, name)
    assert got[0] == got[1]  # output, gas, status, storage: bit for bit


# -- (b) the planner's decoded criticals --------------------------------------

def _tx(data: bytes, nonce: str, to: bytes = po.ADDRESS) -> Transaction:
    return Transaction(to=to, input=data, nonce=nonce, block_limit=100)


def _graph_waves(names: list) -> list:
    """Waves of the conflict graph of (from, to) pairs, by hand: a transfer
    goes one wave after the last that touched either of its names."""
    last: dict = {}
    waves: list = []
    for i, pair in enumerate(names):
        w = 1 + max((last.get(n, -1) for n in pair), default=-1)
        if w == len(waves):
            waves.append([])
        waves[w].append(i)
        for n in pair:
            last[n] = w
    return waves


def _moves(seed: int, count: int, users: int) -> list:
    rng = random.Random(seed)
    out = []
    for i in range(count):
        a = rng.randrange(users)
        b = (a + 1 + rng.randrange(users - 1)) % users
        out.append((b"acct-%07d" % a, b"acct-%07d" % b, 1 + i % 7))
    return out


@pytest.mark.parametrize("seed,count,users", [
    (1, 200, 8), (2, 300, 64), (3, 1000, 100_000)])
def test_plan_is_the_conflict_graph_of_the_names(seed, count, users):
    ex = TransactionExecutor(SUITE)
    st = _state()
    moves = _moves(seed, count, users)
    txs = [_tx(_call("transfer", *m), f"p{i}") for i, m in enumerate(moves)]
    assert ex.plan_dag(txs, st) == _graph_waves([m[:2] for m in moves])
    # the head words alone (the offsets 0x60 / 0xa0) would conflict all
    if users == 100_000:
        assert len(ex.plan_dag(txs, st)) <= 4


def test_set_and_transfer_of_one_name_conflict():
    ex = TransactionExecutor(SUITE)
    st = _state()
    txs = [_tx(_call("set", b"alice", 5), "s1"),
           _tx(_call("transfer", b"bob", b"carol", 1), "t1"),
           _tx(_call("transfer", b"carol", b"alice", 1), "t2")]
    assert ex.plan_dag(txs, st) == [[0, 1], [2]]
    assert ex._evm_parallel_keys(txs[0], st) == [po.ADDRESS + b"alice"]


@pytest.mark.parametrize("data", [
    _call("transfer", b"a", b"b", 1)[:68],     # the second head word cut
    _call("transfer", b"a", b"b", 1)[:150],    # `to`'s length cut
    _call("transfer", b"a", b"b" * 40, 1)[:-30],  # `to`'s contents cut
    _bad_offset(),
    _bad_length(),
    b"\x12\x34\x56\x78" + bytes(96),           # a selector not annotated
], ids=["head", "length", "contents", "offset", "huge_length", "unknown"])
def test_malformed_dynamic_calldata_plans_as_opaque(data):
    ex = TransactionExecutor(SUITE)
    st = _state()
    if data[:4] != b"\x12\x34\x56\x78":
        assert ex._parallel_selectors(po.ADDRESS, po.ABI.encode())[data[:4]]
    opaque = _tx(data, "bad")
    assert ex._evm_parallel_keys(opaque, st) is None
    txs = [_tx(_call("transfer", b"a", b"b", 1), "t1"), opaque,
           _tx(_call("transfer", b"c", b"d", 1), "t2")]
    assert ex.plan_dag(txs, st) == [[0], [1], [2]]


@pytest.mark.parametrize("inputs,n,heads", [
    (["uint256", "uint256"], 1, ((4, 32, False),)),
    (["string", "string", "uint256"], 2, ((4, 32, True), (36, 32, True))),
    (["uint256[2]", "bytes", "address"], 3,
     ((4, 64, False), (68, 32, True), (100, 32, False))),
    (["uint256[]", "uint256"], 1, None),
    (["string"], 2, None),
])
def test_critical_heads(inputs, n, heads):
    assert TransactionExecutor._critical_heads(inputs, n) == heads


# -- (c) a block of transfers against the reference --------------------------

def _block_state(users: list) -> StateStorage:
    st = StateStorage(MemoryStorage())
    po.deploy(st, users, START, H)
    return st


NATIVE = [pytest.param(True, marks=pytest.mark.skipif(
    not nevm.available(), reason="libnevm.so not built")), False]


@pytest.mark.parametrize("native", NATIVE)
@pytest.mark.parametrize("hot", [6, 40])
def test_block_equals_reference_replay_and_serial(native, hot):
    users = [b"acct-%07d" % i for i in range(hot)]
    moves = _moves(100 + hot, 300, hot)
    kp = SUITE.generate_keypair(b"parallelok-block")
    txs = [_tx(_call("transfer", *m), f"b{i}").sign(SUITE, kp)
           for i, m in enumerate(moves)]
    label = f"po-{native}-{hot}"
    otrace.stages(label).reset()
    ex = TransactionExecutor(SUITE, trace_label=label)
    ex.evm.native = native
    st = _block_state(users)
    rcs = ex.execute_block_dag(txs, st, 1, 0)
    assert [(r.status, r.output, r.logs) for r in rcs] == [(0, b"", [])] * 300

    want, untouched, refused = REFERENCE.expected(
        moves, {"prefund_balance": START})
    assert refused == [] and untouched == START
    assert {u: _balance(st, u) for u in users} == \
        {u: want.get(u, START) for u in users}

    serial = _block_state(users)
    ex2 = TransactionExecutor(SUITE)
    rcs2 = [ex2.execute_transaction(t, serial, 1, 0) for t in txs]
    assert [(r.status, r.gas_used) for r in rcs] == \
        [(r.status, r.gas_used) for r in rcs2]
    assert _dump(st) == _dump(serial)

    counts = otrace.stages(label).counters()
    waves = len(ex.plan_dag(txs, st))
    assert (counts["dag_blocks"], counts["dag_txs"], counts["dag_waves"]) \
        == (1, 300, waves)
    assert counts["dag_pooled_txs"] == 0  # every wave runs serially
    assert counts["evm_frames"] == 300
    assert counts["evm_native_frames"] == (300 if native else 0)
    assert otrace.stages(label).snapshot()["dag_plan"]["count"] == 1


SPIN = b"\x5a" * 20
SPIN_ABI = json.dumps([{"type": "function", "name": "spin",
                        "inputs": [{"name": "key", "type": "uint256"}],
                        "outputs": [], "parallel": 1}])


def _spin_state(rounds: int) -> StateStorage:
    """`spin(uint256 key)`: `acc = key`, then `rounds` times `acc = acc *
    acc + n` with `n` counting down, then `s_store[key] = acc`: one SSTORE
    behind a long loop in the interpreter."""
    st = StateStorage(MemoryStorage())
    st.set("s_code", SPIN, po.assemble([
        po._p(4), po.CALLDATALOAD, po.DUP1, po._p(rounds),  # [key, acc, n]
        po._l("loop"),
        po.DUP2, po.DUP1, 0x02, po.DUP2, po.ADD,  # MUL: acc * acc + n
        po.SWAP2, po.POP,
        po._p(1), po.SWAP1, po.SUB,                   # n - 1
        po.DUP1, po._r("loop"), po.JUMPI,
        po.POP, po.SWAP1, po.SSTORE, po.STOP,
    ]))
    st.set(TransactionExecutor.T_ABI, SPIN, SPIN_ABI.encode())
    return st


@pytest.mark.skipif(not nevm.available(), reason="libnevm.so not built")
def test_compute_bound_wave_equals_the_python_interpreters_serial_replay():
    kp = SUITE.generate_keypair(b"spin")
    txs = [Transaction(to=SPIN, input=abi_mod.encode_call(
        "spin(uint256)", [k + 1], H), nonce=f"spin{k}",
        block_limit=100).sign(SUITE, kp) for k in range(12)]
    label = "po-spin"
    otrace.stages(label).reset()
    ex = TransactionExecutor(SUITE, trace_label=label)
    st = _spin_state(2_000)
    assert ex.plan_dag(txs, st) == [list(range(12))]
    rcs = ex.execute_block_dag(txs, st, 1, 0)
    counts = otrace.stages(label).counters()
    assert (counts["dag_pooled_txs"], counts["evm_native_frames"]) == (0, 12)

    serial = _spin_state(2_000)
    ex2 = TransactionExecutor(SUITE)
    ex2.evm.native = False
    rcs2 = [ex2.execute_transaction(t, serial, 1, 0) for t in txs]
    assert [(r.status, r.gas_used, r.output, r.logs) for r in rcs] == \
        [(r.status, r.gas_used, r.output, r.logs) for r in rcs2]
    assert all(r.status == 0 for r in rcs)
    assert _dump(st) == _dump(serial)


def test_reference_replay_is_unchecked_uint256():
    bal, untouched, refused = REFERENCE.expected(
        [(b"a", b"b", 3), (b"b", b"a", 6)], {"prefund_balance": 2})
    assert bal == {b"a": 5, b"b": M256 - 1} and refused == []
    rc = {"status": 0, "output": "0x", "logEntries": []}
    assert REFERENCE.receipt_says(rc, (b"a", b"b", 3), False)
    for bad in ({"status": 14}, {"output": "0x00"},
                {"logEntries": [{"data": "0x"}]}):
        assert not REFERENCE.receipt_says(dict(rc, **bad), None, False)


# -- (d) the cell's four metrics, through the benchmark's own reader ----------

NEW_METRICS = {
    "dag_plan_ms_per_block": 1000 * 0.012 / 3,
    "dag_txs_per_wave": 3000 / 12,
    "dag_pooled_share": 100 * 2700 / 3000,
    "evm_native_share": 100.0,
}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_reads_the_stage_table(name):
    sys.path.insert(0, BENCH)  # the reader imports readers_util
    try:
        read = _bench_module("readers/status_ratio.py",
                             "po_status_ratio").read
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == name)
    # the planner's two are read in the SmallBank cell too
    assert entry["workloads"] == ["air4-parallelok.batch1k-serial"] + (
        ["air4-smallbank.batch1k-serial"] if name.startswith("dag_")
        and name != "dag_pooled_share" else [])
    assert entry["layer"] == "scheduler / executor"

    def status(blocks, txs, waves, pooled, frames, secs):
        return {"0": {"trace": {
            "stages": {"dag_plan": {"count": blocks, "seconds": secs}},
            "counters": {"dag_blocks": blocks, "dag_txs": txs,
                         "dag_waves": waves, "dag_pooled_txs": pooled,
                         "evm_frames": frames, "evm_native_frames": frames}}}}
    ev = {"status": {"before": status(2, 2000, 8, 1800, 2000, 0.01),
                     "after": status(5, 5000, 20, 4500, 5000, 0.022)}}
    assert read(ev, spec) == pytest.approx(NEW_METRICS[name])
    # the parent's status has no such stage or counter: nothing, no raise
    bare = {"0": {"trace": {"stages": {}, "counters": {}}}}
    assert read({"status": {"before": bare, "after": bare}}, spec) is None
