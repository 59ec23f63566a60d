"""Generic DAG critical-field analysis (VERDICT r3 #7).

Reference counterpart: bcos-executor/src/dag/CriticalFields.h:45-60 —
conflict keys derived generically from parallel-contract annotations.
Here: every precompile self-describes via Precompile.conflict_keys, and
EVM contracts opt in with a `"parallel": N` ABI annotation. Mixed blocks
must plan into parallel waves, and the DAG schedule must equal the
serial schedule bit-for-bit.
"""

import json

from fisco_bcos_tpu.codec import abi as abi_mod
from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.executor.executor import TransactionExecutor
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.storage.memory import MemoryStorage
from fisco_bcos_tpu.storage.state import StateStorage

SUITE = make_suite(backend="host")


def make_tx(suite, kp, to, input_, nonce):
    return Transaction(to=to, input=input_, nonce=nonce,
                       block_limit=100).sign(suite, kp)


def fresh():
    ex = TransactionExecutor(SUITE)
    st = StateStorage(MemoryStorage())
    kp = SUITE.generate_keypair(b"dag-criticals")
    return ex, st, kp


def balance_tx(kp, nonce, method, *args):
    def build(w):
        for a in args:
            w.blob(a) if isinstance(a, bytes) else w.u64(a)
    return make_tx(SUITE, kp, pc.BALANCE_ADDRESS,
                   pc.encode_call(method, build), nonce)


def kv_tx(kp, nonce, table, key, value):
    return make_tx(SUITE, kp, pc.KV_TABLE_ADDRESS,
                   pc.encode_call("set", lambda w: (w.text(table),
                                                    w.blob(key),
                                                    w.blob(value))), nonce)


def test_disjoint_precompile_txs_one_wave():
    ex, st, kp = fresh()
    txs = [balance_tx(kp, f"r{i}", "register", b"acct%d" % i, 100)
           for i in range(4)]
    txs += [kv_tx(kp, f"k{i}", "t1", b"key%d" % i, b"v") for i in range(3)]
    waves = ex.plan_dag(txs, st)
    assert len(waves) == 1 and sorted(waves[0]) == list(range(7))


def test_conflicting_transfers_chain_waves():
    ex, st, kp = fresh()
    # A->B, B->C (conflict on B), D->E (independent)
    txs = [balance_tx(kp, "t1", "transfer", b"A", b"B", 1),
           balance_tx(kp, "t2", "transfer", b"B", b"C", 1),
           balance_tx(kp, "t3", "transfer", b"D", b"E", 1)]
    waves = ex.plan_dag(txs, st)
    assert len(waves) == 2
    assert sorted(waves[0]) == [0, 2] and waves[1] == [1]


def test_opaque_tx_is_a_barrier():
    ex, st, kp = fresh()
    opaque = make_tx(SUITE, kp, b"\x77" * 20, b"\x01\x02", "op")
    txs = [balance_tx(kp, "b1", "register", b"X", 1),
           opaque,
           balance_tx(kp, "b2", "register", b"Y", 1)]
    waves = ex.plan_dag(txs, st)
    assert waves == [[0], [1], [2]]


PARALLEL_ABI = json.dumps([{
    "type": "function", "name": "setAcct",
    "inputs": [{"type": "uint256"}, {"type": "uint256"}],
    "parallel": 1,
}])

# setAcct(uint256 slot, uint256 value): SSTORE(slot, value)
SET_ACCT_CODE = bytes([0x60, 36, 0x35,   # PUSH1 36 CALLDATALOAD (value)
                       0x60, 4, 0x35,    # PUSH1 4  CALLDATALOAD (slot)
                       0x55, 0x00])      # SSTORE STOP


def evm_tx(kp, nonce, contract, slot, value):
    data = abi_mod.encode_call("setAcct(uint256,uint256)", [slot, value],
                               SUITE.hash)
    return make_tx(SUITE, kp, contract, data, nonce)


def test_evm_parallel_annotation_waves_and_determinism():
    ex, st, kp = fresh()
    contract = b"\x55" * 20
    st.set("s_code", contract, SET_ACCT_CODE)
    st.set(ex.T_ABI, contract, PARALLEL_ABI.encode())
    # slots 1,2,3 disjoint; second write to slot 1 conflicts
    txs = [evm_tx(kp, "e1", contract, 1, 10),
           evm_tx(kp, "e2", contract, 2, 20),
           evm_tx(kp, "e3", contract, 3, 30),
           evm_tx(kp, "e4", contract, 1, 40)]
    waves = ex.plan_dag(txs, st)
    assert len(waves) == 2
    assert sorted(waves[0]) == [0, 1, 2] and waves[1] == [3]

    # same calldata WITHOUT the annotation: opaque singleton waves
    st2 = StateStorage(MemoryStorage())
    st2.set("s_code", contract, SET_ACCT_CODE)
    assert ex.plan_dag(txs, st2) == [[0], [1], [2], [3]]


def test_mixed_block_dag_equals_serial():
    """Determinism: the wave schedule must produce identical receipts and
    state as strict serial execution, on a block mixing annotated EVM,
    precompiles and an opaque barrier."""
    contract = b"\x55" * 20

    def build_block(ex, st, kp):
        st.set("s_code", contract, SET_ACCT_CODE)
        st.set(ex.T_ABI, contract, PARALLEL_ABI.encode())
        txs = [balance_tx(kp, "r1", "register", b"A", 100),
               balance_tx(kp, "r2", "register", b"B", 50),
               evm_tx(kp, "e1", contract, 7, 70),
               balance_tx(kp, "t1", "transfer", b"A", b"B", 10),
               evm_tx(kp, "e2", contract, 8, 80),
               kv_tx(kp, "k1", "t2", b"k", b"v1"),
               evm_tx(kp, "e3", contract, 7, 71),
               balance_tx(kp, "t2", "transfer", b"B", b"A", 5)]
        return txs

    ex1, st1, kp = fresh()
    txs = build_block(ex1, st1, kp)
    dag_receipts = ex1.execute_block_dag(txs, st1, 1, 0)

    ex2, st2, _ = fresh()
    build_block(ex2, st2, kp)
    serial_receipts = [ex2.execute_transaction(t, st2, 1, 0) for t in txs]

    assert [(r.status, r.gas_used, r.output) for r in dag_receipts] == \
        [(r.status, r.gas_used, r.output) for r in serial_receipts]
    assert sorted(st1.changeset().items()) == sorted(st2.changeset().items())


def test_wide_wave_execution_equals_serial():
    """Waves of six independent transactions, precompile and EVM, run in
    wave order must be bit-identical to block-order serial execution —
    receipts AND state."""
    contract = b"\x55" * 20

    def build(ex, st, kp):
        st.set("s_code", contract, SET_ACCT_CODE)
        st.set(ex.T_ABI, contract, PARALLEL_ABI.encode())
        txs = [balance_tx(kp, f"pr{i}", "register", b"P%d" % i, 100)
               for i in range(6)]
        txs += [evm_tx(kp, f"pe{i}", contract, i + 1, i * 10)
                for i in range(6)]
        txs += [balance_tx(kp, "pt", "transfer", b"P0", b"P1", 5)]
        return txs

    results = []
    for dag in (True, False):
        ex, st, kp = fresh()
        txs = build(ex, st, kp)
        if dag:
            assert max(map(len, ex.plan_dag(txs, st))) >= 6
            rcs = ex.execute_block_dag(txs, st, 1, 0)
        else:
            rcs = [ex.execute_transaction(t, st, 1, 0) for t in txs]
        results.append((
            [(r.status, r.gas_used, r.output) for r in rcs],
            sorted(st.changeset().items()),
        ))
    assert results[0] == results[1]


def test_create_table_then_set_same_block():
    """createTable must act as a barrier: a set to the just-created table
    later in the same block sees it."""
    ex, st, kp = fresh()
    txs = [make_tx(SUITE, kp, pc.KV_TABLE_ADDRESS,
                   pc.encode_call("createTable",
                                  lambda w: w.text("tnew")), "ct"),
           kv_tx(kp, "cs1", "tnew", b"k1", b"v1"),
           kv_tx(kp, "cs2", "tnew", b"k2", b"v2")]
    rcs = ex.execute_block_dag(txs, st, 1, 0)
    assert [r.status for r in rcs] == [0, 0, 0], \
        [(r.status, r.message) for r in rcs]
    assert st.get("u_tnew", b"k1") == b"v1"
    waves = ex.plan_dag(txs, st)
    assert waves[0] == [0]  # createTable is a barrier wave
