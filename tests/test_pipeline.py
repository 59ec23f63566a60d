"""Pipelined block production: off-thread ordered commit stage +
speculative next-height execution over a stacked state view.

Covers the pipeline's correctness contract: speculation reads through the
parent's UNCOMMITTED changeset yet `state_root` stays per-changeset; a
commit failure preserves strict height ordering (N+1 refuses to land
before N) and the retried chain commits byte-identically; an aborted
speculation (view change) discards the speculative tail but never a block
already on the commit stage; a crash between N's commit and N+1's leaves
a durable prefix that replays to the identical root; and — the point —
execute(N+1) demonstrably overlaps commit(N).
"""

import threading
import time

import pytest

from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.executor.executor import TransactionExecutor
from fisco_bcos_tpu.ledger.ledger import ConsensusNode, Ledger
from fisco_bcos_tpu.protocol import Block, BlockHeader, Transaction
from fisco_bcos_tpu.scheduler.scheduler import Scheduler
from fisco_bcos_tpu.storage.interface import Entry
from fisco_bcos_tpu.storage.memory import MemoryStorage
from fisco_bcos_tpu.storage.state import StackedStorageView, StateStorage
from fisco_bcos_tpu.txpool.txpool import TxPool


def make_stack(storage=None, pipeline=True):
    suite = make_suite(False, backend="host")
    storage = storage if storage is not None else MemoryStorage()
    ledger = Ledger(storage, suite)
    kp = suite.generate_keypair(b"pipe-node")
    ledger.build_genesis([ConsensusNode(kp.pub_bytes)])
    pool = TxPool(suite, ledger)
    sched = Scheduler(storage, ledger, TransactionExecutor(suite), suite,
                      pool, pipeline=pipeline)
    return suite, storage, ledger, pool, sched, kp


def reg_tx(suite, kp, name: bytes, value: int, nonce: str):
    return Transaction(to=pc.BALANCE_ADDRESS,
                       input=pc.encode_call(
                           "register",
                           lambda w: w.blob(name).u64(value)),
                       nonce=nonce, block_limit=100).sign(suite, kp)


def transfer_tx(suite, kp, frm: bytes, to: bytes, amount: int, nonce: str):
    return Transaction(to=pc.BALANCE_ADDRESS,
                       input=pc.encode_call(
                           "transfer",
                           lambda w: w.blob(frm).blob(to).u64(amount)),
                       nonce=nonce, block_limit=100).sign(suite, kp)


def make_block(number: int, kp, txs=None):
    return Block(header=BlockHeader(number=number,
                                    sealer_list=[kp.pub_bytes]),
                 transactions=list(txs or []))


# -- StackedStorageView ------------------------------------------------------

def test_stacked_view_layering():
    base = MemoryStorage()
    base.set("t", b"a", b"base-a")
    base.set("t", b"b", b"base-b")
    cs1 = {("t", b"a"): Entry(b"cs1-a"), ("t", b"c"): Entry(b"cs1-c")}
    cs2 = {("t", b"b"): Entry(b"", __import__(
        "fisco_bcos_tpu.storage.interface", fromlist=["EntryStatus"]
    ).EntryStatus.DELETED), ("t", b"d"): Entry(b"cs2-d")}
    view = StackedStorageView(base, [cs1, cs2])
    assert view.get("t", b"a") == b"cs1-a"     # older changeset wins base
    assert view.get("t", b"b") is None          # newest tombstone wins
    assert view.get("t", b"c") == b"cs1-c"
    assert view.get("t", b"d") == b"cs2-d"
    assert list(view.keys("t")) == [b"a", b"c", b"d"]
    with pytest.raises(RuntimeError):
        view.set("t", b"x", b"y")
    # an overlay over the view writes without touching it
    st = StateStorage(view)
    st.set("t", b"a", b"overlay")
    assert st.get("t", b"a") == b"overlay"
    assert view.get("t", b"a") == b"cs1-a"


# -- speculative execution ---------------------------------------------------

def test_speculative_execution_reads_uncommitted_parent():
    """Block 2 executes over block 1's NOT-yet-committed changeset: a
    transfer from an account block 1 registered succeeds only if the
    speculative read-through works — and each header's state_root stays
    the root of its OWN changeset."""
    suite, storage, ledger, pool, sched, kp = make_stack()
    b1 = make_block(1, kp, [reg_tx(suite, kp, b"alice", 100, "p1"),
                            reg_tx(suite, kp, b"bob", 1, "p2")])
    r1 = sched.execute_block(b1)
    assert r1 is not None
    b2 = make_block(2, kp, [transfer_tx(suite, kp, b"alice", b"bob", 40,
                                        "p3")])
    r2 = sched.execute_block(b2)  # block 1 is NOT committed yet
    assert r2 is not None
    assert sched.pipeline_stats()["speculative_execs"] == 1
    [rc] = r2.receipts
    assert rc.status == 0, rc.message  # the transfer saw alice's balance
    # per-changeset roots: block 2's changeset must not contain block 1's
    # register rows, and the two roots differ
    assert r1.header.state_root != r2.header.state_root
    b1_keys = set(r1.changes)
    assert all(k not in b1_keys or sched.executor.state_root(
        {k: r2.changes[k]}) for k in r2.changes)
    # commit in order; the durable state reflects both blocks
    assert sched.commit_block(r1.header)
    assert sched.commit_block(r2.header)
    assert ledger.current_number() == 2
    st = StateStorage(storage)
    bal = sched.call(Transaction(
        to=pc.BALANCE_ADDRESS,
        input=pc.encode_call("balanceOf", lambda w: w.blob(b"bob")),
        nonce="q1", block_limit=100).sign(suite, kp))
    from fisco_bcos_tpu.codec.wire import Reader
    assert Reader(bal.output).u64() == 41


def test_speculative_root_matches_serial_root():
    """The speculative N+1 produces the byte-identical header a strictly
    serial execute-after-commit produces (determinism across the two
    scheduling shapes — replicas may mix them freely)."""
    txs1 = lambda s, k: [reg_tx(s, k, b"acct-x", 10, "d1")]  # noqa: E731
    txs2 = lambda s, k: [transfer_tx(s, k, b"acct-x", b"acct-x", 0, "d2"),
                         reg_tx(s, k, b"acct-y", 3, "d3")]  # noqa: E731

    # pipelined: execute 1 and 2 back to back, then commit both
    suite, _, _, _, sp, kp = make_stack()
    r1 = sp.execute_block(make_block(1, kp, txs1(suite, kp)))
    r2 = sp.execute_block(make_block(2, kp, txs2(suite, kp)))
    assert sp.commit_block(r1.header) and sp.commit_block(r2.header)

    # serial: commit 1 before touching 2 (pipeline disabled)
    suite2, _, _, _, ss, kp2 = make_stack(pipeline=False)
    q1 = ss.execute_block(make_block(1, kp2, txs1(suite2, kp2)))
    assert ss.commit_block(q1.header)
    q2 = ss.execute_block(make_block(2, kp2, txs2(suite2, kp2)))
    assert ss.commit_block(q2.header)

    assert r1.header.state_root == q1.header.state_root
    assert r2.header.state_root == q2.header.state_root
    assert r2.header.txs_root == q2.header.txs_root


def test_commit_failure_keeps_strict_order_and_retries():
    """N's transient 2PC failure must not let N+1 land first (strict
    height ordering), and the preserved chain commits on retry — the
    speculative N+1 result stays valid because N's changeset is
    preserved byte-identically."""
    suite, storage, ledger, pool, sched, kp = make_stack()
    r1 = sched.execute_block(make_block(1, kp,
                                        [reg_tx(suite, kp, b"f1", 5, "f1")]))
    r2 = sched.execute_block(make_block(2, kp,
                                        [reg_tx(suite, kp, b"f2", 6, "f2")]))
    fails = {"n": 1}
    orig_prepare = storage.prepare

    def flaky(number, changes):
        if fails["n"]:
            fails["n"] -= 1
            raise RuntimeError("transient storage failure")
        return orig_prepare(number, changes)

    storage.prepare = flaky
    try:
        assert not sched.commit_block(r1.header)   # transient failure
        assert not sched.commit_block(r2.header)   # refused: out of order
        assert ledger.current_number() == 0        # nothing landed
        assert sched.commit_block(r1.header)       # retry succeeds
        assert sched.commit_block(r2.header)       # N+1 still valid
    finally:
        storage.prepare = orig_prepare
    assert ledger.current_number() == 2


def test_abort_speculation_discards_tail_keeps_committing():
    """A view change aborts the speculative chain — but a block already
    handed to the commit stage (checkpoint quorum) is kept and lands."""
    suite, storage, ledger, pool, sched, kp = make_stack()
    r1 = sched.execute_block(make_block(1, kp,
                                        [reg_tx(suite, kp, b"v1", 5, "v1")]))
    r2 = sched.execute_block(make_block(2, kp,
                                        [reg_tx(suite, kp, b"v2", 6, "v2")]))
    assert sched.next_executable() == 3
    # hold block 1's commit open on the commit stage
    gate = threading.Event()
    entered = threading.Event()
    orig_commit = storage.commit

    def gated(number):
        entered.set()
        assert gate.wait(20)
        return orig_commit(number)

    storage.commit = gated
    done = threading.Event()
    results = {}
    try:
        sched.commit_async(r1.header,
                           lambda ok: (results.__setitem__("ok", ok),
                                       done.set()))
        assert entered.wait(10)         # commit of 1 is mid-2PC
        dropped = sched.abort_speculation()
        assert dropped == 1             # block 2 discarded, block 1 kept
        gate.set()
        assert done.wait(10) and results["ok"]
    finally:
        gate.set()
        storage.commit = orig_commit
    assert ledger.current_number() == 1
    assert sched.next_executable() == 2
    # the discarded speculative block can never commit...
    assert not sched.commit_block(r2.header)
    # ...and a fresh block 2 executes against the durable head
    n2 = sched.execute_block(make_block(2, kp,
                                        [reg_tx(suite, kp, b"v3", 7, "v3")]))
    assert n2 is not None and sched.commit_block(n2.header)
    assert ledger.current_number() == 2


def test_execute_genuinely_overlaps_commit():
    """The instrumented overlap assertion: while block 1's 2PC is held
    open on the commit thread, block 2's execution starts AND finishes on
    the caller thread — the pipeline's defining behavior."""
    suite, storage, ledger, pool, sched, kp = make_stack()
    r1 = sched.execute_block(make_block(1, kp,
                                        [reg_tx(suite, kp, b"o1", 5, "o1")]))
    gate = threading.Event()
    entered = threading.Event()
    orig_commit = storage.commit

    def gated(number):
        entered.set()
        assert gate.wait(20)
        return orig_commit(number)

    storage.commit = gated
    done = threading.Event()
    try:
        sched.commit_async(r1.header, lambda ok: done.set())
        assert entered.wait(10)          # commit(1) is in flight
        t0 = time.monotonic()
        r2 = sched.execute_block(make_block(
            2, kp, [reg_tx(suite, kp, b"o2", 6, "o2")]))
        t_exec = time.monotonic() - t0
        assert r2 is not None            # executed WHILE commit(1) ran
        assert not done.is_set(), "commit finished before execute proved overlap"
        stats = sched.pipeline_stats()
        assert stats["overlap_commits"] >= 1
        assert stats["speculative_execs"] >= 1
        gate.set()
        assert done.wait(10)
    finally:
        gate.set()
        storage.commit = orig_commit
    assert sched.commit_block(r2.header)
    assert ledger.current_number() == 2
    assert t_exec < 20  # sanity: execute did not wait for the gate


def test_drop_executed_cascades_to_children():
    suite, storage, ledger, pool, sched, kp = make_stack()
    r1 = sched.execute_block(make_block(1, kp,
                                        [reg_tx(suite, kp, b"c1", 5, "c1")]))
    r2 = sched.execute_block(make_block(2, kp,
                                        [reg_tx(suite, kp, b"c2", 6, "c2")]))
    sched.drop_executed(r1.header)
    assert sched.next_executable() == 1  # both gone: 2 read through 1
    assert not sched.commit_block(r2.header)


def test_crash_between_commits_replays_to_identical_root(tmp_path):
    """kill -9 window: N committed durably (WAL fsync), N+1 executed
    speculatively but NOT committed. Recovery must come up at N exactly,
    and re-executing N+1 must reproduce the identical header — so a
    rejoining node converges on the same chain."""
    from fisco_bcos_tpu.storage.wal import WalStorage

    path = str(tmp_path / "db")
    storage = WalStorage(path)
    suite, _, ledger, pool, sched, kp = make_stack(storage=storage)
    r1 = sched.execute_block(make_block(1, kp,
                                        [reg_tx(suite, kp, b"k1", 5, "k1")]))
    assert sched.commit_block(r1.header)
    b2_txs = [transfer_tx(suite, kp, b"k1", b"k1", 0, "k2")]
    r2 = sched.execute_block(make_block(2, kp, list(b2_txs)))
    assert r2 is not None
    spec_hash = r2.header.hash(suite)
    spec_root = r2.header.state_root
    storage.close()  # the process dies here: block 2 never reached the WAL

    recovered = WalStorage(path)
    led2 = Ledger(recovered, suite)
    assert led2.current_number() == 1  # the speculative block left no trace
    assert led2.header_by_number(2) is None
    assert led2.header_by_number(1).state_root == r1.header.state_root
    sched2 = Scheduler(recovered, led2, TransactionExecutor(suite), suite,
                       None)
    rb2 = sched2.execute_block(make_block(2, kp, list(b2_txs)))
    assert rb2 is not None
    assert rb2.header.hash(suite) == spec_hash
    assert rb2.header.state_root == spec_root
    assert sched2.commit_block(rb2.header)
    assert led2.current_number() == 2
    recovered.close()


def test_last_committed_ordered_eviction():
    suite, storage, ledger, pool, sched, kp = make_stack()
    for i in range(1, 11):
        r = sched.execute_block(make_block(
            i, kp, [reg_tx(suite, kp, b"e%d" % i, 1, "e%d" % i)]))
        assert sched.commit_block(r.header)
    keys = list(sched.last_committed)
    assert keys == list(range(3, 11))  # oldest evicted in commit order
    # what the commit hands over is the block as committed: the live txs
    # and receipts, the tx hashes, the header with the ledger's hash
    blk = sched.last_committed[10]
    assert blk.tx_hashes == ledger.tx_hashes_by_number(10)
    assert [rc.encode() for rc in blk.receipts] == [
        ledger.receipt(h).encode() for h in blk.tx_hashes]
    assert blk.header.hash(suite) == ledger.header_by_number(10).hash(suite)


# -- sealer busy-fill --------------------------------------------------------

def test_sealer_keeps_filling_while_pipeline_busy():
    """Driven synchronously (no worker thread): a busy pipeline defers a
    partial proposal up to max_seal_time; an idle one seals at
    min_seal_time; a FULL block seals regardless."""
    from fisco_bcos_tpu.sealer.sealer import Sealer

    suite, storage, ledger, pool, sched, kp = make_stack()
    proposals = []
    busy = {"v": True}
    sealer = Sealer(pool, suite, lambda b: (proposals.append(b), True)[1],
                    max_txs_per_block=10, min_seal_time=0.0,
                    max_seal_time=5.0, pipeline_busy=lambda: busy["v"])
    pool.submit_batch([reg_tx(suite, kp, b"s%d" % i, 1, f"s{i}")
                       for i in range(3)])
    sealer.grant(1, 0)
    sealer.execute_worker()
    assert not proposals, "partial block sealed despite a busy pipeline"
    # pipeline drains -> the same partial block seals immediately
    busy["v"] = False
    sealer.execute_worker()
    assert len(proposals) == 1 and len(proposals[0].transactions) == 3
    # a FULL block never waits, busy or not
    busy["v"] = True
    pool.submit_batch([reg_tx(suite, kp, b"t%d" % i, 1, f"t{i}")
                       for i in range(10)])
    sealer.grant(2, 0)
    sealer.execute_worker()
    assert len(proposals) == 2 and len(proposals[1].transactions) == 10
    # busy-fill is a window, not a wedge: past max_seal_time it seals
    busy_sealer_txs = [reg_tx(suite, kp, b"u%d" % i, 1, f"u{i}")
                       for i in range(2)]
    pool.submit_batch(busy_sealer_txs)
    sealer.grant(3, 0)
    sealer.execute_worker()
    assert len(proposals) == 2  # still filling
    sealer._seal_wait.t0 = time.monotonic() - 6.0  # window elapsed
    sealer.execute_worker()
    assert len(proposals) == 3


# -- live cluster ------------------------------------------------------------

def test_pbft_cluster_pipelines_under_load():
    """4-node chain with a slowed commit on node 0: the next height's
    execution provably runs speculatively while the previous commit is in
    flight, and every node converges on the identical chain."""
    from tests.test_pbft import build_cluster, stop_cluster, wait_until

    suite, gateway, nodes, _ = build_cluster(4, tx_count_limit=25)
    try:
        # slow node 0's storage commit so commit(N) reliably overlaps the
        # consensus+execution of N+1
        orig = nodes[0].storage.commit

        def slow_commit(number, _orig=orig):
            time.sleep(0.15)
            return _orig(number)

        nodes[0].storage.commit = slow_commit
        kp = suite.generate_keypair(b"pipe-load")
        txs = [reg_tx(suite, kp, b"pl%d" % i, 1, f"pl-{i}")
               for i in range(100)]  # 4 blocks of 25
        nodes[0].txpool.submit_batch(txs)
        assert wait_until(
            lambda: all(n.ledger.total_tx_count() >= 100 for n in nodes),
            timeout=60), [n.ledger.total_tx_count() for n in nodes]
        stats = nodes[0].scheduler.pipeline_stats()
        assert stats["speculative_execs"] >= 1, stats
        head = nodes[0].ledger.current_number()
        h0 = nodes[0].ledger.header_by_number(head).hash(suite)
        for n in nodes[1:]:
            assert n.ledger.header_by_number(head).hash(suite) == h0
        for n in nodes:
            assert n.ledger.total_tx_count() == 100
    finally:
        stop_cluster(gateway, nodes)
