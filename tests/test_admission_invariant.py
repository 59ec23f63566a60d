"""The pool's admission invariant (txpool/txpool.py `submit_columns`): a
transaction that the ledger holds is never inserted into the pool, whenever
its block committed.

The ledger look runs off the pool's lock and the lock is dropped across the
batch verify, so a block can commit (a) while the cohort is inside its
verify, or (b) between the ledger look and the pre-phase's lock. Either way
every row answers ALREADY_KNOWN, the pool stays empty, nothing is sealable
and no waiter is left hanging — whichever way the cohort came in. Made
deterministic by doubles, no sleeps: the suite commits the block from
inside `recover_addresses`, the ledger commits it from inside its last
look.
"""

import pytest

from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.net.moduleid import ModuleID
from fisco_bcos_tpu.net.txsync import TransactionSync, _pack_txs
from fisco_bcos_tpu.protocol import Transaction, TransactionStatus
from fisco_bcos_tpu.protocol.columnar import decode_columns
from fisco_bcos_tpu.txpool import IngestLane, TxPool

from tests.test_ingest import _tx

N = 6
WAYS_IN = ["submit_batch", "submit_columns", "lane", "fetch_missing"]


class _Ledger:
    """What the pool reads of a ledger, plus `hook`: called after every
    look with the number of looks so far."""

    def __init__(self):
        self.number = 0
        self.receipts: dict = {}
        self.looks = 0
        self.hook = None

    def current_number(self):
        return self.number

    def nonces_by_number(self, _bn):
        return []

    def receipt(self, h):
        rc = self.receipts.get(h)
        self.looks += 1
        if self.hook is not None:
            self.hook(self.looks)
        return rc


class _CommitsInVerify:
    """Suite double: the first batch verify commits the block first."""

    def __init__(self, suite):
        self._suite = suite
        self.commit = None

    def __getattr__(self, name):
        return getattr(self._suite, name)

    def recover_addresses(self, hashes, sigs):
        commit, self.commit = self.commit, None
        if commit is not None:
            commit()
        return self._suite.recover_addresses(hashes, sigs)


class _Front:
    """The front a TransactionSync needs to answer one fetch."""

    def __init__(self):
        self.response = None

    def register_module(self, _module, _fn):
        pass

    def peers(self):
        return []

    def request(self, module, _peer, _req, _timeout):
        assert module == ModuleID.TxsSync
        return self.response


@pytest.fixture()
def world():
    suite = _CommitsInVerify(make_suite(False, backend="host"))
    ledger = _Ledger()
    pool = TxPool(suite, ledger)
    kp = suite.generate_keypair(b"invariant")
    txs = [_tx(suite, kp, i) for i in range(N)]
    hashes = [t.hash(suite) for t in txs]

    def commit():
        """The block that holds the cohort: the ledger first, the pool
        told after it, as the scheduler's notify does."""
        ledger.number += 1
        for h in hashes:
            ledger.receipts[h] = ("receipt", h)
        pool.on_block_committed(ledger.number, hashes,
                                [t.nonce for t in txs])

    return suite, ledger, pool, txs, hashes, commit


def _send(way_in, pool, suite, txs, hashes):
    """The cohort through one way in -> its per-row results."""
    wires = [t.encode() for t in txs]
    if way_in == "submit_batch":
        return pool.submit_batch([Transaction.decode(w) for w in wires])
    if way_in == "submit_columns":
        return pool.submit_columns(decode_columns(wires))
    if way_in == "lane":
        lane = IngestLane(pool, max_batch=64, max_wait_ms=0.0)
        lane.start()
        try:
            return [t.result(30) for t in lane.submit_wire_cohort(wires)]
        finally:
            lane.stop()
    # fetch_missing: a proposal's transactions fetched from its leader
    front = _Front()
    sync = TransactionSync(front, pool, suite)
    front.response = _pack_txs(txs, suite)
    seen: dict = {}
    admit = pool.submit_columns

    def spy(cols, broadcast=True, consensus=False):
        seen["consensus"] = consensus
        seen["results"] = admit(cols, broadcast, consensus)
        return seen["results"]

    pool.submit_columns = spy
    assert sync.fetch_missing(b"\x02" * 64, hashes), \
        "committed transactions are no reason to refuse the proposal"
    assert seen["consensus"] is True
    return seen["results"]


def _assert_none_entered(pool, ledger, results, hashes):
    assert [r.status for r in results] == \
        [TransactionStatus.ALREADY_KNOWN] * N
    assert [r.tx_hash for r in results] == hashes
    assert pool.status()["pending"] == 0 and pool.pending_count() == 0
    assert pool.seal(N) == ([], []), "a committed transaction is sealable"
    assert pool.missing_hashes(hashes) == hashes
    # no waiter left hanging: the receipt is there for whoever asks
    assert not pool._async_waiters
    ledger.hook = None
    assert pool.wait_for_receipt(hashes[0], timeout=1.0) == \
        ("receipt", hashes[0])


@pytest.mark.parametrize("way_in", WAYS_IN)
def test_block_commits_while_cohort_is_in_its_verify(world, way_in):
    suite, ledger, pool, txs, hashes, commit = world
    suite.commit = commit
    results = _send(way_in, pool, suite, txs, hashes)
    assert suite.commit is None, "the verify never ran"
    assert ledger.number == 1
    _assert_none_entered(pool, ledger, results, hashes)


@pytest.mark.parametrize("way_in", WAYS_IN)
def test_block_commits_between_ledger_look_and_prephase_lock(world, way_in):
    suite, ledger, pool, txs, hashes, commit = world

    def after_last_look(looks):
        if looks == N:  # the look saw none of them; now the block lands
            commit()

    ledger.hook = after_last_look
    results = _send(way_in, pool, suite, txs, hashes)
    assert ledger.number == 1 and ledger.looks > N
    _assert_none_entered(pool, ledger, results, hashes)


@pytest.mark.parametrize("way_in", WAYS_IN)
def test_no_commit_means_one_look_and_every_row_admitted(world, way_in):
    """The other side of the compare: where nothing commits during an
    admission the ledger is looked at once a row, and every row enters."""
    suite, ledger, pool, txs, hashes, _commit = world
    results = _send(way_in, pool, suite, txs, hashes)
    assert [r.status for r in results] == [TransactionStatus.OK] * N
    assert ledger.looks == N
    assert pool.pending_count() == N and not pool.missing_hashes(hashes)


def test_other_blocks_commit_during_verify_and_the_cohort_still_enters(world):
    """A commit that does not hold the cohort costs a second look and
    nothing else."""
    suite, ledger, pool, txs, hashes, _commit = world

    def commit_another():
        ledger.number += 1
        pool.on_block_committed(ledger.number, [b"\x07" * 32], ["x"])

    suite.commit = commit_another
    results = pool.submit_columns(decode_columns([t.encode() for t in txs]))
    assert [r.status for r in results] == [TransactionStatus.OK] * N
    assert ledger.looks == 2 * N
    assert pool.pending_count() == N
