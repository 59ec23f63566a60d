"""Commit-coherent query cache (rpc/cache.py) — amortization + coherence.

The amortization claims: N identical getBlock-with-txs requests cost at
most ONE sender-recover batch (computed at commit or first touch, then
reused), and identical queries serve byte-for-byte identical responses.

The coherence claims (the reason a blockchain can cache at all): a
storage-commit ROLLBACK and a snap-sync SNAPSHOT INSTALL both wipe the
cache before any reader can observe the new state. State is verified via
`call` balance reads and c_balance rows, NOT state_root — the root is
per-changeset, so matching roots do not prove matching state.
"""

import http.client
import json
import threading
import time

import pytest

from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.rpc import server as rpc_server
from fisco_bcos_tpu.rpc.cache import QueryCache, RawResult
from fisco_bcos_tpu.sdk.client import SdkClient
from fisco_bcos_tpu.zk import proof as zkproof

from test_rpc_batch import (COHORT, NOTIFIER, cohort_node, cohort_txs,
                            hold_prime, send_cohort, wait_until)


class CountingSuite:
    """Delegating wrapper counting batch-recover crossings (the
    instrument behind the '<= 1 recover batch' assertion)."""

    def __init__(self, suite):
        self._suite = suite
        self.recover_calls = 0

    def __getattr__(self, name):
        return getattr(self._suite, name)

    def recover_addresses(self, hashes, sigs):
        self.recover_calls += 1
        return self._suite.recover_addresses(hashes, sigs)


def _register(node, kp, name: bytes, value: int, nonce: str):
    tx = Transaction(to=pc.BALANCE_ADDRESS,
                     input=pc.encode_call(
                         "register",
                         lambda w: w.blob(name).u64(value)),
                     nonce=nonce, block_limit=100).sign(node.suite, kp)
    rc = node.txpool.wait_for_receipt(node.send_transaction(tx).tx_hash, 30)
    assert rc is not None and rc.status == 0, rc
    return rc


def _balance(client: SdkClient, name: bytes) -> int:
    out = client.call(pc.BALANCE_ADDRESS,
                      pc.encode_call("balanceOf", lambda w: w.blob(name)))
    assert out["status"] == 0, out
    from fisco_bcos_tpu.codec.wire import Reader
    return Reader(bytes.fromhex(out["output"][2:])).u64()


def _post_fixed_id(node, method: str, params: list, rid: int = 424242
                   ) -> bytes:
    """Raw POST with a FIXED request id -> full response body bytes (the
    byte-for-byte comparison needs identical envelopes)."""
    body = json.dumps({"jsonrpc": "2.0", "id": rid, "method": method,
                       "params": params}).encode()
    conn = http.client.HTTPConnection(node.rpc.host, node.rpc.port,
                                      timeout=30)
    try:
        conn.request("POST", "/", body=body,
                     headers={"Content-Type": "application/json"})
        return conn.getresponse().read()
    finally:
        conn.close()


def test_identical_getblock_requests_cost_at_most_one_recover():
    """Satellite: the per-request `batch_recover_senders` is gone — the
    senders row is rendered once (commit prime or first touch) and N
    identical getBlockByNumber --includeTxs requests reuse it."""
    counting = CountingSuite(make_suite(False, backend="host"))
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0), suite=counting)
    node.start()
    try:
        kp = counting.generate_keypair(b"cache-recover")
        txs = [Transaction(to=pc.BALANCE_ADDRESS,
                           input=pc.encode_call(
                               "register",
                               lambda w, i=i: w.blob(b"cr%d" % i).u64(1)),
                           nonce=f"cr-{i}", block_limit=100
                           ).sign(counting, kp) for i in range(8)]
        node.txpool.submit_batch(txs)
        hashes = [tx.hash(counting) for tx in txs]
        for h in hashes:
            assert node.txpool.wait_for_receipt(h, 30) is not None
        head = node.ledger.current_number()
        assert head >= 1
        time.sleep(0.3)  # let the commit-prime observer finish rendering
        sdk = SdkClient(f"http://{node.rpc.host}:{node.rpc.port}")
        counting.recover_calls = 0
        blocks = [sdk.get_block_by_number(head) for _ in range(6)]
        assert all(b == blocks[0] for b in blocks)
        assert any("from" in tj for tj in blocks[0]["transactions"])
        assert counting.recover_calls <= 1, (
            f"{counting.recover_calls} recover batches for 6 identical "
            "getBlock requests — sender recovery is not amortized")
        stats = node.query_cache.stats()
        assert stats["hits"] >= 5
    finally:
        node.stop()


def test_commit_prime_reuses_admission_senders():
    """Priming must not re-pay the recover the admission batch already
    ran: the scheduler hands its LIVE (sender-populated) tx objects to
    prime_block, so submit -> seal -> commit -> prime costs exactly ONE
    recover batch end to end."""
    counting = CountingSuite(make_suite(False, backend="host"))
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0), suite=counting)
    node.start()
    try:
        kp = counting.generate_keypair(b"prime-reuse")
        counting.recover_calls = 0
        txs = [Transaction(to=pc.BALANCE_ADDRESS,
                           input=pc.encode_call(
                               "register",
                               lambda w, i=i: w.blob(b"pr%d" % i).u64(1)),
                           nonce=f"pr-{i}", block_limit=100
                           ).sign(counting, kp) for i in range(6)]
        # submit WIRE-decoded copies (sign() pre-populates _sender on the
        # local objects; a real client's txs arrive sender-less)
        txs = [Transaction.decode(tx.encode()) for tx in txs]
        node.txpool.submit_batch(txs)
        for tx in txs:
            assert node.txpool.wait_for_receipt(tx.hash(counting), 30)
        deadline = time.monotonic() + 5
        head = node.ledger.current_number()
        while time.monotonic() < deadline:  # prime observer settling
            if node.query_cache.get(("senders", head)) is not None:
                break
            time.sleep(0.05)
        assert node.query_cache.get(("senders", head)) is not None, \
            "commit prime never rendered the senders row"
        assert counting.recover_calls == 1, (
            f"{counting.recover_calls} recover batches for submit->commit"
            "->prime — priming re-recovered the admission's senders")
        # and the served block reuses that row too
        sdk = SdkClient(f"http://{node.rpc.host}:{node.rpc.port}")
        blk = sdk.get_block_by_number(head)
        assert all("from" in tj for tj in blk["transactions"])
        assert counting.recover_calls == 1
    finally:
        node.stop()


def test_send_transaction_retry_is_idempotent():
    """A client re-POSTing sendTransaction after a connection reset (the
    SdkClient bounded retry) must get the receipt back, not an
    ALREADY_IN_TXPOOL/ALREADY_KNOWN error — the duplicate statuses are
    benign on this path."""
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0))
    node.start()
    try:
        kp = node.suite.generate_keypair(b"retry-idem")
        tx = Transaction(to=pc.BALANCE_ADDRESS,
                         input=pc.encode_call(
                             "register", lambda w: w.blob(b"ri").u64(3)),
                         nonce="ri-0", block_limit=100).sign(node.suite, kp)
        sdk = SdkClient(f"http://{node.rpc.host}:{node.rpc.port}")
        wire = "0x" + tx.encode().hex()
        first = sdk.request("sendTransaction", ["group0", "", wire, False])
        assert first["status"] == 0
        # the "retry": same bytes again, after the tx committed
        second = sdk.request("sendTransaction", ["group0", "", wire, False])
        assert second["status"] == 0
        assert second["transactionHash"] == first["transactionHash"]
    finally:
        node.stop()


def test_identical_queries_serve_identical_bytes():
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0))
    node.start()
    try:
        kp = node.suite.generate_keypair(b"cache-bytes")
        rc = _register(node, kp, b"bytes", 5, "cb-0")
        n = rc.block_number
        r1 = _post_fixed_id(node, "getBlockByNumber",
                            ["group0", "", n, False, False])
        r2 = _post_fixed_id(node, "getBlockByNumber",
                            ["group0", "", n, False, False])
        assert r1 == r2 and b'"result"' in r1
        # receipts too
        tx_hash = json.loads(r1)["result"]["transactions"][0]["hash"]
        a = _post_fixed_id(node, "getTransactionReceipt",
                           ["group0", "", tx_hash, False])
        b = _post_fixed_id(node, "getTransactionReceipt",
                           ["group0", "", tx_hash, False])
        assert a == b
    finally:
        node.stop()


def test_no_stale_read_after_commit_rollback():
    """Satellite: a storage 2PC rollback invalidates the cache (the
    scheduler's on_invalidate hook), the chain retries and commits, and
    every post-rollback read reflects the really-committed state
    (balance spot-checks via RPC `call`)."""
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0))
    node.start()
    try:
        kp = node.suite.generate_keypair(b"cache-rb")
        _register(node, kp, b"rb-a", 7, "rb-0")
        sdk = SdkClient(f"http://{node.rpc.host}:{node.rpc.port}")
        blk1 = sdk.get_block_by_number(1)
        assert _balance(sdk, b"rb-a") == 7
        inv0 = node.query_cache.stats()["invalidations"]

        # inject ONE commit failure: scheduler rolls back, solo retries
        orig_commit = node.storage.commit
        state = {"tripped": False}

        def flaky(number):
            if not state["tripped"]:
                state["tripped"] = True
                raise RuntimeError("injected commit failure")
            return orig_commit(number)

        node.storage.commit = flaky
        _register(node, kp, b"rb-b", 9, "rb-1")  # survives the rollback
        node.storage.commit = orig_commit
        assert state["tripped"], "injection never fired"

        assert node.query_cache.stats()["invalidations"] > inv0, \
            "rollback did not invalidate the query cache"
        # post-rollback reads: committed state, not cached pre-rollback junk
        assert _balance(sdk, b"rb-b") == 9
        assert _balance(sdk, b"rb-a") == 7
        blk1_again = sdk.get_block_by_number(1)
        want = node.ledger.header_by_number(1).hash(node.suite)
        assert blk1_again["hash"] == "0x" + want.hex() == blk1["hash"]
    finally:
        node.stop()


def test_no_stale_read_after_snapshot_install():
    """Satellite: a snap-sync install jumps the head over WIPED tables;
    `Scheduler.external_commit` must invalidate the cache so neither the
    block JSON nor the balance reads serve the pre-install chain."""
    from fisco_bcos_tpu.snapshot import export_snapshot, install_snapshot

    suite = make_suite(False, backend="host")
    # source chain A: probe = 42, three blocks committed
    a = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0),
             suite=suite)
    a.start()
    kp_a = suite.generate_keypair(b"snap-a")
    _register(a, kp_a, b"probe", 42, "sa-0")
    _register(a, kp_a, b"other", 1, "sa-1")
    _register(a, kp_a, b"third", 2, "sa-2")
    a.stop()
    a_head = a.ledger.current_number()
    a_hash1 = "0x" + a.ledger.header_by_number(1).hash(suite).hex()
    manifest, chunks = export_snapshot(a.storage, a.ledger, suite,
                                       chunk_bytes=4096)

    # serving node B: DIFFERENT chain, same table names — probe = 7
    b = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                        rpc_port=0), suite=suite)
    b.start()
    try:
        kp_b = suite.generate_keypair(b"snap-b")
        _register(b, kp_b, b"probe", 7, "sb-0")
        assert b.ledger.current_number() < a_head
        sdk = SdkClient(f"http://{b.rpc.host}:{b.rpc.port}")
        # populate the cache with B's chain and verify B's state
        pre = sdk.get_block_by_number(1)
        assert pre["hash"] != a_hash1
        assert _balance(sdk, b"probe") == 7

        # snap-sync install (the sync/sync.py _try_snap_sync sequence:
        # invalidate BEFORE the install commit publishes the new state,
        # install into the LIVE storage, then external_commit — whose
        # second invalidation fences out renders in flight across it)
        b.scheduler.invalidate_caches(b.ledger.current_number())
        install_snapshot(manifest, chunks, b.storage, suite,
                         lambda header: True)
        b.scheduler.external_commit(manifest.height)

        # no stale reads: balance via RPC call AND the c_balance row
        assert _balance(sdk, b"probe") == 42, \
            "stale balance served after snapshot install"
        key = next(iter(a.storage.keys("c_balance")))
        assert b.storage.get("c_balance", key) == \
            a.storage.get("c_balance", key)
        post = sdk.get_block_by_number(1)
        assert post["hash"] == a_hash1, \
            "stale block JSON served after snapshot install"
        assert sdk.get_block_number() == a_head
    finally:
        b.stop()


def test_cache_lru_and_generation_fencing():
    """Unit: LRU eviction respects the entry bound; a put carrying a
    pre-invalidation generation is dropped (in-flight render fencing)."""
    c = QueryCache(max_entries=2)
    g = c.generation()
    c.put("a", {"v": 1}, g)
    c.put("b", {"v": 2}, g)
    assert c.get("a") == {"v": 1}  # refresh a: b is now LRU
    c.put("c", {"v": 3}, g)
    assert c.get("b") is None and c.get("a") is not None
    # generation fencing
    stale_gen = c.generation()
    c.invalidate()
    c.put("d", {"v": 4}, stale_gen)
    assert c.get("d") is None, "stale-generation render entered the cache"
    c.put("e", {"v": 5}, c.generation())
    assert c.get("e") == {"v": 5}
    stats = c.stats()
    assert stats["invalidations"] == 1 and stats["entries"] == 1


# ---------------------------------------------------------------------------
# the commit's one render pass (prime_block) against first-touch renders
# ---------------------------------------------------------------------------


def _commit_cohort(node, kp, tag: str):
    """One cohort block through the pool's batch door (no RPC worker in
    the way) -> (block number, tx hashes) once the prime has published."""
    txs = cohort_txs(node, kp, tag)
    hashes = [tx.hash(node.suite) for tx in txs]
    node.txpool.submit_batch([Transaction.decode(tx.encode()) for tx in txs])
    for h in hashes:
        assert node.txpool.wait_for_receipt(h, 30) is not None
    n = node.ledger.current_number()
    assert {node.ledger.receipt(h).block_number for h in hashes} == {n}
    assert wait_until(lambda: node.query_cache.get(("senders", n)))
    return n, hashes


def _published(cache, n: int, hashes: list) -> dict:
    """Every fragment the prime publishes for block n, parsed; a
    fragment's bytes and its dict say the same thing."""
    keys = [("block", n, False, False), ("block", n, False, True),
            ("block", n, True, False)]
    keys += [(kind, h) for h in hashes for kind in ("tx", "rc", "proof")]
    out = {}
    for key in keys:
        frag = cache.get(key)
        assert frag is not None, key
        if key[0] != "proof":
            assert isinstance(frag, RawResult), key
            assert json.loads(frag.raw) == json.loads(json.dumps(frag)), key
        out[key] = json.loads(json.dumps(frag))
    rows = cache.get(("logs", n))
    out["logs"] = [(log, json.loads(raw)) for log, raw in rows]
    out["senders"] = cache.get(("senders", n))
    return out


def _first_touch(node, n: int, hashes: list) -> dict:
    """The same keys rendered the cold way: an impl with no cache, the
    ledger's rows, `render_proof_doc`."""
    cold = rpc_server.JsonRpcImpl(node)
    cold.cache = None
    out = {("block", n, h_only, tx_only): cold.get_block_by_number(
        "group0", "", n, h_only, tx_only)
        for h_only, tx_only in ((False, False), (False, True),
                                (True, False))}
    logs = []
    for ti, h in enumerate(hashes):
        out[("tx", h)] = cold._tx_json_cached(h)
        out[("rc", h)] = cold._receipt_json_cached(h)
        out[("proof", h)] = zkproof.render_proof_doc(node.ledger, h)
        for idx, log in enumerate(node.ledger.receipt(h).logs):
            logs.append((log, {**out[("rc", h)]["logEntries"][idx],
                               "blockNumber": n,
                               "transactionHash": "0x" + h.hex(),
                               "transactionIndex": ti, "logIndex": idx}))
    out = json.loads(json.dumps({repr(k): v for k, v in out.items()}))
    out["logs"] = logs
    out["senders"] = [tx.sender(node.suite) for tx in (
        node.ledger.transaction(h) for h in hashes)]
    return out


class CountingCrypto(CountingSuite):
    """Also counts batch hashes, and says which thread asked."""

    def __init__(self, suite):
        super().__init__(suite)
        self.calls = []

    def recover_addresses(self, hashes, sigs):
        self.calls.append(("recover", threading.current_thread().name))
        return super().recover_addresses(hashes, sigs)

    def hash_batch(self, msgs):
        self.calls.append(("hash_batch", threading.current_thread().name))
        return self._suite.hash_batch(msgs)


@pytest.mark.parametrize("sm", [False, True], ids=["secp", "sm"])
def test_prime_publishes_what_first_touch_renders(sm):
    """After one commit every published fragment — the three block views,
    each tx, each receipt, the logs row, each proof document, the senders
    row — equals the first-touch render of the same key on a cold cache."""
    node, kp, _impl = cohort_node(sm)
    try:
        n, hashes = _commit_cohort(node, kp, "par")
        assert hashes == node.ledger.tx_hashes_by_number(n)
        got = _published(node.query_cache, n, hashes)
        want = _first_touch(node, n, hashes)
        assert len(got["logs"]) == COHORT - 2
        assert got.pop("logs") == want.pop("logs")
        assert got.pop("senders") == want.pop("senders")
        assert {repr(k): v for k, v in got.items()} == want
        full = got[("block", n, False, False)]
        assert [t["hash"] for t in full["transactions"]] \
            == got[("block", n, False, True)]["transactions"]
        assert all(t.get("from") for t in full["transactions"])
    finally:
        node.stop()


@pytest.mark.parametrize("sm", [False, True], ids=["secp", "sm"])
def test_prime_hashes_and_recovers_nothing(sm):
    """submit -> commit -> prime costs no hash_batch and no recover beyond
    admission's and execution's: the notifier thread, which renders the
    block from what the commit holds, asks the suite for neither, and an
    RPC cohort's worker neither."""
    counting = CountingCrypto(make_suite(sm, backend="host"))
    node = Node(NodeConfig(crypto_backend="host", sm_crypto=sm,
                           min_seal_time=0.0, rpc_port=0), suite=counting)
    node.start()
    try:
        kp = counting.generate_keypair(b"cohort-client")
        impl = node.rpc.impl
        _gate, primed = hold_prime(node, impl)
        send_cohort(node, cohort_txs(node, kp, "cnt")[3:])
        head = node.ledger.current_number()
        assert wait_until(lambda: head in primed)
        assert node.query_cache.get(("proof", node.ledger.
                                     tx_hashes_by_number(head)[0]))
        assert counting.calls, "the instrument saw nothing"
        off_path = [c for c in counting.calls
                    if c[1] == NOTIFIER or c[1].startswith("rpc")]
        assert not off_path, off_path
    finally:
        node.stop()


@pytest.mark.parametrize("sm", [False, True], ids=["secp", "sm"])
def test_stash_miss_takes_the_ledger_path(sm):
    """Restart, sync replay, snapshot install: no stash. The prime reads
    the block back and publishes the same fragments."""
    node, kp, impl = cohort_node(sm)
    try:
        n, hashes = _commit_cohort(node, kp, "miss")
        from_stash = _published(node.query_cache, n, hashes)
        node.scheduler.last_committed.clear()
        impl._frags.clear()
        node.query_cache.invalidate()
        assert node.query_cache.get(("rc", hashes[0])) is None
        impl.prime_block(n)
        from_ledger = _published(node.query_cache, n, hashes)
        assert from_ledger == from_stash
        # and a stash that is another chain's block is not believed
        node.scheduler.last_committed[n] = node.scheduler.last_committed.get(
            n - 1) or node.ledger.block_by_number(n - 1)
        impl._frags.clear()
        node.query_cache.invalidate()
        impl.prime_block(n)
        assert _published(node.query_cache, n, hashes) == from_stash
    finally:
        node.stop()


@pytest.mark.parametrize("runner", ["notifier", "worker"])
def test_a_pass_that_raced_an_invalidation_inserts_nothing(runner):
    """The generation is captured before the block's first read; an
    invalidation during the pass voids all of it — also the receipts a
    cohort's worker rendered before the notifier took the table over."""
    node, kp, impl = cohort_node(False)
    try:
        cache = node.query_cache
        gate, primed = hold_prime(node, impl)
        rows = impl.log_rows

        def raced(number):
            cache.invalidate()  # a rollback lands mid-pass
            return rows(number)

        impl.log_rows = raced
        txs = cohort_txs(node, kp, "race")
        hashes = [tx.hash(node.suite) for tx in txs]
        if runner == "worker":
            gate.clear()
            got = send_cohort(node, txs)  # the worker renders the receipts
            assert len(got) == COHORT and not primed - {1}
            gate.set()
        else:
            node.txpool.submit_batch(txs)
            for h in hashes:
                assert node.txpool.wait_for_receipt(h, 30) is not None
        head = node.ledger.current_number()
        assert wait_until(lambda: head in primed)
        assert cache.stats()["entries"] == 0, cache.stats()
        assert all(cache.get((kind, h)) is None for h in hashes
                   for kind in ("rc", "tx", "proof"))
        # the next pass, under the new generation, publishes
        impl.log_rows = rows
        impl.prime_block(head)
        assert cache.get(("rc", hashes[-1])) is not None
    finally:
        node.stop()


def test_put_many_is_one_fenced_transaction():
    reg_calls = []

    class Reg:
        def inc(self, *a, **k):
            pass

        def set_gauge(self, name, value, **k):
            reg_calls.append(name)

    c = QueryCache(max_entries=3, registry=Reg())
    g = c.generation()
    assert c.put_many([(k, {"v": k}, 10) for k in "abcd"], g) is True
    assert len(reg_calls) == 2, reg_calls  # one update of each gauge
    assert c.get("a") is None and c.get("d") == {"v": "d"}  # LRU bound
    assert c.stats()["bytes"] == 30
    c.invalidate()
    assert c.put_many([("e", {"v": 5}, 10)], g) is False
    assert c.get("e") is None and c.stats()["entries"] == 0


def test_once_guard_builds_a_part_once_under_contention():
    """More threads than cores ask one table for the same part at once,
    under a shortened switch interval: one build, one object for all."""
    import sys

    frags = rpc_server._BlockFragments(7, gen=0)
    builds, got, start = [], [], threading.Event()

    def build():
        builds.append(threading.current_thread().name)
        time.sleep(0.01)  # long enough for every thread to pile up
        return {"built": len(builds)}

    def ask():
        start.wait(5)
        got.append(frags.once("receipts", build))

    threads = [threading.Thread(target=ask) for _ in range(32)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        start.set()
        for t in threads:
            t.join(10)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(builds) == 1 and len(got) == 32
    assert all(g is got[0] for g in got) and got[0] == {"built": 1}
    assert frags.peek("receipts") is got[0] and frags.peek("logs") is None
