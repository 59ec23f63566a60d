"""Shared crypto lane (crypto/lane.py).

Asserts the lane's contract: concurrent batch submissions from >= 2
callers (groups) merge into ONE base-suite device call (counted with an
instrumented suite + the gated-dispatch idiom from tests/test_ingest.py,
so coalescing is deterministic on the 2-core host), results demux
positionally (a failed verify in one group's slice never poisons another
group's verdicts), a dispatch error rejects exactly the merged cohort and
the lane survives it, and `LaneSuite` preserves the full CryptoSuite
surface (delegation + tiny-batch bypass).
"""

import threading

import numpy as np
import pytest

from fisco_bcos_tpu.crypto.lane import CryptoLane, LaneSuite
from fisco_bcos_tpu.crypto.suite import make_suite


class CountingSuite:
    """Delegating wrapper counting (and optionally gating) batch entry
    points — the instrument behind every "calls == 1" assertion here."""

    def __init__(self, suite):
        self._suite = suite
        self.recover_calls = 0
        self.verify_calls = 0
        self.hash_calls = 0
        self.recover_sizes = []
        self.verify_sizes = []
        self.gate = None      # threading.Event: first call parks on it
        self.entered = threading.Event()
        self.fail_next = None  # exception to raise on the next batch call

    def __getattr__(self, name):
        return getattr(self._suite, name)

    def _maybe_gate(self):
        if self.fail_next is not None:
            exc, self.fail_next = self.fail_next, None
            raise exc
        if self.gate is not None:
            self.entered.set()
            gate, self.gate = self.gate, None  # first call only
            assert gate.wait(30)

    def recover_batch(self, digests, sigs):
        self.recover_calls += 1
        self.recover_sizes.append(len(digests))
        self._maybe_gate()
        return self._suite.recover_batch(digests, sigs)

    def verify_batch(self, digests, sigs, pubs):
        self.verify_calls += 1
        self.verify_sizes.append(len(digests))
        self._maybe_gate()
        return self._suite.verify_batch(digests, sigs, pubs)

    def hash_batch(self, msgs):
        self.hash_calls += 1
        self._maybe_gate()
        return self._suite.hash_batch(msgs)


def _sigs(suite, kp, n, valid=True):
    """n (digest, sig) pairs; invalid ones are deterministically
    unrecoverable (r > curve order)."""
    digests, sigs = [], []
    for i in range(n):
        d = suite.hash(b"lane-msg-%d" % i)
        g = suite.sign(kp, d)
        if not valid:
            g = b"\xff" * 32 + g[32:]
        digests.append(d)
        sigs.append(g)
    return digests, sigs


@pytest.fixture()
def lane_pair():
    counting = CountingSuite(make_suite(False, backend="host"))
    # host_workers=1: the "exactly ONE base call" assertions below count
    # LANE dispatches — the host path's intra-call core fan-out (covered
    # by test_host_fan_out_preserves_results) would split the counter
    lane = CryptoLane(counting, host_workers=1)
    a = LaneSuite(lane, tag="group0")
    b = LaneSuite(lane, tag="group1")
    yield counting, lane, a, b
    lane.stop()


def _gated_concurrent(counting, lane, calls, probe_op="hash"):
    """Run `calls` (thunks) concurrently with the FIRST base-suite call
    gated until every thunk's request is enqueued: the dispatcher parks
    inside call #1 while the rest queue, so the second device call
    deterministically merges ALL remaining requests (test_ingest's
    gated-dispatch idiom, lifted to the crypto plane)."""
    counting.gate = threading.Event()
    gate = counting.gate
    # `entered` is sticky from any earlier gated stage on this fixture;
    # without the clear, wait(10) below is a no-op and the callers race
    # the dispatcher's coalesce window (their requests get swept into the
    # PROBE's round and _q never refills -> "requests never queued")
    counting.entered.clear()
    # occupy the dispatcher: a tiny probe that parks inside the base call
    # (pick an op DIFFERENT from the one under count so the probe never
    # pollutes the assertion's counter)
    if probe_op == "hash":
        probe = lane.submit("hash", ([b"p1", b"p2"],), 2, "probe")
    else:
        probe = lane.submit("verify", ([b"\x00" * 32] * 2, [b"\x00"] * 2,
                                       [b"\x00" * 64] * 2), 2, "probe")
    assert counting.entered.wait(10), "dispatcher never reached the base"
    results = [None] * len(calls)
    threads = []
    started = threading.Barrier(len(calls) + 1)

    def run(i, fn):
        started.wait()
        results[i] = fn()

    for i, fn in enumerate(calls):
        th = threading.Thread(target=run, args=(i, fn), daemon=True)
        th.start()
        threads.append(th)
    started.wait()
    # every caller parks on its Task BEFORE we release the gate; their
    # requests are already in the lane queue (submit enqueues first)
    deadline = 10.0
    import time
    t0 = time.monotonic()
    while sum(len(lane._q[op]) for op in ("verify", "recover", "hash")) \
            < len(calls):
        assert time.monotonic() - t0 < deadline, "requests never queued"
        time.sleep(0.002)
    gate.set()
    for th in threads:
        th.join(30)
    assert not any(th.is_alive() for th in threads)
    probe.result(10)
    return results


def test_two_groups_one_recover_device_call(lane_pair):
    counting, lane, a, b = lane_pair
    kp = counting.generate_keypair(b"lane-user")
    da, sa = _sigs(counting, kp, 8)
    db, sb = _sigs(counting, kp, 8)
    counting.recover_calls = 0
    counting.recover_sizes = []
    ra, rb = _gated_concurrent(counting, lane, [
        lambda: a.recover_batch(da, sa),
        lambda: b.recover_batch(db, sb),
    ])
    # the claim: BOTH groups' batches crossed the device in ONE call
    assert counting.recover_calls == 1, counting.recover_sizes
    assert counting.recover_sizes == [16]
    for (pubs, ok), n in ((ra, 8), (rb, 8)):
        assert len(pubs) == n and bool(np.all(np.asarray(ok)))
    stats = lane.stats()
    assert stats["merged_calls"] >= 1
    assert stats["per_tag_mean_batch"]["group0"] == 8.0


def test_failed_verify_slice_does_not_poison_other_group(lane_pair):
    counting, lane, a, b = lane_pair
    kp = counting.generate_keypair(b"lane-mixed")
    da, sa = _sigs(counting, kp, 6, valid=False)  # group0: all bad
    db, sb = _sigs(counting, kp, 6, valid=True)   # group1: all good
    counting.recover_calls = 0
    (pa, oka), (pb, okb) = _gated_concurrent(counting, lane, [
        lambda: a.recover_batch(da, sa),
        lambda: b.recover_batch(db, sb),
    ])
    assert counting.recover_calls == 1  # merged, yet verdicts stay per-slice
    assert not np.any(np.asarray(oka))
    assert all(p is None for p in pa)
    assert np.all(np.asarray(okb))
    assert all(p is not None for p in pb)


def test_verify_and_hash_merge_too(lane_pair):
    counting, lane, a, b = lane_pair
    kp = counting.generate_keypair(b"lane-v")
    d1, s1 = _sigs(counting, kp, 4)
    d2, s2 = _sigs(counting, kp, 4)
    pub = kp.pub_bytes
    counting.verify_calls = 0
    va, vb = _gated_concurrent(counting, lane, [
        lambda: a.verify_batch(d1, s1, [pub] * 4),
        lambda: b.verify_batch(d2, s2, [pub] * 4),
    ])
    assert counting.verify_calls == 1
    assert np.all(np.asarray(va)) and np.all(np.asarray(vb))
    counting.hash_calls = 0
    ha, hb = _gated_concurrent(counting, lane, [
        lambda: a.hash_batch([b"x%d" % i for i in range(5)]),
        lambda: b.hash_batch([b"y%d" % i for i in range(5)]),
    ], probe_op="verify")
    assert counting.hash_calls == 1
    base = counting._suite
    assert ha == base.hash_batch([b"x%d" % i for i in range(5)])
    assert hb == base.hash_batch([b"y%d" % i for i in range(5)])


def test_dispatch_error_rejects_cohort_and_lane_survives(lane_pair):
    counting, lane, a, b = lane_pair
    kp = counting.generate_keypair(b"lane-err")
    d, s = _sigs(counting, kp, 4)
    counting.fail_next = RuntimeError("device fell over")
    with pytest.raises(RuntimeError, match="device fell over"):
        a.recover_batch(d, s)
    # the lane thread survived the failed dispatch: next call succeeds
    pubs, ok = b.recover_batch(d, s)
    assert bool(np.all(np.asarray(ok)))


def test_lane_suite_delegates_and_bypasses_tiny_batches(lane_pair):
    counting, lane, a, _b = lane_pair
    kp = a.generate_keypair(b"lane-del")  # delegated keygen
    d = a.hash(b"single")                 # delegated scalar hash
    sig = a.sign(kp, d)                   # delegated signing
    before = lane.stats()["requests_total"]
    # single-item verify takes the base path (no thread hop for size-1)
    assert a.verify(kp.pub_bytes, d, sig)
    assert a.recover(d, sig) is not None
    assert lane.stats()["requests_total"] == before
    # recover_addresses rides the lane's recover and hashes host-side
    ds, ss = _sigs(counting, kp, 4)
    addrs, ok = a.recover_addresses(ds, ss)
    assert bool(np.all(np.asarray(ok)))
    assert all(addr == kp.address for addr in addrs)


def test_host_fan_out_preserves_results():
    """Merged HOST batches through the lane: a recover reaches the base
    suite whole (its host door splits it across cores, nativeec), a hash
    batch is split across the lane's worker pool; results must be
    order-preserving and bit-identical to the unsplit call, bad slices
    staying positional."""
    counting = CountingSuite(make_suite(False, backend="host"))
    lane = CryptoLane(counting, host_workers=2)
    suite = LaneSuite(lane, tag="g")
    try:
        kp = counting.generate_keypair(b"fan-out")
        d, s = _sigs(counting, kp, 20)
        db, sb = _sigs(counting, kp, 4, valid=False)
        digests = d[:10] + db + d[10:]
        sigs = s[:10] + sb + s[10:]
        counting.recover_calls = 0
        pubs, ok = suite.recover_batch(digests, sigs)
        assert counting.recover_calls == 1  # the door splits, not the lane
        want = [True] * 10 + [False] * 4 + [True] * 10
        assert list(np.asarray(ok)) == want
        ref_pubs, _ = counting._suite.recover_batch(digests, sigs)
        assert pubs == ref_pubs
        hashes = suite.hash_batch([b"m%d" % i for i in range(24)])
        assert hashes == counting._suite.hash_batch(
            [b"m%d" % i for i in range(24)])
    finally:
        lane.stop()


def test_stop_rejects_queued_and_refuses_new():
    counting = CountingSuite(make_suite(False, backend="host"))
    lane = CryptoLane(counting)
    counting.gate = threading.Event()
    gate = counting.gate
    parked = lane.submit("hash", ([b"a", b"b"],), 2, "t")
    assert counting.entered.wait(10)
    queued = lane.submit("hash", ([b"c", b"d"],), 2, "t")
    stopper = threading.Thread(target=lane.stop, daemon=True)
    stopper.start()
    gate.set()
    stopper.join(15)
    assert not stopper.is_alive()
    parked.result(5)  # the in-flight call completed
    # the queued one either completed (drained before stop) or was
    # rejected — it must NOT hang
    try:
        queued.result(5)
    except RuntimeError:
        pass
    with pytest.raises(RuntimeError):
        lane.submit("hash", ([b"e", b"f"],), 2, "t")
