"""Native EC engine (native/ncrypto) vs the pure-Python oracle.

Equivalence across valid, invalid, and malformed inputs: the host-path
suite swaps the oracle for the native engine when the library loads, so
classification AND recovered keys must match refimpl bit for bit.
"""

import ctypes
import os
import select
import threading

import numpy as np
import pytest

from fisco_bcos_tpu.crypto import nativeec, refimpl
from fisco_bcos_tpu.crypto.suite import make_suite

pytestmark = pytest.mark.skipif(
    not nativeec.available(), reason="libncrypto.so not built")


def _sigs(params, count, sm=False):
    rows = []
    for i in range(count):
        sk, pub = refimpl.keygen(params, bytes([i + 9]) * 24)
        digest = (refimpl.sm3 if sm else refimpl.keccak256)(
            b"native-ec-%d" % i)
        if sm:
            r, s = refimpl.sm2_sign(sk, digest)
            v = 0
        else:
            r, s, v = refimpl.ecdsa_sign(params, sk, digest)
        rows.append((int.from_bytes(digest, "big"), r, s, v, pub, digest))
    return rows


def test_ecdsa_verify_matches_oracle():
    params = refimpl.SECP256K1
    rows = _sigs(params, 6)
    es = [r[0] for r in rows]
    rs = [r[1] for r in rows]
    ss = [r[2] for r in rows]
    qx = [r[4][0] for r in rows]
    qy = [r[4][1] for r in rows]
    # edge rows: r=0, s=n, tampered e, swapped pub, x>=p style huge coords
    es += [es[0], es[1], es[2] ^ 1, es[3], es[4]]
    rs += [0, rs[1], rs[2], rs[3], rs[4]]
    ss += [ss[0], params.n, ss[2], ss[3], ss[4]]
    qx += [qx[0], qx[1], qx[2], qx[4], params.p + 1]  # x >= p: implicit
    qy += [qy[0], qy[1], qy[2], qy[4], qy[4]]         # mod-p reduction
    got = nativeec.ecdsa_verify_batch(es, rs, ss, qx, qy)
    want = [refimpl.ecdsa_verify(params, (x, y),
                                 int(e).to_bytes(32, "big"), r, s)
            for e, r, s, x, y in zip(es, rs, ss, qx, qy)]
    assert got == want
    assert got[:6] == [True] * 6 and got[6:9] == [False] * 3


def test_sm2_verify_matches_oracle():
    params = refimpl.SM2P256V1
    rows = _sigs(params, 5, sm=True)
    es = [r[0] for r in rows] + [rows[0][0] ^ 1]
    rs = [r[1] for r in rows] + [rows[0][1]]
    ss = [r[2] for r in rows] + [rows[0][2]]
    qx = [r[4][0] for r in rows] + [rows[0][4][0]]
    qy = [r[4][1] for r in rows] + [rows[0][4][1]]
    got = nativeec.sm2_verify_batch(es, rs, ss, qx, qy)
    want = [refimpl.sm2_verify((x, y), int(e).to_bytes(32, "big"), r, s)
            for e, r, s, x, y in zip(es, rs, ss, qx, qy)]
    assert got == want
    assert got == [True] * 5 + [False]


def test_ecdsa_recover_matches_oracle():
    params = refimpl.SECP256K1
    rows = _sigs(params, 6)
    es = [r[0] for r in rows]
    rs = [r[1] for r in rows]
    ss = [r[2] for r in rows]
    vs = [r[3] for r in rows]
    # edge rows: flipped v (wrong key, still valid), v>=4, r=0, huge v
    es += [es[0], es[1], es[2], es[3]]
    rs += [rs[0], rs[1], 0, rs[3]]
    ss += [ss[0], ss[1], ss[2], ss[3]]
    vs += [vs[0] ^ 1, 4, vs[2], 255]
    pubs, ok = nativeec.ecdsa_recover_batch(es, rs, ss, vs)
    for i, (e, r, s, v) in enumerate(zip(es, rs, ss, vs)):
        Q = refimpl.ecdsa_recover(params, int(e).to_bytes(32, "big"),
                                  r, s, v)
        assert ok[i] == (Q is not None), i
        if Q is not None:
            want = Q[0].to_bytes(32, "big") + Q[1].to_bytes(32, "big")
            assert pubs[i] == want, i
    # the 6 untampered rows recover the signing keys
    for i in range(6):
        assert ok[i] and pubs[i] == (
            rows[i][4][0].to_bytes(32, "big")
            + rows[i][4][1].to_bytes(32, "big"))


def test_host_suite_routes_through_native():
    """The host-path CryptoSuite classification equals the oracle's for a
    mixed good/bad workload (suite-level integration)."""
    for sm in (False, True):
        suite = make_suite(sm, backend="host")
        kps = [suite.generate_keypair(bytes([i + 3]) * 20)
               for i in range(4)]
        digests = [suite.hash(b"route-%d" % i) for i in range(4)]
        sigs = [suite.sign(kp, d) for kp, d in zip(kps, digests)]
        pubs = [kp.pub_bytes for kp in kps]
        sigs[-1] = sigs[-1][:10] + b"\x77" + sigs[-1][11:]
        ok = suite.verify_batch(digests, sigs, pubs)
        assert ok.tolist() == [True, True, True, False]
        if not sm:
            addrs, okr = suite.recover_addresses(digests, sigs)
            assert okr.tolist()[:3] == [True] * 3
            assert addrs[:3] == [kp.address for kp in kps[:3]]


def test_native_ec_throughput_sane():
    """Native recover must be orders faster than the Python oracle —
    a cheap regression guard against silently falling back."""
    import time

    params = refimpl.SECP256K1
    rows = _sigs(params, 2)
    es = [rows[0][0]] * 64
    rs = [rows[0][1]] * 64
    ss = [rows[0][2]] * 64
    vs = [rows[0][3]] * 64
    nativeec.ecdsa_recover_batch(es[:2], rs[:2], ss[:2], vs[:2])  # warm
    t0 = time.perf_counter()
    _, ok = nativeec.ecdsa_recover_batch(es, rs, ss, vs)
    dt = time.perf_counter() - t0
    assert all(ok)
    assert 64 / dt > 500, f"native recover too slow: {64 / dt:.0f}/s"


def test_oversized_digest_matches_oracle():
    """Digests longer than 32 bytes classify exactly like refimpl
    (e reduced mod n), instead of crashing the batch."""
    params = refimpl.SECP256K1
    sk, pub = refimpl.keygen(params, b"\x21" * 24)
    digest = b"\x9f" * 40  # 320-bit digest
    r, s, v = refimpl.ecdsa_sign(params, sk, digest)
    e = int.from_bytes(digest, "big")
    got = nativeec.ecdsa_verify_batch([e], [r], [s], [pub[0]], [pub[1]])
    assert got == [refimpl.ecdsa_verify(params, pub, digest, r, s)] == [True]
    pubs, ok = nativeec.ecdsa_recover_batch([e], [r], [s], [v])
    assert ok == [True]
    assert pubs[0] == pub[0].to_bytes(32, "big") + pub[1].to_bytes(32, "big")


def test_mismatched_batch_lengths_rejected():
    """Short argument lists must fail loudly, never read past a buffer."""
    with pytest.raises(ValueError):
        nativeec.ecdsa_verify_batch([1, 2], [1], [1, 2], [1, 2], [1, 2])
    with pytest.raises(ValueError):
        nativeec.ecdsa_recover_batch([1, 2], [1, 2], [1, 2], [0])


def test_ecdsa_recover_rows_door_matches_int_door():
    """The zero-marshalling rows entry (pre-packed 32-byte rows, no int
    round trip) returns bit-identical pubs/ok to the int-marshalling
    door for the same batch, including rejected rows."""
    params = refimpl.SECP256K1
    rows = _sigs(params, 5)
    es = [r[0] for r in rows]
    rs = [r[1] for r in rows]
    ss = [r[2] for r in rows]
    vs = [r[3] for r in rows]
    # edge rows the C side must classify, not crash on
    es += [es[0], es[1]]
    rs += [0, rs[1]]
    ss += [ss[0], ss[1]]
    vs += [vs[0], 255]
    want_pubs, want_ok = nativeec.ecdsa_recover_batch(es, rs, ss, vs)
    got_pubs, got_ok = nativeec.ecdsa_recover_batch_rows(
        b"".join(int(e).to_bytes(32, "big") for e in es),
        b"".join(int(r).to_bytes(32, "big") for r in rs),
        b"".join(int(s).to_bytes(32, "big") for s in ss),
        bytes(vs))
    assert got_ok == want_ok
    assert got_pubs == want_pubs
    with pytest.raises(ValueError):
        nativeec.ecdsa_recover_batch_rows(b"\x00" * 32, b"\x00" * 32,
                                          b"\x00" * 32, bytes([0, 0]))


def test_suite_recover_rows_fast_path_parity(monkeypatch):
    """suite.recover_batch answers identically with the rows fast path
    live vs forced off (int door), across valid / tampered / malformed-
    short signatures; oversized digests take the int door (which
    pre-reduces mod n) without error."""
    suite = make_suite(False, backend="host")
    kps = [suite.generate_keypair(bytes([i + 41]) * 20) for i in range(4)]
    digests = [suite.hash(b"rows-%d" % i) for i in range(4)]
    sigs = [suite.sign(kp, d) for kp, d in zip(kps, digests)]
    sigs[1] = b"\x00" * 32 + sigs[1][32:]  # r=0: unrecoverable
    sigs[2] = sigs[2][:17]                 # malformed: short
    live = suite.recover_batch(digests, sigs)
    monkeypatch.setattr(nativeec, "ecdsa_recover_batch_rows",
                        lambda *a: None)
    forced = suite.recover_batch(digests, sigs)
    assert live[0] == forced[0]
    assert live[1].tolist() == forced[1].tolist() == [
        True, False, False, True]
    monkeypatch.undo()
    # oversized digest: the rows door declines (not 32 bytes), the int
    # door classifies it like the oracle
    params = refimpl.SECP256K1
    sk, pub = refimpl.keygen(params, b"\x23" * 24)
    digest = b"\x8c" * 40
    r, s, v = refimpl.ecdsa_sign(params, sk, digest)
    sig = r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])
    pubs, ok = suite.recover_batch([digest], [sig])
    assert ok.tolist() == [True]
    assert pubs[0] == pub[0].to_bytes(32, "big") + pub[1].to_bytes(32, "big")


# -- the split: a batch of more than one native chunk runs as concurrent
# -- native calls over disjoint row spans (nativeec._run_spans) ---------------

SPLIT_NS = (1, 128, 129, 257, 1000, 10_000)
DOORS = ("ecdsa_recover_batch_rows", "ecdsa_recover_batch",
         "ecdsa_verify_batch", "sm2_verify_batch")
_NATIVE_OF = {"ecdsa_recover_batch_rows": "ncrypto_ecdsa_recover_batch",
              "ecdsa_recover_batch": "ncrypto_ecdsa_recover_batch",
              "ecdsa_verify_batch": "ncrypto_ecdsa_verify_batch",
              "sm2_verify_batch": "ncrypto_sm2_verify_batch"}


@pytest.fixture(scope="module")
def signed_rows():
    """kind -> (suite, digests, sigs, pubs): max(SPLIT_NS) distinct signed
    rows a curve, so a span written to the wrong place cannot pass."""
    out = {}
    for kind, sm in (("ecdsa", False), ("sm", True)):
        suite = make_suite(sm, backend="host")
        kps = [suite.generate_keypair(bytes([i + 61]) * 20) for i in range(5)]
        digests = [suite.hash(b"split-%d" % i) for i in range(max(SPLIT_NS))]
        sigs = [suite.sign(kps[i % 5], d) for i, d in enumerate(digests)]
        pubs = [kps[i % 5].pub_bytes for i in range(len(digests))]
        out[kind] = (suite, digests, sigs, pubs)
    return out


def _set_workers(monkeypatch, workers=4):
    monkeypatch.setattr(nativeec, "_workers", lambda: workers)


def _counted(monkeypatch, native_name):
    """Wrap one library entry: -> list of (rows, thread ident) per call."""
    lib = nativeec.load_library()
    real = getattr(lib, native_name)
    calls = []

    def counting(*args):
        # the row count follows the curve id where the entry takes one
        rows = args[0] if native_name == "ncrypto_sm2_verify_batch" \
            else args[1]
        calls.append((rows, threading.get_ident()))
        return real(*args)

    monkeypatch.setattr(lib, native_name, counting)
    return calls


def _door_ints(door, digests, sigs, pubs, bad):
    """The rows as ints (es, rs, ss, vs, qx, qy), with the rows of `bad`
    ({index: fault}) made invalid: zero r, s >= n, then v = 4 (recover)
    or a key off the curve (verify)."""
    order = (refimpl.SM2P256V1 if door.startswith("sm2")
             else refimpl.SECP256K1).n
    es = [int.from_bytes(d, "big") for d in digests]
    rs = [int.from_bytes(g[:32], "big") for g in sigs]
    ss = [int.from_bytes(g[32:64], "big") for g in sigs]
    vs = [g[64] for g in sigs]
    qx = [int.from_bytes(p[:32], "big") for p in pubs]
    qy = [int.from_bytes(p[32:], "big") for p in pubs]
    for i, fault in bad.items():
        if fault == 0:
            rs[i] = 0
        elif fault == 1:
            ss[i] = order + 5
        elif "recover" in door:
            vs[i] = 4
        else:
            qy[i] ^= 1
    return es, rs, ss, vs, qx, qy


def _door_args(door, es, rs, ss, vs, qx, qy):
    if door == "ecdsa_recover_batch_rows":
        def rows(xs):
            return b"".join(x.to_bytes(32, "big") for x in xs)
        return rows(es), rows(rs), rows(ss), bytes(vs)
    if door == "ecdsa_recover_batch":
        return es, rs, ss, vs
    return es, rs, ss, qx, qy


def _oracle(door, i, es, rs, ss, vs, qx, qy):
    """What refimpl says of row i: recovered key bytes or None (recover),
    True / False (verify)."""
    digest = es[i].to_bytes(32, "big")
    if "recover" in door:
        q = refimpl.ecdsa_recover(refimpl.SECP256K1, digest, rs[i], ss[i],
                                  vs[i])
        return q and q[0].to_bytes(32, "big") + q[1].to_bytes(32, "big")
    if door.startswith("sm2"):
        return refimpl.sm2_verify((qx[i], qy[i]), digest, rs[i], ss[i])
    return refimpl.ecdsa_verify(refimpl.SECP256K1, (qx[i], qy[i]), digest,
                                rs[i], ss[i])


def _bad_rows(n, spans):
    """{row: fault} on both sides of every boundary between `spans` (of
    the middle, where there is one span), at both ends, and on both sides
    of the odd tail's first row: each kind of fault at least once. None
    in a batch too small to keep good rows between them."""
    if n < 8:
        return {}
    bad = {0: 2, n - 1: 1}
    edges = [o for o, _ln in spans[1:]] or [n // 2]
    if n % 128 and n > 128:
        edges.append(n - n % 128)
    for k, edge in enumerate(edges):
        bad.setdefault(edge - 1, k % 3)
        bad.setdefault(edge, (k + 1) % 3)
    return bad


@pytest.mark.parametrize("n", SPLIT_NS)
@pytest.mark.parametrize("door", DOORS)
def test_split_batch_equals_the_single_call_and_the_oracle(
        door, n, signed_rows, monkeypatch):
    """Every door, from one row to a block of 10,000: the same keys, `ok`
    flags and None positions as one native call over the same rows, and
    as the oracle on the rows at the span boundaries, with invalid rows
    on both sides of each boundary and in the odd tail; a batch of at
    most one chunk is one native call on the caller's thread and makes no
    pool."""
    kind = "sm" if door.startswith("sm2") else "ecdsa"
    _suite, digests, sigs, pubs = (x[:n] if isinstance(x, list) else x
                                   for x in signed_rows[kind])
    fn = getattr(nativeec, door)
    _set_workers(monkeypatch)
    spans = nativeec.spans_of(n)
    bad = _bad_rows(n, spans)
    ints = _door_ints(door, digests, sigs, pubs, bad)
    args = _door_args(door, *ints)

    _set_workers(monkeypatch, 1)
    want = fn(*args)
    _set_workers(monkeypatch)
    monkeypatch.setattr(nativeec, "_pool", None)
    calls = _counted(monkeypatch, _NATIVE_OF[door])
    got = fn(*args)
    assert got == want
    ok = got[1] if "recover" in door else got
    assert len(ok) == n
    assert [i for i, o in enumerate(ok) if not o] == sorted(bad)
    if "recover" in door:
        assert [i for i, p in enumerate(got[0]) if p is None] == sorted(bad)
        good = next(i for i in range(n) if i not in bad)
        assert got[0][good] == pubs[good]

    # the oracle, on the invalid rows, their neighbours and a few between
    answers = got[0] if "recover" in door else got
    for i in sorted({j for b in bad for j in (b - 1, b, b + 1)
                     if 0 <= j < n} | set(range(0, n, max(1, n // 4)))):
        assert answers[i] == _oracle(door, i, *ints), i

    assert sorted(c[0] for c in calls) == sorted(ln for _o, ln in spans)
    if n <= 128:
        assert calls == [(n, threading.get_ident())]
        assert nativeec._pool is None
    else:
        assert len(spans) == min(4, -(-n // 128))
        # the caller runs the last span itself, the others run beside it
        assert (spans[-1][1], threading.get_ident()) in calls
        assert len({c[1] for c in calls}) > 1


@pytest.mark.parametrize("workers", (1, 2, 3, 4, 13))
def test_spans_cover_the_rows_once_in_whole_chunks(workers, monkeypatch):
    """`spans_of`: [0, n) exactly once and in order, whole chunks in all
    but the last span, sizes within one chunk of each other, one span for
    n <= 128, and never more spans than cores, chunks or four."""
    monkeypatch.setattr(nativeec.os, "sched_getaffinity",
                        lambda _pid: set(range(workers)))
    sizes = list(range(0, 140)) + [255, 256, 257, 383, 384, 385, 511, 512,
                                   513, 640, 1000, 1153, 9999, 10_000,
                                   10_113, 65_536]
    for n in sizes:
        spans = nativeec.spans_of(n)
        assert spans[0][0] == 0 and sum(ln for _o, ln in spans) == n
        for (o, ln), (o2, _ln2) in zip(spans, spans[1:]):
            assert o + ln == o2 and ln > 0 and ln % 128 == 0
        chunks = -(-n // 128)
        assert len(spans) == max(1, min(4, workers, chunks))
        if n <= 128:
            assert spans == [(0, n)]
        per = [-(-ln // 128) for _o, ln in spans]
        assert max(per) - min(per) <= 1
        assert nativeec.parts_of(n) == len(spans)


@pytest.mark.parametrize("failing", ("first", "callers"))
def test_a_span_that_raises_is_raised_in_the_caller_after_the_others_end(
        failing, monkeypatch):
    """One span fails at once while the others are still at work: the
    caller gets that exception, and only after every other span has
    ended, so no buffer is released under a running native call."""
    import time

    _set_workers(monkeypatch)
    n = 1000
    spans = nativeec.spans_of(n)
    fails = 0 if failing == "first" else len(spans) - 1
    # each row's input byte names its span, so a call knows which it is
    inp = b"".join(bytes([k]) * ln for k, (_o, ln) in enumerate(spans))
    ended = []

    def fn(rows, rows_in, out):
        k = rows_in[0]
        assert rows_in == bytes([k]) * rows == bytes([k]) * spans[k][1]
        if k == fails:
            raise RuntimeError("span %d" % k)
        time.sleep(0.2)
        out[0] = 1
        ended.append(k)

    out = (ctypes.c_uint8 * n)()
    with pytest.raises(RuntimeError, match="span %d" % fails):
        nativeec._run_spans(fn, (), n, ((inp, 1),), ((out, 1),))
    others = [k for k in range(len(spans)) if k != fails]
    assert sorted(ended) == others
    # the spans that ran wrote their own part of the one output buffer
    assert [k for k, (o, _ln) in enumerate(spans) if out[o]] == others


def test_the_pool_is_made_anew_in_a_forked_child(signed_rows, monkeypatch):
    """A forked child inherits the parent's pool object without its
    threads: its first split batch makes a pool of its own and answers
    as the parent does."""
    _suite, digests, sigs, _pubs = signed_rows["ecdsa"]
    _set_workers(monkeypatch)
    n = 600
    args = (b"".join(digests[:n]), b"".join(g[:32] for g in sigs[:n]),
            b"".join(g[32:64] for g in sigs[:n]),
            bytes(g[64] for g in sigs[:n]))
    want = nativeec.ecdsa_recover_batch_rows(*args)
    parent_pool = nativeec._pool
    assert parent_pool is not None and nativeec._pool_pid == os.getpid()
    rd, wr = os.pipe()
    pid = os.fork()
    if pid == 0:
        verdict = b"error"
        try:
            got = nativeec.ecdsa_recover_batch_rows(*args)
            anew = (nativeec._pool is not parent_pool
                    and nativeec._pool_pid == os.getpid())
            verdict = b"same" if got == want and anew else b"differs"
        finally:
            os.write(wr, verdict)
            os._exit(0)
    os.close(wr)
    try:
        ready, _, _ = select.select([rd], [], [], 60)
        verdict = os.read(rd, 16) if ready else b"hung"
    finally:
        os.close(rd)
        if verdict == b"hung":
            os.kill(pid, 9)
        os.waitpid(pid, 0)
    assert verdict == b"same"
    assert nativeec._pool is parent_pool


@pytest.mark.parametrize("n", (128, 1000))
@pytest.mark.parametrize("door", DOORS)
def test_suite_status_counts_the_parts_of_a_host_batch(
        door, n, signed_rows, monkeypatch):
    """Through CryptoSuite, with a short signature and a zero r at a span
    boundary: one host call a batch, `hostParts` native calls, the items
    of a split batch under `hostSplitItems`, and the answers of the
    unsplit suite."""
    kind = "sm" if door.startswith("sm2") else "ecdsa"
    suite0, digests, sigs, pubs = (x[:n] if isinstance(x, list) else x
                                   for x in signed_rows[kind])
    _set_workers(monkeypatch)
    spans = nativeec.spans_of(n)
    calls = _counted(monkeypatch, _NATIVE_OF[door])
    suite = make_suite(kind == "sm", backend="host")
    cut = spans[len(spans) // 2][0] or n // 2
    sigs = list(sigs)
    sigs[cut] = sigs[cut][:17]            # malformed: short
    sigs[cut - 1] = b"\x00" * 32 + sigs[cut - 1][32:]   # r = 0
    if door == "ecdsa_recover_batch":     # the int door: rows door declines
        monkeypatch.setattr(nativeec, "ecdsa_recover_batch_rows",
                            lambda *a: None)
    op = "recover" if "recover" in door else "verify"

    def through_suite(s):
        if op == "recover":
            keys, good = s.recover_batch(digests, sigs)
            return keys, good.tolist()
        return s.verify_batch(digests, sigs, pubs).tolist()

    got = through_suite(suite)
    row = suite.status()["ops"][op]
    assert (row["hostCalls"], row["hostItems"]) == (1, n)
    assert row["hostParts"] == len(spans) == len(calls)
    assert row["hostSplitItems"] == (n if len(spans) > 1 else 0)
    other = suite.status()["ops"]["verify" if op == "recover" else "recover"]
    assert (other["hostParts"], other["hostSplitItems"]) == (0, 0)
    _set_workers(monkeypatch, 1)
    assert got == through_suite(suite0)
    good = got[1] if op == "recover" else got
    assert [i for i, o in enumerate(good) if not o] == [cut - 1, cut]


def test_concurrent_callers_of_a_split_door_get_their_own_answers(
        signed_rows, monkeypatch):
    """More callers than the pool has threads, each with rows of its own,
    all splitting at once: every caller reads the keys of its own rows."""
    import sys

    _suite, digests, sigs, _pubs = signed_rows["ecdsa"]
    _set_workers(monkeypatch)
    n, callers, rounds = 300, 6, 4

    def rows_of(k):
        lo = k * 120
        return (b"".join(digests[lo:lo + n]),
                b"".join(g[:32] for g in sigs[lo:lo + n]),
                b"".join(g[32:64] for g in sigs[lo:lo + n]),
                bytes(g[64] for g in sigs[lo:lo + n]))

    _set_workers(monkeypatch, 1)
    want = [nativeec.ecdsa_recover_batch_rows(*rows_of(k))
            for k in range(callers)]
    _set_workers(monkeypatch)
    got = [[] for _ in range(callers)]
    start = threading.Barrier(callers)

    def caller(k):
        args = rows_of(k)
        start.wait(10)
        for _ in range(rounds):
            got[k].append(nativeec.ecdsa_recover_batch_rows(*args))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(k,), daemon=True)
                   for k in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    for k in range(callers):
        assert got[k] == [want[k]] * rounds
        assert all(want[k][1])
