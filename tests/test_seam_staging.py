"""The crypto seam's host side of a device EC call: the batch is staged as
whole arrays (`CryptoSuite._stage`, `bigint.rows_to_limbs`) and the
recovered keys come back as one array (`bigint.limbs_to_rows`), bit for bit
what one Python integer per signature used to give.

The JAX kernels run on XLA:CPU here only because the tests ask for it in
code (`allow_cpu=True`); the two end-to-end tests compile one bucket-8
program each (about a minute), everything else stubs the kernel."""

import numpy as np
import pytest

from fisco_bcos_tpu.crypto import suite as suite_mod
from fisco_bcos_tpu.crypto.suite import CHUNK, CryptoSuite
from fisco_bcos_tpu.ops import bigint, ec

TOP = (1 << 256) - 1


# -- (a) the two helpers against the integer route --------------------------

def _random_values(count=64):
    rng = np.random.default_rng(33)
    return [int.from_bytes(rng.bytes(32), "big") for _ in range(count)]


@pytest.mark.parametrize("values", [
    pytest.param(_random_values(), id="random"),
    pytest.param([0], id="zero"),
    pytest.param([1], id="one"),
    pytest.param([TOP], id="all-ones"),
    pytest.param([v >> 80 for v in _random_values(8)] + [0xffff, 0x10000],
                 id="zero-high-limbs"),
    pytest.param([(v >> 96) << 96 for v in _random_values(8)]
                 + [1 << 255, 0xffff << 240], id="zero-low-limbs"),
    pytest.param([0, 1, TOP, 1 << 16, (1 << 16) - 1, TOP - 1, 1 << 128],
                 id="mixed-edges"),
])
def test_rows_and_limbs_match_the_integer_route(values):
    rows = np.frombuffer(b"".join(v.to_bytes(32, "big") for v in values),
                         np.uint8).reshape(len(values), 32)
    want = bigint.batch_to_limbs(values)
    got = bigint.rows_to_limbs(rows)
    assert got.dtype == np.uint32 and got.shape == (len(values), 16)
    assert np.array_equal(got, want)
    # a column cut from a wider frame (row stride 65) reads the same
    frame = np.zeros((len(values), 65), np.uint8)
    frame[:, 32:64] = rows
    assert np.array_equal(bigint.rows_to_limbs(frame[:, 32:64]), want)
    # and back: limbs -> rows, against from_limbs
    back = bigint.limbs_to_rows(want)
    assert back.dtype == np.uint8 and back.shape == (len(values), 32)
    assert [bytes(r) for r in back] == \
        [bigint.from_limbs(a).to_bytes(32, "big") for a in want]
    assert np.array_equal(back, rows)
    # a device output may come back column-major: same rows, C-contiguous
    back = bigint.limbs_to_rows(np.asfortranarray(want))
    assert back.flags["C_CONTIGUOUS"] and np.array_equal(back, rows)


# -- (b) the staged operands, array for array -------------------------------

def _integer_route(suite, digests, sigs, pubs=None):
    """The operands as the seam made them before it staged arrays: one
    Python integer per digest, r, s (and key half), `batch_to_limbs`."""
    ssz = suite.signature_size
    rs = [int.from_bytes(g[:32], "big") if len(g) >= ssz else 0 for g in sigs]
    ss = [int.from_bytes(g[32:64], "big") if len(g) >= ssz else 0
          for g in sigs]
    es = [int.from_bytes(d, "big") for d in digests]
    cols = [bigint.batch_to_limbs(c) for c in (es, rs, ss)]
    if pubs is None:
        cols.append(np.array([g[64] if len(g) >= 65 else 255 for g in sigs],
                             np.uint32))
    else:
        cols.append(bigint.batch_to_limbs(
            [int.from_bytes(p[:32], "big") for p in pubs]))
        cols.append(bigint.batch_to_limbs(
            [int.from_bytes(p[32:64], "big") for p in pubs]))
    return cols


def _mixed_batch(ssz, seed):
    """Random well-formed rows and, after them, the rows that are not."""
    rng = np.random.default_rng(seed)
    digests = [rng.bytes(32) for _ in range(20)]
    sigs = [rng.bytes(ssz) for _ in range(20)]
    pubs = [rng.bytes(64) for _ in range(20)]
    odd = [
        (rng.bytes(32), rng.bytes(64), rng.bytes(64)),      # v cut off
        (rng.bytes(32), b"", rng.bytes(64)),                # no signature
        (rng.bytes(32), rng.bytes(ssz - 1), rng.bytes(64)),
        (rng.bytes(32), rng.bytes(ssz + 3), rng.bytes(64)),  # cut to size
        (rng.bytes(31), rng.bytes(ssz), rng.bytes(64)),     # same integer
        (b"", rng.bytes(ssz), rng.bytes(64)),
        (b"\x00" + rng.bytes(32), rng.bytes(ssz), rng.bytes(64)),
        (rng.bytes(32), rng.bytes(ssz), rng.bytes(40)),     # qy of 8 bytes
        (rng.bytes(32), rng.bytes(ssz), b""),
        (rng.bytes(32), rng.bytes(ssz), rng.bytes(70)),
    ]
    for d, g, p in odd:
        digests.append(d), sigs.append(g), pubs.append(p)
    # sound rows after the odd ones too: a fix-up may not shift its neighbours
    digests.append(rng.bytes(32)), sigs.append(rng.bytes(ssz))
    pubs.append(rng.bytes(64))
    return digests, sigs, pubs


class _Recorder:
    """Stands in for an EC kernel: keeps its operands, answers in shape."""

    def __init__(self, outputs):
        self.outputs = outputs
        self.operands = None

    def __call__(self, curve, *cols):
        self.operands = cols
        b = cols[0].shape[0]
        if self.outputs == 3:
            return (np.zeros((b, 16), np.uint32), np.zeros((b, 16), np.uint32),
                    np.zeros(b, bool))
        return np.zeros(b, bool)


STAGED = [
    pytest.param("ecdsa", "recover", "ecdsa_recover_batch", id="secp-recover"),
    pytest.param("ecdsa", "verify", "ecdsa_verify_batch", id="secp-verify"),
    pytest.param("sm", "verify", "sm2_verify_batch", id="sm2-verify"),
    pytest.param("sm", "recover", "sm2_verify_batch", id="sm2-recover"),
]


def _call(dev, op, digests, sigs, pubs):
    if op == "recover":
        return dev.recover_batch(digests, sigs)
    return dev.verify_batch(digests, sigs, pubs)


@pytest.mark.parametrize("kind,op,kernel", STAGED)
def test_staged_operands_equal_the_integer_route(monkeypatch, kind, op,
                                                 kernel):
    dev = CryptoSuite(kind, backend="device", allow_cpu=True)
    digests, sigs, pubs = _mixed_batch(dev.signature_size, seed=len(kernel))
    n = len(digests)
    rec = _Recorder(3 if kernel == "ecdsa_recover_batch" else 1)
    monkeypatch.setattr(ec, kernel, rec)
    _call(dev, op, digests, sigs, pubs)
    if (kind, op) == ("sm", "recover"):  # the keys the signatures carry
        pubs = [g[64:128] if len(g) >= 128 else bytes(64) for g in sigs]
    want = _integer_route(dev, digests, sigs,
                          None if kernel == "ecdsa_recover_batch" else pubs)
    assert len(rec.operands) == len(want)
    for got, ref in zip(rec.operands, want):
        assert got.dtype == np.uint32 and got.shape == (64,) + ref.shape[1:]
        assert np.array_equal(got[:n], ref)
        assert not got[n:].any()                # the bucket's padding
    # what the issue names: r = s = 0 and v = 255 for the rows cut short
    r, s = rec.operands[1], rec.operands[2]
    for i in (20, 21, 22):
        assert not r[i].any() and not s[i].any()
    if kernel == "ecdsa_recover_batch":
        assert rec.operands[3][20:23].tolist() == [255, 255, 255]
        assert rec.operands[3][23] == sigs[23][64]


@pytest.mark.parametrize("kind,op,kernel", STAGED)
def test_a_digest_that_does_not_fit_256_bits_is_an_error_of_the_call(
        monkeypatch, kind, op, kernel):
    dev = CryptoSuite(kind, backend="device", allow_cpu=True)
    digests, sigs, pubs = _mixed_batch(dev.signature_size, seed=7)
    digests[3] = b"\x01" + digests[3]           # 33 bytes, 2^256 and above
    with pytest.raises(ValueError, match="out of range for 16 limbs"):
        bigint.batch_to_limbs([int.from_bytes(digests[3], "big")])
    rec = _Recorder(3 if kernel == "ecdsa_recover_batch" else 1)
    monkeypatch.setattr(ec, kernel, rec)
    with pytest.raises(ValueError, match="out of range for 16 limbs"):
        _call(dev, op, digests, sigs, pubs)
    assert rec.operands is None
    row = dev.status()["ops"]["verify" if kind == "sm" else op]
    assert row["deviceCalls"] == 0 and row["packSeconds"] == 0.0


# -- (c) end to end: the device door against the host door ------------------

def _flip(b: bytes, i: int) -> bytes:
    return b[:i] + bytes([b[i] ^ 0x5a]) + b[i + 1:]


def _signed(host, count, tag):
    keys = [host.generate_keypair(tag + bytes([i])) for i in range(count)]
    digests = [host.hash(tag + b"-tx-%d" % i) for i in range(count)]
    sigs = [host.sign(kp, d) for kp, d in zip(keys, digests)]
    return keys, digests, sigs


def test_secp_recover_on_the_device_equals_the_host_door():
    """Bucket 8 through the real kernel: sound, tampered and malformed."""
    host = CryptoSuite("ecdsa", backend="host")
    dev = CryptoSuite("ecdsa", backend="device", allow_cpu=True)
    keys, digests, sigs = _signed(host, 8, b"seam-secp")
    sigs[1] = sigs[1] + b"\x07"                 # 66 bytes: cut to 65
    sigs[2] = _flip(sigs[2], 40)                # tampered s
    digests[3] = _flip(digests[3], 0)           # another message
    sigs[4] = sigs[4][:64]                      # v cut off
    sigs[5] = b""
    digests[6] = digests[6][1:]                 # 31 bytes
    sigs[7] = sigs[7][:64] + b"\x09"            # no such recovery id
    pubs_d, ok_d = dev.recover_batch(digests, sigs)
    pubs_h, ok_h = host.recover_batch(digests, sigs)
    assert ok_d.dtype == bool and ok_d.tolist() == ok_h.tolist()
    assert pubs_d == pubs_h
    assert [p is None for p in pubs_d] == [not o for o in ok_d.tolist()]
    assert pubs_d[0] == keys[0].pub_bytes and pubs_d[1] == keys[1].pub_bytes
    assert all(type(p) is bytes and len(p) == 64
               for p in pubs_d if p is not None)
    assert pubs_d[2] != keys[2].pub_bytes and pubs_d[3] != keys[3].pub_bytes
    assert pubs_d[4] is None and pubs_d[5] is None and pubs_d[7] is None
    addr_d, _ = dev.recover_addresses(digests, sigs)
    addr_h, _ = host.recover_addresses(digests, sigs)
    assert addr_d == addr_h and addr_d[0] == keys[0].address
    row = dev.status()["ops"]["recover"]
    assert (row["deviceCalls"], row["deviceItems"], row["deviceLanes"]) == \
        (2, 16, 16)
    assert row["hostCalls"] == 0


def test_sm2_verify_and_recover_on_the_device_equal_the_host_door():
    """Five columns, 128-byte signatures that carry their keys."""
    host = CryptoSuite("sm", backend="host")
    dev = CryptoSuite("sm", backend="device", allow_cpu=True)
    keys, digests, sigs = _signed(host, 8, b"seam-sm")
    assert all(len(g) == 128 for g in sigs)
    sigs[1] = sigs[1] + b"\x07\x07"             # 130 bytes: cut to 128
    sigs[2] = _flip(sigs[2], 5)                 # tampered r
    digests[3] = _flip(digests[3], 31)
    sigs[4] = sigs[4][:127]
    sigs[5] = b""
    digests[6] = digests[6][1:]
    sigs[7] = sigs[7][:64] + keys[0].pub_bytes  # another signer's key
    pubs_d, ok_d = dev.recover_batch(digests, sigs)
    pubs_h, ok_h = host.recover_batch(digests, sigs)
    assert ok_d.tolist() == ok_h.tolist() == [True, True] + [False] * 6
    assert pubs_d == pubs_h
    assert pubs_d[:2] == [keys[0].pub_bytes, keys[1].pub_bytes]
    assert pubs_d[2:] == [None] * 6
    # explicit keys, some of them not 64 bytes
    pubs = [kp.pub_bytes for kp in keys]
    pubs[1] = pubs[1] + b"\x00"                 # cut to 64
    pubs[4] = pubs[4][:40]
    pubs[5] = b""
    sigs[4] = host.sign(keys[4], digests[4])    # sound, under a short key
    ok_d = dev.verify_batch(digests, sigs, pubs)
    ok_h = host.verify_batch(digests, sigs, pubs)
    assert ok_d.dtype == bool and ok_d.tolist() == ok_h.tolist()
    # row 7: r, s are sound under the key handed in, the one carried is ignored
    assert ok_d.tolist() == [True, True] + [False] * 5 + [True]
    row = dev.status()["ops"]
    assert row["verify"]["deviceCalls"] == 2 and \
        row["verify"]["deviceLanes"] == 16
    assert row["recover"]["deviceCalls"] == 0   # recover is verify + extract


def _echo_recover(curve, e, r, s, v):
    """A recover kernel that costs nothing: r, s come back as the key,
    column-major as the chip hands its outputs back, and a row staged as
    r = 0 is refused."""
    return np.asfortranarray(r), np.asfortranarray(s), r.any(axis=1)


def test_a_batch_above_chunk_is_staged_in_chunks(monkeypatch):
    dev = CryptoSuite("ecdsa", backend="device", allow_cpu=True)
    shapes = []

    def echo(curve, e, r, s, v):
        shapes.append((e.shape, r.shape, s.shape, v.shape))
        return _echo_recover(curve, e, r, s, v)

    monkeypatch.setattr(ec, "ecdsa_recover_batch", echo)
    n = CHUNK + 5
    rng = np.random.default_rng(5)
    blob = rng.bytes(65 * n)
    sigs = [blob[i * 65:(i + 1) * 65] for i in range(n)]
    short = (0, CHUNK - 1, CHUNK, n - 1)        # both sides of the seam
    for i in short:
        sigs[i] = sigs[i][:64]
    pubs, ok = dev.recover_batch([bytes(32)] * n, sigs)
    assert shapes == [((CHUNK, 16), (CHUNK, 16), (CHUNK, 16), (CHUNK,))] * 2
    assert ok.shape == (n,) and len(pubs) == n
    assert [i for i in range(n) if not ok[i]] == list(short)
    assert pubs == [None if i in short else g[:64]
                    for i, g in enumerate(sigs)]
    row = dev.status()["ops"]["recover"]
    assert (row["deviceCalls"], row["deviceItems"], row["deviceLanes"]) == \
        (1, n, 2 * CHUNK)


# -- (d) the seam's counters still move -------------------------------------

def test_ec_seam_counters_move_on_a_device_call(monkeypatch):
    dev = CryptoSuite("ecdsa", backend="device", allow_cpu=True)
    monkeypatch.setattr(ec, "ecdsa_recover_batch", _echo_recover)
    monkeypatch.setattr(ec, "ecdsa_verify_batch",
                        lambda curve, e, r, s, x, y: np.ones(e.shape[0], bool))
    keys = ("packSeconds", "callSeconds", "unpackSeconds")
    for op in ("recover", "verify"):
        assert all(dev.status()["ops"][op][k] == 0.0 for k in keys)
    rng = np.random.default_rng(9)
    n = 700
    digests = [rng.bytes(32) for _ in range(n)]
    sigs = [rng.bytes(65) for _ in range(n)]
    pubs, ok = dev.recover_batch(digests, sigs)
    assert ok.all() and pubs == [g[:64] for g in sigs]
    first = dev.status()["ops"]["recover"]
    assert (first["deviceCalls"], first["deviceItems"],
            first["deviceLanes"]) == (1, n, 4096)
    assert all(first[k] > 0.0 for k in keys), first
    dev.recover_batch(digests[:9], sigs[:9])
    again = dev.status()["ops"]["recover"]
    assert (again["deviceCalls"], again["deviceLanes"]) == (2, 4096 + 64)
    assert all(again[k] > first[k] for k in keys), (first, again)
    assert dev.verify_batch(digests, sigs, [bytes(64)] * n).all()
    ver = dev.status()["ops"]["verify"]
    assert (ver["deviceCalls"], ver["deviceItems"], ver["deviceLanes"]) == \
        (1, n, 4096)
    assert ver["packSeconds"] > 0.0 and ver["callSeconds"] > 0.0
    assert ver["unpackSeconds"] >= 0.0          # verify returns the array
    for op in ("recover", "verify"):
        assert dev.status()["ops"][op]["hostCalls"] == 0


def test_the_device_branch_makes_no_integer_of_a_row(monkeypatch):
    """One staging routine: the integer helpers are not reached from the
    device door, whatever the rows look like."""
    def refuse(*_a, **_k):
        raise AssertionError("integer route reached from the device door")

    monkeypatch.setattr(bigint, "to_limbs", refuse)
    monkeypatch.setattr(bigint, "batch_to_limbs", refuse)
    monkeypatch.setattr(bigint, "from_limbs", refuse)
    monkeypatch.setattr(CryptoSuite, "_split_sigs", refuse)
    monkeypatch.setattr(ec, "ecdsa_recover_batch", _echo_recover)
    monkeypatch.setattr(ec, "sm2_verify_batch",
                        lambda curve, e, r, s, x, y: np.ones(e.shape[0], bool))
    for kind in ("ecdsa", "sm"):
        dev = CryptoSuite(kind, backend="device", allow_cpu=True)
        digests, sigs, _pubs = _mixed_batch(dev.signature_size, seed=3)
        pubs, ok = dev.recover_batch(digests, sigs)
        assert len(pubs) == len(digests) == ok.shape[0]
        assert [p is None for p in pubs] == [not o for o in ok.tolist()]


def test_frame_mends_only_the_rows_of_another_length():
    seen = []

    def fix(row):
        seen.append(row)
        return row[:2].ljust(2, b"-")

    rows = [b"ab", b"c", memoryview(b"de"), b"", memoryview(b"fgh")]
    frame = suite_mod._frame(rows, 2, fix)
    assert frame.dtype == np.uint8 and frame.shape == (5, 2)
    assert frame.tobytes() == b"abc-de--fg"
    assert seen == [b"c", b"", b"fgh"]
    assert rows[1] == b"c"                      # the caller's list is not touched
