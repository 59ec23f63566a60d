"""Golden tests for Keccak256/SM3 TPU kernels vs known vectors + Python oracle."""

import random

import pytest

import jax.numpy as jnp
import numpy as np

from fisco_bcos_tpu.crypto import refimpl
from fisco_bcos_tpu.ops import keccak, merkle, sm3

rng = random.Random(7)


def test_keccak_vectors_ref():
    assert refimpl.keccak256(b"").hex() == (
        "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
    )
    assert refimpl.keccak256(b"abc").hex() == (
        "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"
    )


def test_sm3_vectors_ref():
    assert refimpl.sm3(b"abc").hex() == (
        "66c7f0f462eeedd9d1f2d46bdc10e4e24167c4875cf2f7a2297da02b8f4ba8e0"
    )
    assert refimpl.sm3(b"abcd" * 16).hex() == (
        "debe9ff92275b8a138604889c18e5a4d6fdb70e5387e5765293dcba39c0c5732"
    )


def test_keccak_device_matches_ref():
    msgs = [b"", b"abc", bytes(range(136)), rng.randbytes(300), rng.randbytes(135),
            rng.randbytes(136), rng.randbytes(137), rng.randbytes(500)]
    got = keccak.keccak256_batch_np(msgs)
    for i, m in enumerate(msgs):
        assert bytes(got[i]) == refimpl.keccak256(m), f"msg {i} len {len(m)}"


def test_sm3_device_matches_ref():
    msgs = [b"", b"abc", rng.randbytes(55), rng.randbytes(56), rng.randbytes(64),
            rng.randbytes(200)]
    got = sm3.sm3_batch_np(msgs)
    for i, m in enumerate(msgs):
        assert bytes(got[i]) == refimpl.sm3(m), f"msg {i} len {len(m)}"


def _host_root(leaves, alg):
    return merkle.merkle_levels_host(leaves, alg)[-1][0]


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
def test_merkle_root_device_vs_host():
    for alg in ("keccak256", "sm3"):
        for n in (1, 2, 16, 17, 40, 256, 300):
            leaves = [rng.randbytes(32) for _ in range(n)]
            dev = bytes(np.asarray(merkle.merkle_root(
                np.frombuffer(b"".join(leaves), dtype=np.uint8).reshape(n, 32), alg)))
            host = _host_root(leaves, alg)
            assert dev == host, (alg, n)


def test_merkle_bucket_invariance():
    # same logical n must give same root regardless of bucket padding
    leaves = [rng.randbytes(32) for _ in range(20)]
    arr = np.frombuffer(b"".join(leaves), dtype=np.uint8).reshape(20, 32)
    r1 = bytes(np.asarray(merkle.merkle_root(arr)))
    big = np.concatenate([arr, np.zeros((1004, 32), np.uint8)])
    r2 = bytes(np.asarray(merkle._merkle_root_bucketed(jnp.asarray(big), jnp.int32(20), "keccak256")))
    assert r1 == r2


def test_merkle_proof():
    leaves = [rng.randbytes(32) for _ in range(40)]
    root = _host_root(leaves, "keccak256")
    for idx in (0, 15, 16, 39):
        proof = merkle.merkle_proof(leaves, idx)
        assert merkle.verify_merkle_proof(leaves[idx], proof, root)
    bad = merkle.merkle_proof(leaves, 3)
    assert not merkle.verify_merkle_proof(leaves[4], bad, root)


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
def test_suite_chunked_device_batches(monkeypatch):
    """Batches above CHUNK pipeline multiple kernel calls (double-buffered
    staging analogue) and must be bit-identical to the host oracle."""
    from fisco_bcos_tpu.crypto import suite as suite_mod
    from fisco_bcos_tpu.crypto.suite import make_suite

    monkeypatch.setattr(suite_mod, "CHUNK", 8)
    s = make_suite(backend="device", device_min_batch=1, allow_cpu=True)
    host = make_suite(backend="host")
    kps = [host.generate_keypair(bytes([i + 1]) * 8) for i in range(4)]
    digests, sigs, pubs = [], [], []
    for i in range(20):  # > 2 chunks of 8
        kp = kps[i % 4]
        d = host.hash(b"chunk-%d" % i)
        digests.append(d)
        sigs.append(host.sign(kp, d))
        pubs.append(kp.pub_bytes)
    # corrupt one signature: chunking must preserve per-index results
    sigs[13] = sigs[12]

    ok_dev = s.verify_batch(digests, sigs, pubs)
    ok_host = host.verify_batch(digests, sigs, pubs)
    assert list(ok_dev) == list(ok_host)
    assert not ok_dev[13] and ok_dev[12]

    pubs_dev, okr_dev = s.recover_batch(digests, sigs)
    pubs_host, okr_host = host.recover_batch(digests, sigs)
    assert list(okr_dev) == list(okr_host)
    assert pubs_dev == pubs_host


def test_native_host_hash_matches_refimpl():
    """native/nevm's C++ Keccak-256 and SM3 (the host-path suite hashers)
    must match the pure-Python oracle across padding boundaries (empty,
    sub-rate, rate-1/rate/rate+1, multi-block)."""
    import pytest

    from fisco_bcos_tpu.crypto import nativehash, refimpl

    nk, ns = nativehash.keccak256(), nativehash.sm3()
    if nk is None:
        pytest.skip("libnevm.so not built")
    rng = np.random.default_rng(9)
    sizes = [0, 1, 31, 32, 55, 56, 63, 64, 65, 135, 136, 137, 200, 500,
             1000]
    for n in sizes:
        data = rng.bytes(n)
        assert nk(data) == refimpl.keccak256(data), n
        assert ns(data) == refimpl.sm3(data), n


def test_suite_host_hash_uses_native_when_available():
    from fisco_bcos_tpu.crypto import nativehash, refimpl
    from fisco_bcos_tpu.crypto.suite import make_suite

    s = make_suite(backend="host")
    if nativehash.keccak256() is not None:
        assert s._host_hash is not refimpl.keccak256
    assert s.hash(b"abc") == refimpl.keccak256(b"abc")
    sm = make_suite(True, backend="host")
    assert sm.hash(b"abc") == refimpl.sm3(b"abc")


def test_native_host_hash_accepts_buffer_types():
    from fisco_bcos_tpu.crypto import nativehash, refimpl

    nk = nativehash.keccak256()
    if nk is None:
        import pytest
        pytest.skip("libnevm.so not built")
    want = refimpl.keccak256(b"buffer-shapes")
    assert nk(bytearray(b"buffer-shapes")) == want
    assert nk(memoryview(b"buffer-shapes")) == want


def _pad_loop(msgs, pad_fn, block_bytes, batch, nblocks):
    """The reference layout: each message padded alone by `pad_fn` and
    copied into its row of a zeroed batch."""
    blocks = np.zeros((batch, nblocks, block_bytes), dtype=np.uint8)
    nvalid = np.zeros((batch,), dtype=np.int32)
    for i, m in enumerate(msgs):
        p = pad_fn(m)
        blocks[i, : p.shape[0]] = p
        nvalid[i] = p.shape[0]
    return blocks, nvalid


_ALGS = {"keccak": (keccak, keccak.RATE_BYTES), "sm3": (sm3, sm3.BLOCK_BYTES)}


def _pack_lengths(alg):
    rate = _ALGS[alg][1]
    lens = [0, 1, rate - 1, rate, rate + 1, 3 * rate + 1]
    return lens + ([55, 56, 63, 64] if alg == "sm3" else [])


_PACK_CASES = [(alg, n) for alg in _ALGS for n in _pack_lengths(alg)] + [
    (alg, "mixed") for alg in _ALGS]


@pytest.mark.parametrize("alg,length", _PACK_CASES,
                         ids=[f"{a}-{n}" for a, n in _PACK_CASES])
def test_pack_batch_matches_the_pad_loop(alg, length):
    """The one-join pack lays a batch out byte for byte as `_pad_loop`:
    blocks, block counts, zero blocks past each message's pad and
    zero rows past the batch, under a bucket larger than the batch and a
    block axis longer than the longest message."""
    mod, rate = _ALGS[alg]
    r = random.Random(f"{alg}-{length}")
    lens = _pack_lengths(alg) if length == "mixed" else [length] * 3
    msgs = [r.randbytes(n) for n in lens]
    longest = max(mod.nblocks_of(n) for n in lens)
    batch, nblocks = len(msgs) + 5, longest + 2
    blocks, nvalid = keccak.pack_batch_np(msgs, mod.pad_tail, mod.nblocks_of,
                                          rate, batch, nblocks)
    want_blocks, want_nvalid = _pad_loop(msgs, mod.pad_message_np, rate,
                                         batch, nblocks)
    assert blocks.shape == (batch, nblocks, rate) and blocks.dtype == np.uint8
    assert nvalid.dtype == np.int32
    assert np.array_equal(blocks, want_blocks)
    assert np.array_equal(nvalid, want_nvalid)
    for i, m in enumerate(msgs):
        k = mod.nblocks_of(len(m))
        assert nvalid[i] == k
        assert np.array_equal(blocks[i, :k], mod.pad_message_np(m))
        assert not blocks[i, k:].any()
    assert not blocks[len(msgs):].any() and not nvalid[len(msgs):].any()


@pytest.mark.parametrize("sm", [False, True], ids=["keccak", "sm3"])
def test_device_hash_batch_keeps_order_around_host_messages(sm, monkeypatch):
    """A batch of 512 on the device door (the JAX kernels on the CPU),
    split into chunks, with messages past HASH_MAX_BLOCKS interleaved:
    every digest is the host hasher's, in the order asked."""
    from fisco_bcos_tpu.crypto import suite as suite_mod
    from fisco_bcos_tpu.crypto.suite import HASH_MAX_BLOCKS, make_suite

    monkeypatch.setattr(suite_mod, "CHUNK", 200)
    dev = make_suite(sm, backend="device", allow_cpu=True)
    host = make_suite(sm, backend="host")
    mod, rate = _ALGS["sm3" if sm else "keccak"]
    r = random.Random(41 + sm)
    big = {3, 100, 257, 400, 511}
    msgs = [r.randbytes(rate * HASH_MAX_BLOCKS + r.randrange(300)) if i in big
            else r.randbytes((i * 37) % (3 * rate)) for i in range(512)]
    assert {i for i, m in enumerate(msgs)
            if mod.nblocks_of(len(m)) > HASH_MAX_BLOCKS} == big
    assert dev.hash_batch(msgs) == host.hash_batch(msgs)
    ops = dev.status()["ops"]["hash"]
    assert (ops["hostCalls"], ops["hostItems"]) == (1, len(big))
    assert (ops["deviceCalls"], ops["deviceItems"]) == (3, 512 - len(big))
