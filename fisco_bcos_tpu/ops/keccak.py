"""Batched Keccak-256 (Ethereum-style, pad 0x01) on TPU.

Replaces the reference's OpenSSL EVP Keccak256 hasher
(/root/reference/bcos-crypto/bcos-crypto/hash/Keccak256.h:31,
 hasher/OpenSSLHasher.h:23) with a vmappable JAX kernel.

TPU has no 64-bit integers, so each of the 25 Keccak lanes is a
(hi, lo) pair of uint32; rotations become paired-word shifts. The
permutation is fully unrolled (24 rounds ≈ a few thousand VPU ops) and
vectorises over a leading batch axis — hashing 64k transaction payloads or
Merkle nodes is one fused XLA program.

Message layout: callers supply fixed-shape blocks. For variable-length
batches use `keccak256_varlen`, which scans over the padded block axis and
masks absorption per message (dynamic shapes are hostile to XLA; padding to
a bucketed max is the TPU-native answer to the reference's arbitrary-length
`hasher.update()` streams).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

RATE_BYTES = 136  # 1088-bit rate for Keccak-256
RATE_WORDS = RATE_BYTES // 8  # 17 lanes
U32 = jnp.uint32

# round constants as (hi, lo) uint32 pairs
_RC64 = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
_RC_HI = np.array([(rc >> 32) & 0xFFFFFFFF for rc in _RC64], dtype=np.uint32)
_RC_LO = np.array([rc & 0xFFFFFFFF for rc in _RC64], dtype=np.uint32)

# rotation offsets r[x][y] laid out by lane index i = x + 5*y
_ROT = [0, 1, 62, 28, 27,
        36, 44, 6, 55, 20,
        3, 10, 43, 25, 39,
        41, 45, 15, 21, 8,
        18, 2, 61, 56, 14]

# pi permutation: lane i moves to _PI[i] (dest index) — computed from
# B[y, 2x+3y] = rot(A[x,y]); build source table instead.
_PI_SRC = [0] * 25
for x in range(5):
    for y in range(5):
        src = x + 5 * y
        dst = y + 5 * ((2 * x + 3 * y) % 5)
        _PI_SRC[dst] = src


def _rotl64(hi, lo, r):
    r = r % 64
    if r == 0:
        return hi, lo
    if r == 32:
        return lo, hi
    if r < 32:
        nhi = (hi << np.uint32(r)) | (lo >> np.uint32(32 - r))
        nlo = (lo << np.uint32(r)) | (hi >> np.uint32(32 - r))
        return nhi, nlo
    r -= 32
    nhi = (lo << np.uint32(r)) | (hi >> np.uint32(32 - r))
    nlo = (hi << np.uint32(r)) | (lo >> np.uint32(32 - r))
    return nhi, nlo


def _round(hi, lo, rc_hi, rc_lo):
    """One Keccak round on stacked lanes [..., 25]."""
    H = [hi[..., i] for i in range(25)]
    L = [lo[..., i] for i in range(25)]
    H, L = round_lists(H, L, rc_hi, rc_lo)
    return jnp.stack(H, axis=-1), jnp.stack(L, axis=-1)


def round_lists(H, L, rc_hi, rc_lo):
    """One Keccak round on 25 (hi, lo) lane arrays of any uniform shape.

    List-based so fused Pallas kernels (ops.pallas_merkle) can inline it on
    row-sliced state without the lane-axis stack/unstack.
    """
    H, L = list(H), list(L)
    # theta
    CH = [H[x] ^ H[x + 5] ^ H[x + 10] ^ H[x + 15] ^ H[x + 20] for x in range(5)]
    CL = [L[x] ^ L[x + 5] ^ L[x + 10] ^ L[x + 15] ^ L[x + 20] for x in range(5)]
    for x in range(5):
        rh, rl = _rotl64(CH[(x + 1) % 5], CL[(x + 1) % 5], 1)
        dh = CH[(x + 4) % 5] ^ rh
        dl = CL[(x + 4) % 5] ^ rl
        for y in range(5):
            H[x + 5 * y] = H[x + 5 * y] ^ dh
            L[x + 5 * y] = L[x + 5 * y] ^ dl
    # rho + pi
    BH = [None] * 25
    BL = [None] * 25
    for dst in range(25):
        src = _PI_SRC[dst]
        BH[dst], BL[dst] = _rotl64(H[src], L[src], _ROT[src])
    # chi
    for y in range(5):
        for x in range(5):
            i = x + 5 * y
            H[i] = BH[i] ^ (~BH[(x + 1) % 5 + 5 * y] & BH[(x + 2) % 5 + 5 * y])
            L[i] = BL[i] ^ (~BL[(x + 1) % 5 + 5 * y] & BL[(x + 2) % 5 + 5 * y])
    # iota
    H[0] = H[0] ^ rc_hi
    L[0] = L[0] ^ rc_lo
    return H, L


def keccak_f(hi, lo):
    """Keccak-f[1600] permutation.

    hi, lo: [..., 25] uint32 — high/low words of the 25 lanes (lane index
    i = x + 5*y), little-endian 64-bit lanes. Scanned over the 24 rounds to
    keep the traced graph small (the Merkle reduction inlines this many
    times per tree level).
    """

    def body(carry, rc):
        h, l = carry
        h, l = _round(h, l, rc[0], rc[1])
        return (h, l), None

    rcs = jnp.stack([jnp.asarray(_RC_HI), jnp.asarray(_RC_LO)], axis=-1)
    (hi, lo), _ = jax.lax.scan(body, (hi, lo), rcs)
    return hi, lo


def bytes_to_words(data: jax.Array):
    """[..., nbytes] uint8 (nbytes % 8 == 0) -> (hi, lo) [..., nbytes//8] uint32, LE."""
    b = data.astype(U32)
    w = b[..., 0::4] | (b[..., 1::4] << U32(8)) | (b[..., 2::4] << U32(16)) | (
        b[..., 3::4] << U32(24))
    return w[..., 1::2], w[..., 0::2]


def words_to_bytes(hi: jax.Array, lo: jax.Array):
    """(hi, lo) [..., n] uint32 -> [..., 8n] uint8, little-endian per 64-bit lane."""
    n = lo.shape[-1]
    w = jnp.stack([lo, hi], axis=-1).reshape(lo.shape[:-1] + (2 * n,))
    b = jnp.stack(
        [(w >> U32(8 * k)) & U32(0xFF) for k in range(4)], axis=-1
    ).reshape(lo.shape[:-1] + (8 * n,))
    return b.astype(jnp.uint8)


def _absorb_block(state_hi, state_lo, block_hi, block_lo):
    pad_h = jnp.zeros_like(state_hi[..., : 25 - RATE_WORDS])
    pad_l = jnp.zeros_like(state_lo[..., : 25 - RATE_WORDS])
    bh = jnp.concatenate([block_hi, pad_h], axis=-1)
    bl = jnp.concatenate([block_lo, pad_l], axis=-1)
    return keccak_f(state_hi ^ bh, state_lo ^ bl)


def keccak256_blocks(blocks_u8: jax.Array) -> jax.Array:
    """Keccak-256 of pre-padded messages.

    blocks_u8: [..., nblocks, RATE_BYTES] uint8, already Keccak-padded
    (0x01 ... 0x80). Returns [..., 32] uint8 digests.
    """
    nblocks = blocks_u8.shape[-2]
    sh = jnp.zeros(blocks_u8.shape[:-2] + (25,), U32)
    sl = jnp.zeros(blocks_u8.shape[:-2] + (25,), U32)
    for i in range(nblocks):
        bh, bl = bytes_to_words(blocks_u8[..., i, :])
        sh, sl = _absorb_block(sh, sl, bh, bl)
    return words_to_bytes(sh[..., :4], sl[..., :4])


def pad_message_np(msg: bytes) -> np.ndarray:
    """Host-side Keccak pad -> [nblocks, RATE_BYTES] uint8."""
    n = len(msg)
    nblocks = n // RATE_BYTES + 1
    buf = np.zeros(nblocks * RATE_BYTES, dtype=np.uint8)
    buf[:n] = np.frombuffer(msg, dtype=np.uint8)
    buf[n] ^= 0x01
    buf[-1] ^= 0x80
    return buf.reshape(nblocks, RATE_BYTES)


@functools.partial(jax.jit, static_argnames=("nblocks",))
def _keccak256_varlen_impl(blocks_u8, nvalid, nblocks):
    sh = jnp.zeros(blocks_u8.shape[:-2] + (25,), U32)
    sl = jnp.zeros(blocks_u8.shape[:-2] + (25,), U32)
    for i in range(nblocks):
        bh, bl = bytes_to_words(blocks_u8[..., i, :])
        nh, nl = _absorb_block(sh, sl, bh, bl)
        live = (nvalid > i)[..., None]
        sh = jnp.where(live, nh, sh)
        sl = jnp.where(live, nl, sl)
    return words_to_bytes(sh[..., :4], sl[..., :4])


def keccak256_varlen(blocks_u8: jax.Array, nvalid: jax.Array) -> jax.Array:
    """Variable-length batch: [B, maxblocks, RATE_BYTES] pre-padded blocks,
    nvalid[B] = per-message block count. Messages shorter than maxblocks
    mask out the trailing permutations. Returns [B, 32] digests. Both
    implementations are one jit each: one compile per (B, maxblocks)."""
    from . import fp as _fp
    if _fp._use_pallas() and blocks_u8.ndim == 3 and blocks_u8.shape[0]:
        from . import pallas_hash

        if pallas_hash.keccak_fused_ok(blocks_u8.shape[1]):
            return pallas_hash.keccak256_varlen_fused(blocks_u8, nvalid)
    return _keccak256_varlen_impl(blocks_u8, nvalid, blocks_u8.shape[-2])


def pad_tail(n: int, nblocks: int) -> bytes:
    """What follows a message of n bytes in its row of `nblocks` rate
    blocks: Keccak's pad (0x01, zeros, 0x80 closing the message's last
    block; one 0x81 where they meet), then zero blocks. The message and its
    tail are `pad_message_np(msg)` and `nblocks - nblocks_of(n)` zero
    blocks, byte for byte."""
    k = nblocks_of(n) * RATE_BYTES - n
    pad = b"\x81" if k == 1 else b"\x01" + bytes(k - 2) + b"\x80"
    return pad + bytes((nblocks - nblocks_of(n)) * RATE_BYTES)


def pack_batch_np(msgs, tail_fn, nblocks_fn, block_bytes: int, batch: int,
                  nblocks: int):
    """Host-side packing shared by the Keccak and SM3 batch APIs: the batch
    as [batch, nblocks, block_bytes] uint8 + per-message block counts. One
    `b"".join` of every message beside its `tail_fn(len, nblocks)` (pad and
    zero blocks, made once per length) and zero rows up to `batch`, viewed
    as one array; the counts are `nblocks_fn` of the lengths, in one numpy
    step. `batch`/`nblocks` are the CALLER's buckets (>= len(msgs) / the
    longest message): every distinct pair is one compiled program, so the
    caller keeps the set small."""
    n = len(msgs)
    lens = list(map(len, msgs))
    tails = {k: tail_fn(k, nblocks) for k in set(lens)}
    parts = [b""] * (2 * n + 1)  # m0, tail0, m1, tail1, ..., zero rows
    parts[0:2 * n:2] = msgs
    parts[1:2 * n:2] = map(tails.__getitem__, lens)
    parts[-1] = bytes((batch - n) * nblocks * block_bytes)
    blocks = np.frombuffer(b"".join(parts), np.uint8).reshape(
        batch, nblocks, block_bytes)
    nvalid = np.zeros((batch,), dtype=np.int32)
    nvalid[:n] = nblocks_fn(np.fromiter(lens, np.int64, n))
    return blocks, nvalid


def nblocks_of(n: int) -> int:
    """Rate blocks a message of n bytes pads to."""
    return n // RATE_BYTES + 1


def keccak256_batch_np(msgs: list[bytes], batch: int | None = None,
                       nblocks: int | None = None) -> np.ndarray:
    """Host API: pad on host into the (batch, nblocks) bucket (default: the
    exact batch size and longest message), hash on device, return
    [len(msgs), 32] uint8."""
    batch = batch or len(msgs)
    nblocks = nblocks or max(nblocks_of(len(m)) for m in msgs)
    blocks, nvalid = pack_batch_np(msgs, pad_tail, nblocks_of, RATE_BYTES,
                                   batch, nblocks)
    return np.asarray(keccak256_varlen(blocks, nvalid))[: len(msgs)]
