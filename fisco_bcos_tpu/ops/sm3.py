"""Batched SM3 (GB/T 32905-2016, 国密 hash) on TPU.

Replaces the reference's OpenSSL EVP SM3 hasher
(/root/reference/bcos-crypto/bcos-crypto/hash/SM3.h via
 hasher/OpenSSLHasher.h:23). SM3 is a Merkle–Damgård design over 32-bit
words — it maps 1:1 onto TPU uint32 lanes; the 64-round compression is
unrolled and vectorises over a leading batch axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
BLOCK_BYTES = 64

_IV = np.array(
    [0x7380166F, 0x4914B2B9, 0x172442D7, 0xDA8A0600,
     0xA96F30BC, 0x163138AA, 0xE38DEE4D, 0xB0FB0E4E],
    dtype=np.uint32,
)
_TJ = np.array(
    [0x79CC4519] * 16 + [0x7A879D8A] * 48, dtype=np.uint64
)


def _rotl(x, r):
    r %= 32
    if r == 0:
        return x
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _p0(x):
    return x ^ _rotl(x, 9) ^ _rotl(x, 17)


def _p1(x):
    return x ^ _rotl(x, 15) ^ _rotl(x, 23)


# per-round constants rotl(Tj, j) and early-phase flags, precomputed on host
_TJROT = np.array(
    [((int(_TJ[j]) << (j % 32)) | (int(_TJ[j]) >> (32 - j % 32))) & 0xFFFFFFFF
     if j % 32 else int(_TJ[j]) for j in range(64)],
    dtype=np.uint32,
)
_EARLY = np.array([j < 16 for j in range(64)])


def _compress(V, W):
    """One SM3 compression. V: list of 8 [...] uint32; W: [..., 16] uint32 (BE
    words). Message expansion and the 64 rounds are lax.scans to keep the
    traced graph small."""

    def expand(carry, _):
        # carry: [..., 16] rolling window W[j-16..j-1]
        new = (
            _p1(carry[..., 0] ^ carry[..., 7] ^ _rotl(carry[..., 13], 15))
            ^ _rotl(carry[..., 3], 7)
            ^ carry[..., 10]
        )
        return jnp.concatenate([carry[..., 1:], new[..., None]], axis=-1), new

    _, Wext = jax.lax.scan(expand, W, None, length=52)  # [52, ...]
    W_all = jnp.concatenate([jnp.moveaxis(W, -1, 0), Wext], axis=0)  # [68, ...]

    def round_body(carry, xs):
        A, B, C, D, E, F, G, H = carry
        wj, wj4, tjrot, early = xs
        a12 = _rotl(A, 12)
        SS1 = _rotl(a12 + E + tjrot, 7)
        SS2 = SS1 ^ a12
        FF = jnp.where(early, A ^ B ^ C, (A & B) | (A & C) | (B & C))
        GG = jnp.where(early, E ^ F ^ G, (E & F) | (~E & G))
        TT1 = FF + D + SS2 + (wj ^ wj4)
        TT2 = GG + H + SS1 + wj
        return (TT1, A, _rotl(B, 9), C, _p0(TT2), E, _rotl(F, 19), G), None

    xs = (W_all[:64], W_all[4:68], jnp.asarray(_TJROT), jnp.asarray(_EARLY))
    out, _ = jax.lax.scan(round_body, tuple(V), xs)
    return [v ^ o for v, o in zip(V, out)]


def bytes_to_be_words(data: jax.Array):
    """[..., nbytes] uint8 (mult of 4) -> [..., nbytes//4] uint32 big-endian."""
    b = data.astype(U32)
    return (b[..., 0::4] << U32(24)) | (b[..., 1::4] << U32(16)) | (
        b[..., 2::4] << U32(8)) | b[..., 3::4]


def be_words_to_bytes(w: jax.Array):
    b = jnp.stack(
        [(w >> U32(24)) & U32(0xFF), (w >> U32(16)) & U32(0xFF),
         (w >> U32(8)) & U32(0xFF), w & U32(0xFF)], axis=-1
    )
    return b.reshape(w.shape[:-1] + (w.shape[-1] * 4,)).astype(jnp.uint8)


def sm3_blocks(blocks_u8: jax.Array) -> jax.Array:
    """SM3 of pre-padded messages: [..., nblocks, 64] uint8 -> [..., 32] uint8."""
    nblocks = blocks_u8.shape[-2]
    batch = blocks_u8.shape[:-2]
    V = [jnp.broadcast_to(U32(int(v)), batch) for v in _IV]
    for i in range(nblocks):
        W = bytes_to_be_words(blocks_u8[..., i, :])
        V = _compress(V, W)
    return be_words_to_bytes(jnp.stack(V, axis=-1))


@functools.partial(jax.jit, static_argnames=("nblocks",))
def _sm3_varlen_impl(blocks_u8, nvalid, nblocks):
    batch = blocks_u8.shape[:-2]
    V = [jnp.broadcast_to(U32(int(v)), batch) for v in _IV]
    for i in range(nblocks):
        W = bytes_to_be_words(blocks_u8[..., i, :])
        NV = _compress(V, W)
        live = nvalid > i
        V = [jnp.where(live, nv, v) for nv, v in zip(NV, V)]
    return be_words_to_bytes(jnp.stack(V, axis=-1))


def sm3_varlen(blocks_u8: jax.Array, nvalid: jax.Array) -> jax.Array:
    """[B, maxblocks, 64] pre-padded blocks + per-message block counts ->
    [B, 32] digests; each implementation is one jit."""
    from . import fp as _fp
    if _fp._use_pallas() and blocks_u8.ndim == 3 and blocks_u8.shape[0]:
        from . import pallas_hash

        if pallas_hash.sm3_fused_ok(blocks_u8.shape[1]):
            return pallas_hash.sm3_varlen_fused(blocks_u8, nvalid)
    return _sm3_varlen_impl(blocks_u8, nvalid, blocks_u8.shape[-2])


def pad_message_np(msg: bytes) -> np.ndarray:
    """Host-side SHA-2-style pad -> [nblocks, 64] uint8."""
    n = len(msg)
    total = ((n + 8) // BLOCK_BYTES + 1) * BLOCK_BYTES
    buf = np.zeros(total, dtype=np.uint8)
    buf[:n] = np.frombuffer(msg, dtype=np.uint8)
    buf[n] = 0x80
    bitlen = n * 8
    for k in range(8):
        buf[total - 1 - k] = (bitlen >> (8 * k)) & 0xFF
    return buf.reshape(-1, BLOCK_BYTES)


def nblocks_of(n: int) -> int:
    """Compression blocks a message of n bytes pads to."""
    return (n + 8) // BLOCK_BYTES + 1


def pad_tail(n: int, nblocks: int) -> bytes:
    """What follows a message of n bytes in its row of `nblocks` blocks:
    SM3's pad (0x80, zeros, the 64-bit big-endian bit length), then zero
    blocks; with the message, `pad_message_np(msg)` and zero blocks."""
    k = nblocks_of(n) * BLOCK_BYTES - n
    return (b"\x80" + bytes(k - 9) + (8 * n).to_bytes(8, "big")
            + bytes((nblocks - nblocks_of(n)) * BLOCK_BYTES))


def sm3_batch_np(msgs: list[bytes], batch: int | None = None,
                 nblocks: int | None = None) -> np.ndarray:
    """Host API, bucketed like keccak.keccak256_batch_np."""
    from .keccak import pack_batch_np

    batch = batch or len(msgs)
    nblocks = nblocks or max(nblocks_of(len(m)) for m in msgs)
    blocks, nvalid = pack_batch_np(msgs, pad_tail, nblocks_of, BLOCK_BYTES,
                                   batch, nblocks)
    return np.asarray(sm3_varlen(blocks, nvalid))[: len(msgs)]
