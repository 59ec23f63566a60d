"""Fused variable-length batch hashing (Keccak-256 / SM3) in one kernel.

The XLA varlen hashers (`keccak.keccak256_varlen`, `sm3.sm3_varlen`) emit
~300 vector ops per permutation round at the XLA level, each round's state
a round trip through HBM, and they sit in two production paths:
transaction-hash fill (protocol/types.py:305) and state-root leaves
(executor/executor.py:623). Here the whole sponge/compression runs inside
a single pallas_call: per-message block counts mask the absorb loop
exactly like the XLA implementations, states stay in vregs, and only the
digests leave the kernel.

Byte->word packing and lane transposes happen OUTSIDE the kernel (a
handful of XLA ops); the kernel consumes lane-major word planes.

Reference counterpart: the OpenSSL EVP hashers behind
/root/reference/bcos-crypto/bcos-crypto/hash/{Keccak256,SM3}.h and their
per-transaction use in Transaction::verify (bcos-framework protocol/
Transaction.h:68-82) — rebuilt batch-first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import keccak as _keccak
from . import pallas_fp
from . import sm3 as _sm3
from .pallas_merkle import _keccak_rounds, _sm3_compress_values

U32 = jnp.uint32
BLK = 1024  # lanes per kernel instance

# The input tile is [nblocks, words, blk] u32 (x2 planes for keccak) and
# the pipeline double-buffers it. Budget the tile: shrink blk as nblocks
# grows; when even blk=128 exceeds the budget the fused path is ineligible
# and the ops.keccak / ops.sm3 varlen dispatch takes the XLA scan
# implementation. 2 MiB (4 MiB double-buffered) stays well inside v5e's
# 16 MiB scoped-VMEM default; at 6 MiB a 177-block Keccak batch failed on
# the chip with RESOURCE_EXHAUSTED in vmem. The block loop is also
# unrolled, so a long message costs compile time before it costs VMEM —
# CryptoSuite.hash_batch keeps messages above HASH_MAX_BLOCKS on the host.
_VMEM_TILE_BUDGET = 2 * 1024 * 1024


def _tile_blk_cap(nblocks: int, words: int, planes: int) -> int:
    """Largest power-of-two blk in [128, BLK] whose input tile fits the
    VMEM budget; 0 when nothing fits (fused path ineligible)."""
    per_lane = nblocks * words * 4 * planes
    cap = _VMEM_TILE_BUDGET // max(1, per_lane)
    if cap < 128:
        return 0
    blk = 128
    while blk * 2 <= min(cap, BLK):
        blk *= 2
    return blk


def keccak_fused_ok(nblocks: int) -> bool:
    return _tile_blk_cap(nblocks, _keccak.RATE_WORDS, 2) >= 128


def sm3_fused_ok(nblocks: int) -> bool:
    return _tile_blk_cap(nblocks, 16, 1) >= 128


# ---------------------------------------------------------------------------
# Keccak-256 varlen
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _keccak_call(nblocks: int, B: int, blk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rw = _keccak.RATE_WORDS  # 17

    def kernel(rch_ref, rcl_ref, bh_ref, bl_ref, nv_ref, o_ref):
        k = bh_ref.shape[-1]
        sh = jnp.zeros((25, k), U32)
        sl = jnp.zeros((25, k), U32)
        for i in range(nblocks):
            xh = jnp.concatenate([bh_ref[i], jnp.zeros((25 - rw, k), U32)],
                                 axis=0)
            xl = jnp.concatenate([bl_ref[i], jnp.zeros((25 - rw, k), U32)],
                                 axis=0)
            nh, nl = _keccak_rounds(sh ^ xh, sl ^ xl, rch_ref, rcl_ref)
            live = (nv_ref[0] > i)[None, :]
            sh = jnp.where(live, nh, sh)
            sl = jnp.where(live, nl, sl)
        o_ref[:, :] = jnp.concatenate([sh[:4], sl[:4]], axis=0)

    spec = pl.BlockSpec((nblocks, rw, blk), lambda i: (0, 0, i))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((8, B), U32),  # hi[4] | lo[4]
        grid=(B // blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            spec, spec,
            pl.BlockSpec((1, blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((8, blk), lambda i: (0, i)),
        interpret=interpret,
    )


def _lane_pad(blocks_u8, nvalid):
    """Pad the batch axis to a 128-lane multiple (masked rows hash to
    garbage that the caller slices away). Returns (blocks, nvalid, B)."""
    blocks_u8 = jnp.asarray(blocks_u8, jnp.uint8)
    B = blocks_u8.shape[0]
    pad = (-B) % 128 if B else 128
    if pad:
        blocks_u8 = jnp.concatenate(
            [blocks_u8, jnp.zeros((pad,) + blocks_u8.shape[1:],
                                  jnp.uint8)], axis=0)
        nvalid = jnp.concatenate(
            [jnp.asarray(nvalid, jnp.int32), jnp.zeros((pad,), jnp.int32)])
    return blocks_u8, nvalid, B


@functools.partial(jax.jit, static_argnames=("interpret",))
def keccak256_varlen_fused(blocks_u8, nvalid, interpret: bool = False):
    """[B, nblocks, RATE_BYTES] pre-padded uint8 + per-message block count
    -> [B, 32] uint8 digests. Any B (lane padding handled here). One jit:
    the byte packing and lane transposes around the kernel compile WITH
    it — run eagerly they were a compile per op per new batch size."""
    blocks_u8, nvalid, B = _lane_pad(blocks_u8, nvalid)
    nblocks = blocks_u8.shape[1]
    bh, bl = _keccak.bytes_to_words(blocks_u8)  # [B', nb, 17]
    bh = jnp.transpose(bh, (1, 2, 0))  # [nb, 17, B'] lane-major
    bl = jnp.transpose(bl, (1, 2, 0))
    Bp = bh.shape[-1]
    blk = pallas_fp._pick_blk(
        Bp, _tile_blk_cap(nblocks, _keccak.RATE_WORDS, 2) or 128)
    out = _keccak_call(nblocks, Bp, blk,
                       pallas_fp._auto_interpret(interpret))(
        jnp.asarray(_keccak._RC_HI), jnp.asarray(_keccak._RC_LO),
        bh, bl, jnp.asarray(nvalid, jnp.int32)[None, :])
    hi, lo = out[:4, :B], out[4:, :B]
    return _keccak.words_to_bytes(jnp.transpose(hi), jnp.transpose(lo))


# ---------------------------------------------------------------------------
# SM3 varlen
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sm3_call(nblocks: int, B: int, blk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(bw_ref, nv_ref, o_ref):
        k = bw_ref.shape[-1]
        V = [jnp.broadcast_to(U32(int(v)), (k,)) for v in _sm3._IV]
        for i in range(nblocks):
            W16 = [bw_ref[i, j] for j in range(16)]
            NV = _sm3_compress_values(V, W16)
            live = nv_ref[0] > i
            V = [jnp.where(live, nv, v) for nv, v in zip(NV, V)]
        o_ref[:, :] = jnp.stack(V, axis=0)

    spec = pl.BlockSpec((nblocks, 16, blk), lambda i: (0, 0, i))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((8, B), U32),
        grid=(B // blk,),
        in_specs=[spec, pl.BlockSpec((1, blk), lambda i: (0, i))],
        out_specs=pl.BlockSpec((8, blk), lambda i: (0, i)),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def sm3_varlen_fused(blocks_u8, nvalid, interpret: bool = False):
    """[B, nblocks, 64] pre-padded uint8 + block counts -> [B, 32].
    Any B (lane padding handled here); one jit, as above."""
    blocks_u8, nvalid, B = _lane_pad(blocks_u8, nvalid)
    nblocks = blocks_u8.shape[1]
    w = _sm3.bytes_to_be_words(blocks_u8)  # [B', nb, 16]
    w = jnp.transpose(w, (1, 2, 0))  # [nb, 16, B']
    Bp = w.shape[-1]
    blk = pallas_fp._pick_blk(Bp, _tile_blk_cap(nblocks, 16, 1) or 128)
    out = _sm3_call(nblocks, Bp, blk,
                    pallas_fp._auto_interpret(interpret))(
        w, jnp.asarray(nvalid, jnp.int32)[None, :])
    return _sm3.be_words_to_bytes(jnp.transpose(out[:, :B]))
