"""Whole-tree Merkle root in ONE Pallas kernel (Keccak-256 / SM3).

NOT on the TPU dispatch: Mosaic refuses this kernel at lowering (see the
note above `ops.merkle.merkle_root`; ROADMAP queue 1 item 5 owns the
rewrite). It runs in interpret mode in the CPU tests; `_keccak_rounds` and
`_sm3_compress_values` below are shared with ops.pallas_hash, which does
compile.

The XLA Merkle path (`ops.merkle._merkle_root_bucketed`) emits ~2.5k vector
ops per tree level (4 sponge blocks x 24 rounds x ~30 ops, plus padding and
masking glue), every level a round trip through HBM. Here the ENTIRE tree
runs inside a single pallas_call: the level node arrays are VALUES carried through the unrolled
level loop (widths are static, shrinking 16x per level), each level hashes
all width-16 groups vectorized over sublanes x lanes, and only the 32-byte
root leaves the chip.

Logical-count masking matches ops.merkle bit-for-bit: the bucket is padded
with zero digests, parents beyond ceil(n/16^k) are zeroed, and the root is
captured at the first level whose live count collapses to 1.

Reference counterpart: bcos-crypto's width-16 Merkle
(/root/reference/bcos-crypto/bcos-crypto/merkle/Merkle.h:36-120) and the
tbb-parallel ParallelMerkleProof
(/root/reference/bcos-protocol/bcos-protocol/ParallelMerkleProof.cpp:32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import keccak as _keccak
from . import pallas_fp
from . import sm3 as _sm3

WIDTH = 16
DIGEST = 32
NODE_BYTES = WIDTH * DIGEST  # 512
U32 = jnp.uint32


# ---------------------------------------------------------------------------
# in-kernel Keccak-256 of [k, 512]-byte nodes (value-level, Mosaic-safe)
# ---------------------------------------------------------------------------

def _words_from_bytes_le(b):
    """[k, nbytes] uint8 -> (hi, lo) [k, nbytes//8] uint32, little-endian."""
    w = (b[:, 0::4].astype(U32)
         | (b[:, 1::4].astype(U32) << U32(8))
         | (b[:, 2::4].astype(U32) << U32(16))
         | (b[:, 3::4].astype(U32) << U32(24)))
    return w[:, 1::2], w[:, 0::2]


def _digest_bytes_le(hi, lo):
    """(hi, lo) [k, 4] uint32 -> [k, 32] uint8 (LE per 64-bit lane)."""
    k = hi.shape[0]
    w = jnp.stack([lo, hi], axis=-1).reshape(k, 8)
    b = jnp.stack([(w >> U32(8 * i)) & U32(0xFF) for i in range(4)],
                  axis=-1).reshape(k, 32)
    return b.astype(jnp.uint8)


def _keccak_rounds(sh, sl, rc_hi_ref, rc_lo_ref):
    """24 rounds on stacked state [25, k]; round consts from SMEM refs."""

    def body(r, st):
        h, l = st
        H = [h[i] for i in range(25)]
        L = [l[i] for i in range(25)]
        H, L = _keccak.round_lists(H, L, rc_hi_ref[r], rc_lo_ref[r])
        return (jnp.stack(H, axis=0), jnp.stack(L, axis=0))

    return jax.lax.fori_loop(0, 24, body, (sh, sl))


def _keccak_node_hash(nodes_u8, rc_hi_ref, rc_lo_ref):
    """[k, 512] uint8 (one width-16 group per row) -> [k, 32] digests.

    512 bytes + pad -> 4 rate blocks; block 4 is 13 data words + the
    constant padding words (0x01 after the data, 0x80 closing the rate).
    """
    k = nodes_u8.shape[0]
    bh, bl = _words_from_bytes_le(nodes_u8)  # [k, 64] words each
    sh = jnp.zeros((25, k), U32)
    sl = jnp.zeros((25, k), U32)
    rw = _keccak.RATE_WORDS  # 17
    for blk in range(4):
        if blk < 3:
            wh, wl = (bh[:, blk * rw:(blk + 1) * rw],
                      bl[:, blk * rw:(blk + 1) * rw])
        else:
            nw = 64 - 3 * rw  # 13 remaining data words
            ph = jnp.zeros((k, rw - nw), U32)
            pl_ = jnp.zeros((k, rw - nw), U32)
            pl_ = pl_.at[:, 0].set(U32(0x01))       # pad 0x01 at byte 512
            ph = ph.at[:, -1].set(U32(0x80000000))  # pad 0x80 at byte 135
            wh = jnp.concatenate([bh[:, 3 * rw:], ph], axis=1)
            wl = jnp.concatenate([bl[:, 3 * rw:], pl_], axis=1)
        xh = jnp.concatenate([jnp.transpose(wh),
                              jnp.zeros((25 - rw, k), U32)], axis=0)
        xl = jnp.concatenate([jnp.transpose(wl),
                              jnp.zeros((25 - rw, k), U32)], axis=0)
        sh, sl = _keccak_rounds(sh ^ xh, sl ^ xl, rc_hi_ref, rc_lo_ref)
    return _digest_bytes_le(jnp.transpose(sh[:4]), jnp.transpose(sl[:4]))


# ---------------------------------------------------------------------------
# in-kernel SM3 of [k, 512]-byte nodes
# ---------------------------------------------------------------------------

def _sm3_compress_values(V, W16):
    """Kernel-safe SM3 compress: V = list of 8 [k] arrays, W16 = list of
    16 [k] big-endian word arrays. Rounds and expansion are Python-
    unrolled with scalar constants only (Mosaic rejects captured array
    constants; scan xs would capture them)."""
    W = list(W16)
    for j in range(52):  # message expansion -> W[0..67]
        nw = (_sm3._p1(W[j] ^ W[j + 7] ^ _sm3._rotl(W[j + 13], 15))
              ^ _sm3._rotl(W[j + 3], 7) ^ W[j + 10])
        W.append(nw)
    A, B, C, D, E, F, G, H = V
    for j in range(64):
        tjrot = U32(int(_sm3._TJROT[j]))
        a12 = _sm3._rotl(A, 12)
        SS1 = _sm3._rotl(a12 + E + tjrot, 7)
        SS2 = SS1 ^ a12
        if j < 16:
            FF = A ^ B ^ C
            GG = E ^ F ^ G
        else:
            FF = (A & B) | (A & C) | (B & C)
            GG = (E & F) | (~E & G)
        TT1 = FF + D + SS2 + (W[j] ^ W[j + 4])
        TT2 = GG + H + SS1 + W[j]
        A, B, C, D, E, F, G, H = (TT1, A, _sm3._rotl(B, 9), C,
                                  _sm3._p0(TT2), E, _sm3._rotl(F, 19), G)
    return [v ^ o for v, o in zip(V, (A, B, C, D, E, F, G, H))]


def _sm3_node_hash(nodes_u8, _h, _l):
    """[k, 512] uint8 -> [k, 32] SM3 digests (9 compress blocks: 512 bytes
    + 0x80 + 8-byte bit length)."""
    k = nodes_u8.shape[0]
    w = ((nodes_u8[:, 0::4].astype(U32) << U32(24))
         | (nodes_u8[:, 1::4].astype(U32) << U32(16))
         | (nodes_u8[:, 2::4].astype(U32) << U32(8))
         | nodes_u8[:, 3::4].astype(U32))  # [k, 128] big-endian words
    pad = jnp.zeros((k, 16 * 9 - 128), U32)
    pad = pad.at[:, 0].set(U32(0x80000000))
    pad = pad.at[:, -1].set(U32(NODE_BYTES * 8))
    words = jnp.concatenate([w, pad], axis=1)  # [k, 144]
    V = [jnp.broadcast_to(U32(int(v)), (k,)) for v in _sm3._IV]
    for blk in range(9):
        W16 = [words[:, blk * 16 + j] for j in range(16)]
        V = _sm3_compress_values(V, W16)
    out = jnp.stack(V, axis=-1)  # [k, 8] big-endian words
    b = jnp.stack([(out >> U32(24 - 8 * i)) & U32(0xFF) for i in range(4)],
                  axis=-1).reshape(k, 32)
    return b.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# the whole-tree kernel
# ---------------------------------------------------------------------------

def _levels_for(nbucket: int) -> list[int]:
    """Static group counts per level, e.g. 10240 -> [640, 40, 3, 1]."""
    out = []
    m = nbucket
    while m > 1:
        m = -(-m // WIDTH)
        out.append(m)
    return out


@functools.lru_cache(maxsize=None)
def _tree_call(nbucket: int, alg: str, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    node_hash = (_keccak_node_hash if alg == "keccak256"
                 else _sm3_node_hash)
    levels = _levels_for(nbucket)

    def kernel(n_ref, rch_ref, rcl_ref, leaves_ref, root_ref):
        count = n_ref[0]
        nodes = leaves_ref[:, :]  # [nbucket, 32] value
        root = nodes[0:1, :]      # n <= 1 case
        found = count <= 1
        for m in levels:
            need = m * WIDTH
            if need > nodes.shape[0]:  # zero-pad to a full group multiple
                nodes = jnp.concatenate(
                    [nodes, jnp.zeros((need - nodes.shape[0], DIGEST),
                                      jnp.uint8)], axis=0)
            parents = node_hash(nodes.reshape(m, NODE_BYTES),
                                rch_ref, rcl_ref)
            count = (count + (WIDTH - 1)) // WIDTH
            live = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0) < count
            parents = jnp.where(live, parents, jnp.zeros_like(parents))
            is_root = jnp.logical_and(jnp.logical_not(found), count <= 1)
            root = jnp.where(is_root, parents[0:1, :], root)
            found = jnp.logical_or(found, is_root)
            nodes = parents
        root_ref[:, :] = root

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, DIGEST), jnp.uint8),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )


def merkle_root_fused(leaves_padded, n: "jax.Array | int",
                      alg: str = "keccak256", interpret: bool = False):
    """Root of the canonical width-16 tree.

    leaves_padded: [nbucket, 32] uint8, zero-padded beyond the logical
    count; n: logical leaf count (traced or static). Returns [32] uint8.
    """
    nbucket = int(leaves_padded.shape[0])
    nvec = jnp.asarray([n], jnp.int32)
    rc_hi = jnp.asarray(_keccak._RC_HI)
    rc_lo = jnp.asarray(_keccak._RC_LO)
    out = _tree_call(nbucket, alg, pallas_fp._auto_interpret(interpret))(
        nvec, rc_hi, rc_lo, jnp.asarray(leaves_padded, jnp.uint8))
    return out[0]
