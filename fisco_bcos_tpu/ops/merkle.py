"""Device-resident wide Merkle tree reduction (Keccak256 / SM3).

TPU-native counterpart of the reference's width-16 compile-time Merkle
(/root/reference/bcos-crypto/bcos-crypto/merkle/Merkle.h:36-120) and the tbb
parallel Merkle root (/root/reference/bcos-protocol/bcos-protocol/
ParallelMerkleProof.cpp:32-89), used for block transaction/receipt roots
(bcos-tars-protocol/bcos-tars-protocol/protocol/BlockImpl.h:111,156).

Canonical tree (this framework's protocol definition, deterministic and
identical on CPU fallback and TPU):
  - leaves: n 32-byte digests, n >= 1; a single leaf is its own root.
  - each level is zero-padded to a multiple of WIDTH; parent_i =
    H(children[16i] || ... || children[16i+15]) over the fixed 512-byte
    concatenation; levels repeat until one node remains.

To keep XLA shapes static with varying n, `merkle_root` buckets n up to the
next power of two and masks virtual nodes to zero digests at every level, so
the root for logical n is bit-identical regardless of bucket size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import keccak as _keccak
from . import sm3 as _sm3

WIDTH = 16
DIGEST = 32


def _hash_nodes(nodes: jax.Array, alg: str) -> jax.Array:
    """[k, WIDTH*DIGEST] uint8 -> [k, DIGEST] digests."""
    k, nbytes = nodes.shape
    if alg == "keccak256":
        rate = _keccak.RATE_BYTES
        nb = nbytes // rate + 1
        buf = jnp.zeros((k, nb * rate), jnp.uint8)
        buf = buf.at[:, :nbytes].set(nodes)
        buf = buf.at[:, nbytes].set(jnp.uint8(0x01))
        buf = buf.at[:, -1].add(jnp.uint8(0x80))
        return _keccak.keccak256_blocks(buf.reshape(k, nb, rate))
    elif alg == "sm3":
        blk = _sm3.BLOCK_BYTES
        total = ((nbytes + 8) // blk + 1) * blk
        buf = jnp.zeros((k, total), jnp.uint8)
        buf = buf.at[:, :nbytes].set(nodes)
        buf = buf.at[:, nbytes].set(jnp.uint8(0x80))
        bitlen = nbytes * 8
        for kk in range(8):
            v = (bitlen >> (8 * kk)) & 0xFF
            if v:
                buf = buf.at[:, total - 1 - kk].set(jnp.uint8(v))
        return _sm3.sm3_blocks(buf.reshape(k, total // blk, blk))
    raise ValueError(f"unknown hash alg {alg!r}")


@functools.partial(jax.jit, static_argnames=("alg",))
def _merkle_root_bucketed(leaves: jax.Array, n: jax.Array, alg: str) -> jax.Array:
    """leaves: [N_bucket, 32] uint8 (zero-padded); n: scalar int32 logical count.

    Returns [32] uint8 root for the logical-n canonical tree.
    """
    nbucket = leaves.shape[0]
    nodes = leaves
    count = n.astype(jnp.int32)
    root = jnp.where(count <= 1, 1, 0).astype(jnp.uint8) * nodes[0]
    found = count <= 1
    while nodes.shape[0] > 1:
        m = nodes.shape[0]
        pad = (-m) % WIDTH
        if pad:
            nodes = jnp.concatenate(
                [nodes, jnp.zeros((pad, DIGEST), jnp.uint8)], axis=0
            )
            m += pad
        parents = _hash_nodes(nodes.reshape(m // WIDTH, WIDTH * DIGEST), alg)
        count = (count + (WIDTH - 1)) // WIDTH
        live = jnp.arange(parents.shape[0], dtype=jnp.int32) < count
        nodes = jnp.where(live[:, None], parents, jnp.zeros_like(parents))
        is_root_level = (~found) & (count <= 1)
        root = jnp.where(is_root_level, nodes[0], root)
        found = found | is_root_level
    return root


# The whole-tree Pallas kernel (ops.pallas_merkle) is NOT on this dispatch:
# Mosaic (jax 0.9.0 / libtpu 0.0.34, TPU v5e) refuses it at lowering — its
# in-kernel byte packing (stride-4 lane slices of uint8 rows) becomes a
# gather the TPU lowering rejects ("Shape mismatch in input, indices and
# output"), and behind that wait uint8 [n,32] -> [m,512] reshapes, [k,17]
# transposes, scatter `.at[].set` and an un-gridded whole-array VMEM
# operand. That is a rewrite to word planes, not a layout fix (ROADMAP
# queue 1 item 5). The XLA level loop below compiles and matches the host
# oracle on the chip (PERF.md "Chip bring-up").


def merkle_root(leaves, alg: str = "keccak256",
                nbucket: int | None = None) -> jax.Array:
    """Merkle root of [n, 32] uint8 leaf digests.

    The leaves are zero-padded ON THE HOST to `nbucket` (default: the next
    power of two, >= WIDTH) so the device sees one shape per bucket — the
    root for logical n is bit-identical regardless of bucket size."""
    leaves = np.asarray(leaves, dtype=np.uint8)
    n = leaves.shape[0]
    if n == 0:
        return jnp.zeros((DIGEST,), jnp.uint8)
    nbucket = nbucket or max(WIDTH, 1 << (n - 1).bit_length())
    assert nbucket >= n, (nbucket, n)
    if nbucket > n:
        leaves = np.concatenate(
            [leaves, np.zeros((nbucket - n, DIGEST), np.uint8)], axis=0)
    return merkle_root_padded(leaves, np.int32(n), alg)


def merkle_root_padded(leaves: np.ndarray, n, alg: str) -> jax.Array:
    """`merkle_root` for a caller that padded the leaves itself: [bucket,
    32] uint8 rows of which the first `n` are the tree's."""
    return _merkle_root_bucketed(leaves, n, alg)


# ---------------------------------------------------------------------------
# host-side reference + proofs (low-volume path: Ledger.cpp:759-844 proofs)
# ---------------------------------------------------------------------------

_HOST_HASH: dict = {}


def _hash_host(data: bytes, alg: str) -> bytes:
    fn = _HOST_HASH.get(alg)
    if fn is None:
        from ..crypto import nativehash

        fn = _HOST_HASH[alg] = nativehash.host_hash(alg)
    return fn(data)


_HOST_HASH_BATCH: dict = {}


def _hash_host_batch(msgs: list[bytes], alg: str) -> list[bytes]:
    fn = _HOST_HASH_BATCH.get(alg)
    if fn is None:
        from ..crypto import nativehash

        fn = _HOST_HASH_BATCH[alg] = nativehash.host_hash_batch(alg)
    return fn(msgs)


def merkle_levels_host(leaves: list[bytes], alg: str = "keccak256") -> list[list[bytes]]:
    """All tree levels, canonical semantics (host loop, one native hash
    call per level)."""
    assert leaves
    levels = [list(leaves)]
    while len(levels[-1]) > 1:
        cur = list(levels[-1])
        while len(cur) % WIDTH:
            cur.append(b"\x00" * DIGEST)
        joined = [b"".join(cur[i: i + WIDTH])
                  for i in range(0, len(cur), WIDTH)]
        levels.append(_hash_host_batch(joined, alg))
    return levels


def sibling_group(level: list[bytes], group: int) -> list[bytes]:
    """The WIDTH siblings of `level`'s `group`-th parent, zero-padded: one
    row of a proof, the same for every leaf under that parent."""
    sibs = list(level[group * WIDTH: (group + 1) * WIDTH])
    if len(sibs) < WIDTH:
        sibs.extend([b"\x00" * DIGEST] * (WIDTH - len(sibs)))
    return sibs


def proof_from_levels(levels: list[list[bytes]], index: int):
    """Inclusion proof for leaf `index` out of prebuilt levels — the
    shared walk for `merkle_proof` and the light node's span server,
    which build the levels ONCE per block instead of once per
    transaction. Slices its group out of each level; never copies one."""
    proof = []
    idx = index
    for level in levels[:-1]:
        group = idx // WIDTH
        proof.append((sibling_group(level, group), idx % WIDTH))
        idx = group
    return proof


def merkle_proof(leaves: list[bytes], index: int, alg: str = "keccak256"):
    """Inclusion proof: list of (siblings_bytes, position) per level."""
    return proof_from_levels(merkle_levels_host(leaves, alg), index)


def verify_merkle_proof(leaf: bytes, proof, root: bytes, alg: str = "keccak256") -> bool:
    cur = leaf
    for sibs, pos in proof:
        if sibs[pos] != cur:
            return False
        cur = _hash_host(b"".join(sibs), alg)
    return cur == root
