"""What a kernel's call has to do at the least: the textbook algorithm's
work per REAL (unpadded) item, whatever implements it, and the bytes that
have to cross HBM. A roofline share is the larger of ops / peak ops and
bytes / peak bandwidth over the kernel's device time; padding a batch of
1,000 to a bucket of 4096 does no counted work and so reads as a lower
share, as it should.

Units: one int8 multiply-add is 2 ops, as the published peak counts it.

Elliptic curve, 256-bit prime field, Jacobian coordinates (Cohen, Miyaji,
Ono 1998): a doubling is 4M + 6S, a mixed addition 8M + 3S. u1*G + u2*Q by
double-and-add with Shamir's trick is 256 doublings and an addition where
either scalar has a bit set, 3/4 of the positions. One exponentiation by
square-and-multiply (a Fermat inversion, or the square root that recover
needs to lift r to a point) is 255 squarings and 128 multiplies. A field
multiply of two 256-bit numbers as 32 x 32 byte limbs is 1024 int8
multiply-adds; the reduction is not counted.

Hashing: Keccak-f[1600] is 24 rounds of 155 64-bit operations (theta 55,
rho and pi 24, chi 75, iota 1), 8 int8 ops each; SM3's compression is 64
rounds of 40 32-bit operations plus 52 expanded words of 10, 4 int8 ops
each. A width-16 Merkle node hashes 16 x 32 bytes: 4 Keccak blocks of 136
bytes or 9 SM3 blocks of 64, padding included; a tree over n leaves has
n/16 + n/256 + ... nodes.
"""

from __future__ import annotations

FIELD_MUL_OPS = 2 * 32 * 32
DOUBLE_MULS, MIXED_ADD_MULS = 4 + 6, 8 + 3
SHAMIR_MULS = 256 * DOUBLE_MULS + 192 * MIXED_ADD_MULS
EXPONENTIATION_MULS = 255 + 128
KECCAK_F_OPS = 24 * 155 * 8
SM3_COMPRESS_OPS = (64 * 40 + 52 * 10) * 4
MERKLE_WIDTH, DIGEST = 16, 32


def ecdsa_recover(items: int) -> tuple[float, float]:
    """-> (ops, bytes) of recovering `items` secp256k1 public keys: the
    square root, the double scalar multiplication, one inversion to affine.
    In: 32-byte digest + 65-byte signature; out: 64-byte key + a flag."""
    muls = SHAMIR_MULS + 2 * EXPONENTIATION_MULS
    return items * muls * FIELD_MUL_OPS, items * (32 + 65 + 64 + 1)


def sm2_verify(items: int) -> tuple[float, float]:
    """-> (ops, bytes) of verifying `items` SM2 signatures: s*G + t*P and
    one inversion to affine. In: digest, (r, s), the key; out: a flag."""
    muls = SHAMIR_MULS + EXPONENTIATION_MULS
    return items * muls * FIELD_MUL_OPS, items * (32 + 64 + 64 + 1)


def merkle_nodes(leaves: int) -> int:
    nodes, level = 0, leaves
    while level > 1:
        level = -(-level // MERKLE_WIDTH)
        nodes += level
    return nodes


def merkle_root(leaves: int, trees: int, hash_name: str) -> tuple[float, float]:
    """-> (ops, bytes) of `trees` width-16 roots over `leaves` 32-byte
    leaves in all: every node hashed once; the leaves read and a root
    written (inner levels can stay on the chip)."""
    if hash_name not in ("keccak256", "sm3"):
        raise ValueError(f"hash {hash_name!r}")
    per_node = (4 * KECCAK_F_OPS if hash_name == "keccak256"
                else 9 * SM3_COMPRESS_OPS)
    # trees of equal size: the count of nodes is taken for the mean tree
    nodes = trees * merkle_nodes(-(-leaves // max(trees, 1)))
    return nodes * per_node, leaves * DIGEST + trees * DIGEST


COUNTERS = {"ecdsa_recover": ecdsa_recover, "sm2_verify": sm2_verify}


def roofline_share(ops: float, nbytes: float, device_seconds: float,
                   peaks: dict) -> tuple[float, str]:
    """-> (share of the roofline in %, which bound won)."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "ops" if t_ops >= t_bytes else "bytes"
    return 100.0 * max(t_ops, t_bytes) / device_seconds, bound
