"""256-bit unsigned integer arithmetic on TPU vector lanes.

This module replaces the reference's native big-int crypto dependencies (the
WeDPR Rust FFI used by /root/reference/bcos-crypto/bcos-crypto/signature/
secp256k1/Secp256k1Crypto.cpp:40,57,85 and the OpenSSL bignum paths) with limb
arithmetic that vectorises over a *batch* axis on the TPU VPU: every operation
below maps elementwise over leading axes, so `jax.vmap`/`shard_map` turn one
scalar algorithm into a 64k-signature batch kernel.

Representation
--------------
A 256-bit unsigned integer is a little-endian vector of ``NLIMBS = 16`` limbs,
``LIMB_BITS = 16`` bits per limb, each stored in a ``uint32`` lane (upper 16
bits zero in canonical form).  16-bit limbs are the TPU-native choice: a limb
product fits a uint32 exactly (no uint64 on TPU), carry chains are short, and
every op is a plain int32/uint32 VPU instruction.

Montgomery arithmetic
---------------------
`Mod` bundles a modulus with its Montgomery constants (R = 2^256).  `mont_mul`
is a CIOS (coarsely integrated operand scanning) multiply-reduce: the outer
limb loop is a `lax.fori_loop` (keeps traced graph small — it is inlined
thousands of times into EC scalar-mult scan bodies), the inner carry chains
are unrolled; all lanes stay below 2^18 so uint32 never overflows.

No constant-time discipline is attempted: these kernels only ever *verify*
public data (signatures, hashes), mirroring the reference's use of
non-secret-dependent batch verification in TransactionSync.cpp:516-537.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

NLIMBS = 16
LIMB_BITS = 16
LIMB_RADIX = 1 << LIMB_BITS
MASK32 = np.uint32(LIMB_RADIX - 1)
BITS = NLIMBS * LIMB_BITS  # 256

__all__ = [
    "NLIMBS",
    "LIMB_BITS",
    "BITS",
    "to_limbs",
    "from_limbs",
    "add",
    "sub",
    "geq",
    "is_zero",
    "eq",
    "select",
    "Mod",
]


# ---------------------------------------------------------------------------
# host-side conversions (numpy / Python int)
# ---------------------------------------------------------------------------

def to_limbs(x: int, nlimbs: int = NLIMBS) -> np.ndarray:
    """Python int -> little-endian uint32 limb vector (16 bits per limb)."""
    if x < 0 or x >= 1 << (nlimbs * LIMB_BITS):
        raise ValueError(f"out of range for {nlimbs} limbs: {x}")
    return np.array(
        [(x >> (LIMB_BITS * i)) & (LIMB_RADIX - 1) for i in range(nlimbs)],
        dtype=np.uint32,
    )


def from_limbs(a) -> int:
    """Limb vector (numpy or jax, 1-D) -> Python int."""
    a = np.asarray(a, dtype=np.uint64)
    return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(a.tolist()))


def batch_to_limbs(xs) -> np.ndarray:
    """List of Python ints -> [N, NLIMBS] uint32."""
    return np.stack([to_limbs(int(x)) for x in xs], axis=0)


def rows_to_limbs(rows: np.ndarray) -> np.ndarray:
    """uint8[N, 32] big-endian rows -> [N, NLIMBS] uint32, `to_limbs`'
    layout, in one pass over the array (no integer per row). `rows` may be
    a column slice of a wider frame."""
    return rows.view(">u2")[:, ::-1].astype(np.uint32, order="C")


def limbs_to_rows(a: np.ndarray) -> np.ndarray:
    """[N, NLIMBS] canonical limbs, in any memory layout (a device output
    may come back column-major) -> C-contiguous uint8[N, 32] big-endian
    rows: the inverse of `rows_to_limbs`."""
    return np.asarray(a)[:, ::-1].astype(">u2", order="C").view(np.uint8)


# ---------------------------------------------------------------------------
# raw 256-bit ops (vectorised over leading axes)
# ---------------------------------------------------------------------------

def add(a: jax.Array, b: jax.Array):
    """(a + b) mod 2^256 -> (limbs, carry_out in {0,1})."""
    out = []
    c = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]), jnp.uint32)
    for i in range(NLIMBS):
        s = a[..., i] + b[..., i] + c
        out.append(s & MASK32)
        c = s >> LIMB_BITS
    return jnp.stack(out, axis=-1), c


def sub(a: jax.Array, b: jax.Array):
    """(a - b) mod 2^256 -> (limbs, borrow_out in {0,1})."""
    out = []
    brw = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]), jnp.uint32)
    for i in range(NLIMBS):
        # t in [1, 2^17): LIMB_RADIX + a_i - b_i - brw
        t = np.uint32(LIMB_RADIX) + a[..., i] - b[..., i] - brw
        out.append(t & MASK32)
        brw = np.uint32(1) - (t >> LIMB_BITS)
    return jnp.stack(out, axis=-1), brw


def geq(a: jax.Array, b: jax.Array) -> jax.Array:
    """a >= b (bool over leading axes)."""
    _, brw = sub(a, b)
    return brw == 0


def is_zero(a: jax.Array) -> jax.Array:
    return jnp.all(a == 0, axis=-1)


def eq(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.all(a == b, axis=-1)


def select(cond: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """cond ? a : b, broadcasting cond over the limb axis."""
    return jnp.where(cond[..., None], a, b)


def shift_right_bits(a: jax.Array, k: int) -> jax.Array:
    """a >> k for 0 <= k < 16 (static small shift, used by digit extraction)."""
    if k == 0:
        return a
    lo = a >> np.uint32(k)
    hi = jnp.concatenate(
        [a[..., 1:], jnp.zeros_like(a[..., :1])], axis=-1
    ) << np.uint32(LIMB_BITS - k)
    return (lo | hi) & MASK32


def window_digits(a: jax.Array, w: int) -> jax.Array:
    """Split 256-bit a into 256/w w-bit digits, little-endian: [..., 256//w].

    w must divide LIMB_BITS. Used for windowed scalar multiplication.
    """
    assert LIMB_BITS % w == 0
    per = LIMB_BITS // w
    digs = []
    m = np.uint32((1 << w) - 1)
    for i in range(NLIMBS):
        limb = a[..., i]
        for j in range(per):
            digs.append((limb >> np.uint32(w * j)) & m)
    return jnp.stack(digs, axis=-1)


# ---------------------------------------------------------------------------
# Montgomery modular arithmetic
# ---------------------------------------------------------------------------

class Mod:
    """A fixed odd modulus with device-resident Montgomery constants.

    All methods operate on canonical limb vectors (< modulus) and vectorise
    over leading axes. Values passed to `mul`/`sqr`/`pow_const`/`inv` must be
    in Montgomery form (use `to_mont`/`from_mont`).
    """

    def __init__(self, n: int, name: str = "mod"):
        if n % 2 == 0 or n < 3:
            raise ValueError("modulus must be odd > 2")
        self.name = name
        self.n_int = n
        self.limbs = to_limbs(n)
        self.n0inv = np.uint32((-pow(n, -1, LIMB_RADIX)) % LIMB_RADIX)
        self.r_int = (1 << BITS) % n
        self.r2 = to_limbs(pow(self.r_int, 2, n))
        self.one_m = to_limbs(self.r_int)  # 1 in Montgomery form
        self.zero = to_limbs(0)

    # -- pytree-friendly: treat Mod as static (hashable by identity) --------
    def __hash__(self):
        return hash((self.name, self.n_int))

    def __eq__(self, other):
        return isinstance(other, Mod) and other.n_int == self.n_int

    def __repr__(self):
        return f"Mod({self.name}, 0x{self.n_int:x})"

    # -- non-Montgomery ring ops -------------------------------------------
    def add(self, a, b):
        s, c = add(a, b)
        d, brw = sub(s, jnp.asarray(self.limbs))
        take_d = (c == 1) | (brw == 0)
        return select(take_d, d, s)

    def sub(self, a, b):
        d, brw = sub(a, b)
        d2, _ = add(d, jnp.asarray(self.limbs))
        return select(brw == 1, d2, d)

    def neg(self, a):
        d, _ = sub(jnp.asarray(self.limbs), a)
        return select(is_zero(a), a, d)

    def reduce_once(self, a):
        """a (< 2^256) -> a mod n, assuming a < 2n (single conditional sub)."""
        d, brw = sub(a, jnp.asarray(self.limbs))
        return select(brw == 0, d, a)

    def reduce_full(self, a):
        """a (any 256-bit value) -> a mod n via Montgomery round trip."""
        return self.from_mont(self.to_mont(a))

    # -- Montgomery multiply (CIOS, 16-bit limbs) --------------------------
    def mul(self, a, b):
        """REDC(a*b): Montgomery product, canonical (< n)."""
        n = jnp.asarray(self.limbs)
        n0inv = jnp.uint32(self.n0inv)
        batch_shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
        a = jnp.broadcast_to(a, batch_shape + (NLIMBS,))
        b = jnp.broadcast_to(b, batch_shape + (NLIMBS,))
        t0 = jnp.zeros(batch_shape + (NLIMBS + 2,), jnp.uint32)

        def body(i, t):
            bi = jax.lax.dynamic_index_in_dim(b, i, axis=-1, keepdims=False)
            # --- multiplication step: t += a * bi ---
            ts = [t[..., j] for j in range(NLIMBS + 2)]
            prod = a * bi[..., None]  # [..., NLIMBS], each < 2^32
            c = jnp.zeros_like(bi)
            for j in range(NLIMBS):
                pj = prod[..., j]
                s = ts[j] + (pj & MASK32) + c
                ts[j] = s & MASK32
                c = (s >> LIMB_BITS) + (pj >> LIMB_BITS)
            s = ts[NLIMBS] + c
            ts[NLIMBS] = s & MASK32
            ts[NLIMBS + 1] = ts[NLIMBS + 1] + (s >> LIMB_BITS)
            # --- reduction step: m = t0 * n0inv mod 2^16; t = (t + m*n)/2^16
            m = (ts[0] * n0inv) & MASK32
            mp = n * m[..., None]
            s = ts[0] + (mp[..., 0] & MASK32)
            c = (s >> LIMB_BITS) + (mp[..., 0] >> LIMB_BITS)
            for j in range(1, NLIMBS):
                pj = mp[..., j]
                s = ts[j] + (pj & MASK32) + c
                ts[j - 1] = s & MASK32
                c = (s >> LIMB_BITS) + (pj >> LIMB_BITS)
            s = ts[NLIMBS] + c
            ts[NLIMBS - 1] = s & MASK32
            s2 = ts[NLIMBS + 1] + (s >> LIMB_BITS)
            ts[NLIMBS] = s2 & MASK32
            ts[NLIMBS + 1] = s2 >> LIMB_BITS
            return jnp.stack(ts, axis=-1)

        t = jax.lax.fori_loop(0, NLIMBS, body, t0, unroll=2)
        lo = t[..., :NLIMBS]
        hi = t[..., NLIMBS]
        d, brw = sub(lo, n)
        return select((hi > 0) | (brw == 0), d, lo)

    def sqr(self, a):
        return self.mul(a, a)

    def to_mont(self, a):
        return self.mul(a, jnp.asarray(self.r2))

    def from_mont(self, a):
        return self.mul(a, jnp.asarray(to_limbs(1)))

    def one_mont(self, batch_shape=()) -> jax.Array:
        return jnp.broadcast_to(jnp.asarray(self.one_m), batch_shape + (NLIMBS,))

    # -- fixed-exponent power (exponent is a static Python int) ------------
    def pow_const(self, a, e: int, window: int = 4):
        """a^e in Montgomery form; e is a compile-time constant.

        Fixed 4-bit windows, MSB-first, scanned over digits so the traced
        graph stays small. Not constant-time (verify-only kernels).
        """
        if e == 0:
            return self.one_mont(a.shape[:-1])
        nd = (e.bit_length() + window - 1) // window
        digits = np.array(
            [(e >> (window * i)) & ((1 << window) - 1) for i in range(nd)][::-1],
            dtype=np.int32,
        )

        # table[k] = a^k (Montgomery form), k in [0, 2^window); built with a
        # scan so the multiply body is compiled once, not 2^w times
        def tbl_step(prev, _):
            nxt = self.mul(prev, a)
            return nxt, nxt

        _, rest = jax.lax.scan(tbl_step, a, None, length=(1 << window) - 2)
        table = jnp.concatenate(
            [self.one_mont(a.shape[:-1])[None], a[None], rest], axis=0
        )  # [2^w, ..., NLIMBS]

        def body(acc, dig):
            for _ in range(window):
                acc = self.sqr(acc)
            factor = jax.lax.dynamic_index_in_dim(table, dig, axis=0, keepdims=False)
            acc = self.mul(acc, factor)
            return acc, None

        # first digit initialises the accumulator (skip leading squarings)
        init = jax.lax.dynamic_index_in_dim(table, digits[0].item(), axis=0, keepdims=False)
        acc, _ = jax.lax.scan(body, init, jnp.asarray(digits[1:]))
        return acc

    def inv(self, a):
        """a^(n-2) — inverse in Montgomery form for prime n."""
        return self.pow_const(a, self.n_int - 2)

    def half(self, a):
        """a/2 mod n (n odd): (a + (a odd ? n : 0)) >> 1."""
        n = jnp.asarray(self.limbs)
        odd = (a[..., 0] & 1) == 1
        s, c = add(a, jnp.where(odd[..., None], n, jnp.zeros_like(n)))
        # shift right 1 bit across limbs, feeding carry into the top limb
        lo = s >> np.uint32(1)
        hi = jnp.concatenate([s[..., 1:], c[..., None]], axis=-1) << np.uint32(
            LIMB_BITS - 1
        )
        return (lo | hi) & MASK32
