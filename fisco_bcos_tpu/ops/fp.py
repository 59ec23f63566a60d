"""Lane-major 256-bit field arithmetic on TPU — the fast crypto substrate.

This supersedes `bigint.Mod`'s CIOS loop for the elliptic-curve kernels.
Two TPU-specific design decisions drive it (see /opt/skills/guides/
pallas_guide.md: the VPU is (8, 128) lanes and the minor-most axis maps to
the 128-wide lane dimension):

1. **Lane-major layout.** Values are ``uint32[..., NLIMBS, B]`` — the batch
   axis is minor-most, so every limb operation is a full-width vector op over
   128 lanes. The previous ``[B, NLIMBS]`` layout put the *16-limb* axis in
   the lane dimension, capping utilization at 16/128 = 12.5%.

2. **Unrolled outer-product multiply, no fori_loop.** A 256x256-bit product
   is 16 broadcast multiplies (one per limb of `a`, each against all 16 limbs
   of `b`), accumulated into 32 redundant columns (each < 2^21, safe in
   uint32), then one sequential carry sweep. There is no inner XLA while
   loop and no per-iteration stack/unstack churn; the whole multiply is
   a few hundred straight-line vector ops that XLA fuses freely.

Reduction strategies per modulus:

* ``SolinasField`` — for p = 2^256 - c with tiny c (secp256k1:
  c = 2^32 + 977). The high 256 bits fold back as H*c, twice; 3 carry
  sweeps total. Values stay in the plain (non-Montgomery) domain.
* ``MontField`` — any odd 256-bit modulus (SM2's p, both curve orders n).
  Full-product Montgomery reduction with R = 2^256: m = (Z mod R) * n'
  (half product), t = (Z + m*n)/R. Values live in the Montgomery domain
  between `to_rep`/`from_rep`.

Both maintain a **canonical invariant**: every value a method returns is
fully carried (16-bit limbs) and < modulus, so equality is plain limb
comparison.

Reference counterpart: the WeDPR/OpenSSL bignum paths behind
/root/reference/bcos-crypto/bcos-crypto/signature/secp256k1/
Secp256k1Crypto.cpp:40,57,85 — rebuilt batch-first for the TPU VPU rather
than wrapped scalar calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import platform

NLIMBS = 16
LIMB_BITS = 16
LIMB_RADIX = 1 << LIMB_BITS
MASK = np.uint32(LIMB_RADIX - 1)
BITS = NLIMBS * LIMB_BITS  # 256


def _use_pallas() -> bool:
    """Pallas-fused kernels: compiled by Mosaic on TPU, off everywhere else
    (the interpreter is slower than plain XLA on CPU). The platform
    decides — asked at trace time, so it is frozen into each jit."""
    return platform.on_tpu()


__all__ = ["NLIMBS", "LIMB_BITS", "BITS", "SolinasField", "MontField",
           "to_limbs", "from_limbs_np", "window_digits", "is_zero", "eq",
           "select", "add_limbs", "sub_limbs"]


# ---------------------------------------------------------------------------
# host conversions (lane-major: limbs on axis -2)
# ---------------------------------------------------------------------------

def to_limbs(x: int, nlimbs: int = NLIMBS) -> np.ndarray:
    """Python int -> little-endian uint32[nlimbs] (16 bits per limb)."""
    if x < 0 or x >= 1 << (nlimbs * LIMB_BITS):
        raise ValueError(f"out of range for {nlimbs} limbs: {x}")
    return np.array(
        [(x >> (LIMB_BITS * i)) & (LIMB_RADIX - 1) for i in range(nlimbs)],
        dtype=np.uint32,
    )


def from_limbs_np(a) -> int:
    """uint32[NLIMBS] -> Python int."""
    a = np.asarray(a, dtype=np.uint64)
    return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(a.tolist()))


def _col(c: np.ndarray) -> jnp.ndarray:
    """Constant limb vector [L] -> broadcastable [L, 1] device constant."""
    return jnp.asarray(c)[:, None]


def _pad(x, lo, hi):
    """Zero-pad along the limb axis (-2)."""
    if lo == 0 and hi == 0:
        return x
    spec = [(0, 0)] * (x.ndim - 2) + [(lo, hi), (0, 0)]
    return jnp.pad(x, spec)


# ---------------------------------------------------------------------------
# raw multi-limb primitives (all shapes [..., L, B], batch minor-most)
# ---------------------------------------------------------------------------

def _diag_sum(p, width: int):
    """Anti-diagonal reduction: p[..., R, J, B] -> cols[..., width, B] with
    cols[k] = sum_i p[i, k - i] (out-of-range j treated as zero).

    Implemented branch-free via the pad-and-reshape shear: padding each row
    to width+1 and re-viewing the flat buffer at stride `width` shifts row i
    right by i, so one axis reduction produces every column. ~5 HLO ops
    total — this replaces an unrolled per-limb pad/add chain, which is what
    made XLA compiles of the EC kernels pathological.
    """
    R, J = p.shape[-3], p.shape[-2]
    assert J <= width + 1 and R <= width
    spec = [(0, 0)] * (p.ndim - 2) + [(0, width + 1 - J), (0, 0)]
    flat = jnp.pad(p, spec).reshape(p.shape[:-3] + (R * (width + 1), p.shape[-1]))
    sheared = flat[..., : R * width, :].reshape(
        p.shape[:-3] + (R, width, p.shape[-1]))
    return jnp.sum(sheared, axis=-3)


def mul_wide(a, b):
    """Full 512-bit product as 32 redundant columns, each < 2^21.

    a, b: uint32[..., 16, B] with exact 16-bit limbs. One [16, 16, B] outer
    product, split 16/16 per partial product, reduced along anti-diagonals.
    """
    p = a[..., :, None, :] * b[..., None, :, :]  # [..., 16, 16, B] < 2^32
    lo = _diag_sum(p & MASK, 2 * NLIMBS)
    hi = _diag_sum(_pad(p >> LIMB_BITS, 1, 0), 2 * NLIMBS)  # offset +1 col
    return lo + hi


def mul_low(a, b):
    """Low 16 columns of the product (mod 2^256), redundant (< 2^21)."""
    return mul_wide(a, b)[..., :NLIMBS, :]


def _shift_up(x, k: int):
    """Along the limb axis (-2): out[i] = x[i - k], zero-fill below."""
    return _pad(x, k, 0)[..., : x.shape[-2], :]


def carry_prop(cols, nout: int):
    """Redundant columns -> exact 16-bit limbs, in log depth.

    cols: uint32[..., ncols, B] with ncols <= nout, every column < 2^31.
    Returns (limbs [..., nout, B], carry_out [..., B]) where carry_out is
    the value overflowing limb nout-1 (fits uint32).

    Two vectorized collapse passes bring every column to <= 2^16, then a
    Kogge-Stone carry-lookahead (prefix over the generate/propagate
    semigroup) resolves the remaining single-bit ripple exactly in
    ceil(log2(m)) steps — no 32-long sequential dependency chain and no
    stack-of-slices, which together dominated both compile time and the
    critical path of the previous per-limb sweep.
    """
    ncols = cols.shape[-2]
    assert ncols <= nout, (ncols, nout)
    m = nout + 2  # headroom: total value < 2^(16*nout + 16) for ncols<=nout
    cols = _pad(cols, 0, m - ncols)
    # collapse: < 2^31 -> < 2^17 -> <= 2^16
    w = (cols & MASK) + _shift_up(cols >> LIMB_BITS, 1)
    w = (w & MASK) + _shift_up(w >> LIMB_BITS, 1)
    # carry-lookahead over values <= 2^16
    r = w & MASK
    G = w >> LIMB_BITS  # generate, in {0, 1}
    P = (r == MASK).astype(jnp.uint32)  # propagate
    k = 1
    while k < m:
        G = G | (P & _shift_up(G, k))
        P = P & _shift_up(P, k)
        k *= 2
    cin = _shift_up(G, 1)
    limbs = (r + cin) & MASK
    carry = limbs[..., nout, :] | (limbs[..., nout + 1, :] << LIMB_BITS)
    return limbs[..., :nout, :], carry


def add_limbs(a, b):
    """Exact-limb add -> (limbs mod 2^256, carry bit)."""
    return carry_prop(a + b, NLIMBS)


def sub_limbs(a, b):
    """Exact-limb subtract -> (limbs mod 2^256, borrow bit in {0,1})."""
    # a - b == a + ~b + 1 over 16-bit limbs; per-column value < 2^17 + 1.
    cols = a + ((~b) & MASK)
    bump = jnp.concatenate(
        [jnp.ones_like(cols[..., :1, :]), jnp.zeros_like(cols[..., 1:, :])],
        axis=-2)
    limbs, carry = carry_prop(cols + bump, NLIMBS)
    return limbs, np.uint32(1) - carry


def is_zero(a):
    return jnp.all(a == 0, axis=-2)


def eq(a, b):
    return jnp.all(a == b, axis=-2)


def select(cond, a, b):
    """cond ? a : b with cond shaped [..., B] (broadcast over limbs)."""
    return jnp.where(cond[..., None, :], a, b)


def geq(a, b):
    """a >= b over exact limb vectors."""
    _, brw = sub_limbs(a, b)
    return brw == 0


def msb_digits(e: int, window: int = 4) -> np.ndarray:
    """Static exponent -> MSB-first window digits (shared by the XLA
    pow_const scan and the fused pallas kernel so the encodings cannot
    diverge)."""
    nd = max(1, (e.bit_length() + window - 1) // window)
    return np.array(
        [(e >> (window * i)) & ((1 << window) - 1) for i in range(nd)][::-1],
        dtype=np.int32)


def window_digits(a, w: int):
    """[..., 16, B] -> [..., 256//w, B] little-endian w-bit digits."""
    assert LIMB_BITS % w == 0
    per = LIMB_BITS // w
    m = np.uint32((1 << w) - 1)
    digs = []
    for i in range(NLIMBS):
        limb = a[..., i, :]
        for j in range(per):
            digs.append((limb >> np.uint32(w * j)) & m)
    return jnp.stack(digs, axis=-2)


# ---------------------------------------------------------------------------
# field classes
# ---------------------------------------------------------------------------

class _FieldBase:
    """Shared modulus plumbing. Subclasses define the mul domain.

    `mul` dispatches to the pallas-fused kernel (ops.pallas_fp) for
    lane-major shapes on TPU — one HBM round-trip per multiply instead of
    the XLA outer-product path's reshape-relayout storm; the XLA `mul_xla`
    body remains the fallback (CPU tests, odd shapes, pallas disabled).
    """

    def __init__(self, n: int, name: str):
        self.name = name
        self.n_int = n
        self.limbs = to_limbs(n)
        # The curve fields are all > 2^255 (one conditional subtract fully
        # canonicalizes any value < 2^256); SNARK scalar fields sit lower
        # (BN254 r ~ 2^253.8). Everything in the shared ring-op layer is
        # correct for any n > 2^253: canonical inputs keep a+b < 2n < 2^255
        # (no carry), and Montgomery REDC's output stays < 2n — hence
        # canonical after one conditional subtract — whenever AT LEAST ONE
        # operand is canonical (< n): t <= (a*b + R*n)/R < 2n for a < R,
        # b < n. Two loose operands can exceed that bound, so loose values
        # may only ever meet canonical ones (to_rep pairs reduce_loose(a)
        # with r2 < n). Only `reduce_loose` itself weakens (see its
        # docstring) — its other callers are 2n > 2^256 fields (ops/ec.py).
        assert n > 1 << (BITS - 3), "modulus must exceed 2^253"

    def mul(self, a, b):
        if _use_pallas():
            from . import pallas_fp

            a, b = jnp.asarray(a), jnp.asarray(b)
            # single-column constant operand (to_rep/from_rep): dedicated
            # kernel — broadcasting it to [16, B] first would materialize
            # an HBM-sized input per multiply
            if (a.ndim == 2 and b.ndim == 2 and b.shape == (NLIMBS, 1)
                    and pallas_fp.pallas_ok(a.shape)):
                return pallas_fp.mul_const(self, a, b)
            if (b.ndim == 2 and a.ndim == 2 and a.shape == (NLIMBS, 1)
                    and pallas_fp.pallas_ok(b.shape)):
                return pallas_fp.mul_const(self, b, a)
            if a.shape != b.shape:
                shape = jnp.broadcast_shapes(a.shape, b.shape)
                a = jnp.broadcast_to(a, shape)
                b = jnp.broadcast_to(b, shape)
            if pallas_fp.pallas_ok(a.shape[-2:]):
                if a.ndim == 2:
                    return pallas_fp.mul(self, a, b)
                # stacked [..., 16, B]: collapse the leading (major) axes —
                # layout-safe, the lane-minor batch axis is untouched
                lead = a.shape[:-2]
                k = int(np.prod(lead))
                out = pallas_fp.mul_stacked(
                    self, a.reshape((k,) + a.shape[-2:]),
                    b.reshape((k,) + b.shape[-2:]))
                return out.reshape(lead + a.shape[-2:])
        return self.mul_xla(a, b)

    # hashable-by-value so fields can be jit static args
    def __hash__(self):
        return hash((type(self).__name__, self.n_int))

    def __eq__(self, other):
        return type(other) is type(self) and other.n_int == self.n_int

    def __repr__(self):
        return f"{type(self).__name__}({self.name})"

    # -- ring ops on canonical values (domain-agnostic) --------------------
    def add(self, a, b):
        s, c = add_limbs(a, b)
        d, brw = sub_limbs(s, _col(self.limbs))
        return select((c == 1) | (brw == 0), d, s)

    def sub(self, a, b):
        d, brw = sub_limbs(a, b)
        d2, _ = add_limbs(d, _col(self.limbs))
        return select(brw == 1, d2, d)

    def neg(self, a):
        d, _ = sub_limbs(_col(self.limbs) + jnp.zeros_like(a), a)
        return select(is_zero(a), a, d)

    def reduce_loose(self, a):
        """One conditional subtract: any exact-limb value < 2^256 becomes
        canonical (< n) when 2n > 2^256 (every curve field); for smaller
        moduli (BN254 r) the result is merely < 2^256 - n — callers there
        must tolerate a loose value (MontField.to_rep's REDC does)."""
        d, brw = sub_limbs(a, _col(self.limbs))
        return select(brw == 0, d, a)

    def sqr(self, a):
        return self.mul(a, a)

    def half(self, a):
        """a/2 mod n (n odd), canonical in, canonical out."""
        n = jnp.broadcast_to(_col(self.limbs), a.shape)
        odd = (a[..., 0, :] & 1) == 1
        s, c = add_limbs(a, select(odd, n, jnp.zeros_like(a)))
        lo = s >> np.uint32(1)
        hi = jnp.concatenate([s[..., 1:, :], c[..., None, :]], axis=-2)
        return (lo | (hi << np.uint32(LIMB_BITS - 1))) & MASK

    # -- fixed-exponent power (exponent static) ----------------------------
    def pow_const(self, a, e: int, window: int = 4):
        """a^e in the internal domain; e is a compile-time int."""
        if e == 0:
            return self.one_rep(a.shape)
        if _use_pallas():
            from . import pallas_fp

            a = jnp.asarray(a)
            if pallas_fp.pallas_ok(a.shape):
                # the XLA form is ~5 multiplies x 64 scan steps of per-op
                # dispatch; the fused kernel is ONE pallas call
                return pallas_fp.pow_const(self, a, e)
        digits = msb_digits(e, window)

        def tbl_step(prev, _):
            nxt = self.mul(prev, a)
            return nxt, nxt

        _, rest = jax.lax.scan(tbl_step, a, None, length=(1 << window) - 2)
        table = jnp.concatenate(
            [self.one_rep(a.shape)[None], a[None], rest], axis=0)

        def body(acc, dig):
            for _ in range(window):
                acc = self.sqr(acc)
            factor = jax.lax.dynamic_index_in_dim(
                table, dig, axis=0, keepdims=False)
            acc = self.mul(acc, factor)
            return acc, None

        init = jax.lax.dynamic_index_in_dim(
            table, int(digits[0]), axis=0, keepdims=False)
        acc, _ = jax.lax.scan(body, init, jnp.asarray(digits[1:]))
        return acc

    def inv(self, a):
        """a^(n-2) in the internal domain (n prime)."""
        return self.pow_const(a, self.n_int - 2)

    def inv_batch(self, a):
        """Batched inversion via a product tree over the lane axis
        (Montgomery's trick, tree-shaped for SIMD): ~2*log2(B) wide
        multiplies + ONE Fermat inversion on a single lane, versus a
        ~300-multiply exponentiation across the whole batch. Zero lanes
        (invalid/padded entries — every caller masks them) pass through as
        zero without poisoning the tree. Requires B a power of two (all
        batch buckets are); falls back to `inv` otherwise."""
        B = a.shape[-1]
        if B & (B - 1) or a.ndim != 2:
            return self.inv(a)
        zero = is_zero(a)
        safe = select(zero, self.one_rep(a.shape), a)
        levels = []
        cur = safe
        while cur.shape[-1] > 1:
            w = cur.shape[-1] // 2
            # contiguous halves (not an even/odd stride): when B is sharded
            # over the device mesh, every level below the per-shard width
            # stays shard-local; a stride-2 split would reshard at EVERY
            # level of both passes
            left, right = cur[..., :w], cur[..., w:]
            levels.append((left, right))
            cur = self.mul(left, right)
        invp = self.inv(cur)  # [L, 1]
        for left, right in reversed(levels):
            # one stacked multiply per level (the _mulk pattern): halves the
            # HLO mul instantiations on the unwind
            both = self.mul(jnp.broadcast_to(invp, (2,) + invp.shape),
                            jnp.stack([right, left]))
            invp = jnp.concatenate([both[0], both[1]], axis=-1)
        return select(zero, jnp.zeros_like(a), invp)


class SolinasField(_FieldBase):
    """p = 2^256 - c for tiny c (secp256k1: c = 2^32 + 977). Plain domain.

    Folding uses the limb decomposition c = sum coef_j * 2^(16*shift_j) and
    requires every coef < 2^11 so coef * (redundant column < 2^21) fits
    uint32.
    """

    def __init__(self, p: int, name: str = "solinas"):
        super().__init__(p, name)
        c = (1 << BITS) - p
        assert 0 < c < 1 << (3 * LIMB_BITS)
        self.c_int = c
        self.terms: list[tuple[int, int]] = []
        for sh in range((c.bit_length() + LIMB_BITS - 1) // LIMB_BITS):
            coef = (c >> (LIMB_BITS * sh)) & (LIMB_RADIX - 1)
            if coef:
                assert coef < (1 << 11), "fold coefficient too large"
                self.terms.append((coef, sh))

    def _fold_into(self, low_cols, top, ntop: int):
        """low_cols (16 redundant) += top * c (top: ntop exact limbs)."""
        out = low_cols
        for coef, sh in self.terms:
            contrib = top * np.uint32(coef)  # [..., ntop, B] < 2^27
            out = out + _pad(contrib, sh, NLIMBS - ntop - sh)
        return out

    def mul_xla(self, a, b):
        cols = mul_wide(a, b)  # 32 redundant cols < 2^21
        low, high = cols[..., :NLIMBS, :], cols[..., NLIMBS:, :]
        # fold 1: value = L + H*c; coef*H[k] < 2^11 * 2^21 = 2^32.
        t = _pad(low, 0, 2)
        for coef, sh in self.terms:
            t = t + _pad(high * np.uint32(coef), sh, 2 - sh)
        t_limbs, topc = carry_prop(t, NLIMBS + 2)
        # fold 2: top := limbs 16,17 + sweep carry (3 exact limbs, < 2^36)
        top = jnp.concatenate(
            [t_limbs[..., NLIMBS:, :], topc[..., None, :]], axis=-2)
        r_cols = self._fold_into(t_limbs[..., :NLIMBS, :], top, 3)
        r_limbs, o = carry_prop(r_cols, NLIMBS)
        # fold 3: o in {0,1}; adding o*c cannot carry out of 2^256 again
        r2_cols = self._fold_into(r_limbs, o[..., None, :], 1)
        r2_limbs, _ = carry_prop(r2_cols, NLIMBS)
        return self.reduce_loose(r2_limbs)

    def one_rep(self, shape):
        one = np.zeros((NLIMBS,), np.uint32)
        one[0] = 1
        return jnp.broadcast_to(_col(one), shape[:-2] + (NLIMBS, shape[-1]))

    # plain domain: encode/decode are (almost) identity
    def encode_int(self, v: int) -> np.ndarray:
        return to_limbs(v % self.n_int)

    def to_rep(self, a):
        return self.reduce_loose(a)

    def from_rep(self, a):
        return a


class MontField(_FieldBase):
    """Generic odd 256-bit modulus; Montgomery domain with R = 2^256."""

    def __init__(self, n: int, name: str = "mont"):
        super().__init__(n, name)
        assert n % 2 == 1
        self.r_int = (1 << BITS) % n
        self.r2 = to_limbs(pow(self.r_int, 2, n))
        self.nprime = to_limbs((-pow(n, -1, 1 << BITS)) % (1 << BITS))
        self.one_m = to_limbs(self.r_int)

    def mul_xla(self, a, b):
        """REDC(a*b) for canonical Montgomery-domain inputs (< n)."""
        n = _col(self.limbs)
        z_cols = mul_wide(a, b)
        z, _ = carry_prop(z_cols, 2 * NLIMBS)  # exact; product < 2^512
        m_cols = mul_low(z[..., :NLIMBS, :], _col(self.nprime))
        m, _ = carry_prop(m_cols, NLIMBS)
        s_cols = mul_wide(m, n) + z  # redundant < 2^21 + 2^16
        s, o = carry_prop(s_cols, 2 * NLIMBS)  # low 16 limbs are zero
        hi = s[..., NLIMBS:, :]
        d, brw = sub_limbs(hi, n)
        return select((o == 1) | (brw == 0), d, hi)

    def one_rep(self, shape):
        return jnp.broadcast_to(_col(self.one_m),
                                shape[:-2] + (NLIMBS, shape[-1]))

    def encode_int(self, v: int) -> np.ndarray:
        return to_limbs(v % self.n_int * self.r_int % self.n_int)

    def to_rep(self, a):
        """Exact-limb value < 2^256 -> Montgomery domain (canonical)."""
        return self.mul(self.reduce_loose(a), _col(self.r2))

    def from_rep(self, a):
        """Montgomery domain -> plain canonical integer limbs."""
        one = np.zeros((NLIMBS,), np.uint32)
        one[0] = 1
        return self.mul(a, _col(one))
