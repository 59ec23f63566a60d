"""Batched elliptic-curve signature kernels (secp256k1 ECDSA + SM2) on TPU.

This is the north-star component: the reference's per-transaction hot path is
`Transaction::verify` — Keccak hash + **ecrecover** + sender derivation
(/root/reference/bcos-framework/bcos-framework/protocol/Transaction.h:68-82),
dispatched to the WeDPR Rust FFI one signature at a time under a tbb loop
(/root/reference/bcos-txpool/bcos-txpool/sync/TransactionSync.cpp:516-537,
 /root/reference/bcos-crypto/bcos-crypto/signature/secp256k1/
 Secp256k1Crypto.cpp:40,57,85). Here the batch IS the kernel: the public
entry points take [B, NLIMBS] uint32 limb arrays, transpose to the
lane-major [NLIMBS, B] layout (batch in the TPU's 128-wide lane axis — see
ops.fp), and map the whole batch onto vector lanes; `jax.sharding` splits B
across the device mesh for 64k-tx blocks.

Algorithms
----------
* Field arithmetic: `fp.SolinasField` fold reduction for secp256k1's
  pseudo-Mersenne prime (plain domain); `fp.MontField` full-product REDC for
  SM2's prime and both curve orders (Montgomery domain).
* Point arithmetic: Jacobian coordinates, *complete by selection* — every
  add also computes the doubling and infinity cases and selects, so
  adversarial inputs (forced collisions) cannot produce wrong results. TPU
  control flow must be branch-free anyway; completeness is free-ish.
* Double-scalar mult u1*G + u2*Q: Shamir's trick with 4-bit windows over a
  `lax.scan` of 64 steps. The G window table is a host-precomputed affine
  constant (mixed addition); the Q table (15 multiples) is built on device
  per batch element.
* No constant-time discipline: verify/recover consume public data only
  (signing happens host-side, one sig at a time — `crypto.refimpl`).

SM2 verify consumes the precomputed digest e = SM3(Z_A || M); Z_A derivation
is host-side hashing (mirrors the reference's SM2Crypto seam, which signs the
digest produced upstream: bcos-crypto/bcos-crypto/signature/sm2/SM2Crypto.h).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import bigint, fp
from .fp import NLIMBS, eq, geq, is_zero, select
from ..crypto import refimpl

WINDOW = 4
NDIGITS = fp.BITS // WINDOW  # 64 digit positions
TBL = 1 << WINDOW  # 16 window entries (index 0 = skip)
GLV_DIGITS = 34  # 136-bit signed halves (worst observed magnitude: 129 bits)

__all__ = [
    "Curve",
    "SECP256K1",
    "SM2P256V1",
    "ecdsa_verify_batch",
    "ecdsa_recover_batch",
    "sm2_verify_batch",
]


class Curve:
    """Static curve context: field objects + curve constants + G table.

    Hashable by identity (module-level singletons) so it can be a jit static
    argument.
    """

    def __init__(self, params: refimpl.CurveParams):
        self.params = params
        if (1 << fp.BITS) - params.p < 1 << 34:
            self.fp: fp._FieldBase = fp.SolinasField(params.p, params.name + ".p")
        else:
            self.fp = fp.MontField(params.p, params.name + ".p")
        self.fn = fp.MontField(params.n, params.name + ".n")
        self.a_is_zero = params.a % params.p == 0
        self.a_is_minus3 = params.a % params.p == params.p - 3

        self.a_rep = self.fp.encode_int(params.a)
        self.b_rep = self.fp.encode_int(params.b)
        # affine window table for G: entry k = k*G in field rep, k >= 1;
        # flattened [TBL, 2*NLIMBS] for the constant-table lane select.
        tbl = np.zeros((TBL, 2 * NLIMBS), np.uint32)
        chain = []  # k*G affine ints, reused for the phi(G) table below
        P = None
        for k in range(1, TBL):
            P = refimpl.ec_add(params, P, (params.gx, params.gy))
            chain.append(P)
            tbl[k, :NLIMBS] = self.fp.encode_int(P[0])
            tbl[k, NLIMBS:] = self.fp.encode_int(P[1])
        self.g_table = tbl

        # GLV endomorphism plane (secp256k1: j-invariant 0). Guarded by an
        # explicit host check that the published (beta, lambda) pair is
        # consistent (phi(G) == lambda*G) — if not, the plain double-width
        # Shamir ladder is used.
        self.has_endo = False
        if self.a_is_zero and params.p % 3 == 1:
            lG = refimpl.ec_mul(params, refimpl.GLV_LAMBDA,
                                (params.gx, params.gy))
            if lG == (refimpl.GLV_BETA * params.gx % params.p, params.gy):
                self.has_endo = True
                self.beta_rep = self.fp.encode_int(refimpl.GLV_BETA)
                self.glv_lambda = refimpl.GLV_LAMBDA
                # phi(G) window table: phi(k*G) = (beta*x_k, y_k)
                tbl2 = np.zeros_like(tbl)
                for k, (px, py) in enumerate(chain, start=1):
                    tbl2[k, :NLIMBS] = self.fp.encode_int(
                        refimpl.GLV_BETA * px % params.p)
                    tbl2[k, NLIMBS:] = self.fp.encode_int(py)
                self.g_table_endo = tbl2
                # split constants as plain canonical limb columns
                self.g1_limbs = fp.to_limbs(refimpl._GLV_G1)
                self.g2_limbs = fp.to_limbs(refimpl._GLV_G2)
                self.mb1_int = refimpl._GLV_MINUS_B1
                self.mb2_int = refimpl._GLV_MINUS_B2
                # n/2 threshold for the signed mapping
                self.half_n_limbs = fp.to_limbs(params.n // 2)

    def __repr__(self):
        return f"Curve({self.params.name})"


SECP256K1 = Curve(refimpl.SECP256K1)
SM2P256V1 = Curve(refimpl.SM2P256V1)


# ---------------------------------------------------------------------------
# Jacobian point arithmetic (points packed as [..., 3, NLIMBS, B], field rep)
# ---------------------------------------------------------------------------

def _pack(X, Y, Z):
    return jnp.stack([X, Y, Z], axis=-3)


def _unpack(P):
    return P[..., 0, :, :], P[..., 1, :, :], P[..., 2, :, :]


def _sel(cond, a, b):
    """cond ? a : b over packed points (cond: [..., B])."""
    return jnp.where(cond[..., None, None, :], a, b)


def _inf_like(P):
    return jnp.zeros_like(P)


def _mulk(f, pairs):
    """One stacked field multiply for k independent products.

    Stacking along a fresh leading axis turns k multiplies into one call —
    k-fold fewer HLO nodes (compile time) and longer vectors at run time.
    """
    a = jnp.stack([p[0] for p in pairs], axis=0)
    b = jnp.stack([p[1] for p in pairs], axis=0)
    r = f.mul(a, b)
    return [r[i] for i in range(len(pairs))]


def jac_double(cv: Curve, P):
    """2P. Complete: Z=0 (infinity) propagates as Z3=0."""
    f = cv.fp
    X, Y, Z = _unpack(P)
    two_y = f.add(Y, Y)
    if cv.a_is_zero:
        XX, YY = _mulk(f, [(X, X), (Y, Y)])
        XYY, YYYY, Z3 = _mulk(f, [(X, YY), (YY, YY), (two_y, Z)])
        M = f.add(f.add(XX, XX), XX)  # 3*X^2
    elif cv.a_is_minus3:
        # a = -3 (SM2, NIST curves): M = 3*(X - Z^2)*(X + Z^2)
        YY, ZZ = _mulk(f, [(Y, Y), (Z, Z)])
        XYY, YYYY, Z3, T = _mulk(
            f, [(X, YY), (YY, YY), (two_y, Z),
                (f.sub(X, ZZ), f.add(X, ZZ))])
        M = f.add(f.add(T, T), T)
    else:
        XX, YY, ZZ = _mulk(f, [(X, X), (Y, Y), (Z, Z)])
        XYY, YYYY, Z3, ZZZZ = _mulk(
            f, [(X, YY), (YY, YY), (two_y, Z), (ZZ, ZZ)])
        a_c = jnp.broadcast_to(fp._col(cv.a_rep), ZZZZ.shape)
        aZ4 = f.mul(a_c, ZZZZ)
        M = f.add(f.add(f.add(XX, XX), XX), aZ4)
    S = f.add(XYY, XYY)
    S = f.add(S, S)  # 4*X*Y^2
    MM = f.mul(M, M)
    X3 = f.sub(MM, f.add(S, S))
    y8 = f.add(YYYY, YYYY)
    y8 = f.add(y8, y8)
    y8 = f.add(y8, y8)  # 8*Y^4
    Y3 = f.sub(f.mul(M, f.sub(S, X3)), y8)
    return _pack(X3, Y3, Z3)


def jac_add(cv: Curve, P, Q):
    """P + Q, both Jacobian. Complete by selection (doubling/infinity)."""
    f = cv.fp
    X1, Y1, Z1 = _unpack(P)
    X2, Y2, Z2 = _unpack(Q)
    p_inf = is_zero(Z1)
    q_inf = is_zero(Z2)
    Z1Z1, Z2Z2 = _mulk(f, [(Z1, Z1), (Z2, Z2)])
    U1, U2, Y1Z2, Y2Z1 = _mulk(
        f, [(X1, Z2Z2), (X2, Z1Z1), (Y1, Z2), (Y2, Z1)])
    S1, S2 = _mulk(f, [(Y1Z2, Z2Z2), (Y2Z1, Z1Z1)])
    H = f.sub(U2, U1)
    R = f.sub(S2, S1)
    h0 = is_zero(H)
    r0 = is_zero(R)
    HH, RR = _mulk(f, [(H, H), (R, R)])
    HHH, V, Z1Z2 = _mulk(f, [(H, HH), (U1, HH), (Z1, Z2)])
    X3 = f.sub(f.sub(RR, HHH), f.add(V, V))
    t1, t2, Z3 = _mulk(f, [(R, f.sub(V, X3)), (S1, HHH), (Z1Z2, H)])
    Y3 = f.sub(t1, t2)
    res = _pack(X3, Y3, Z3)
    res = _sel(h0 & r0, jac_double(cv, P), res)  # P == Q
    res = _sel(h0 & ~r0, _inf_like(res), res)  # P == -Q
    res = _sel(q_inf, P, res)
    res = _sel(p_inf, Q, res)
    return res


def jac_add_affine(cv: Curve, P, qx, qy):
    """P + (qx, qy) with the second operand affine (Z2 = 1): mixed add."""
    f = cv.fp
    X1, Y1, Z1 = _unpack(P)
    p_inf = is_zero(Z1)
    Z1Z1 = f.mul(Z1, Z1)
    U2, qyZ1 = _mulk(f, [(qx, Z1Z1), (qy, Z1)])
    S2 = f.mul(qyZ1, Z1Z1)
    H = f.sub(U2, X1)
    R = f.sub(S2, Y1)
    h0 = is_zero(H)
    r0 = is_zero(R)
    HH, RR = _mulk(f, [(H, H), (R, R)])
    HHH, V, Z3 = _mulk(f, [(H, HH), (X1, HH), (Z1, H)])
    X3 = f.sub(f.sub(RR, HHH), f.add(V, V))
    t1, t2 = _mulk(f, [(R, f.sub(V, X3)), (Y1, HHH)])
    Y3 = f.sub(t1, t2)
    res = _pack(X3, Y3, Z3)
    res = _sel(h0 & r0, jac_double(cv, P), res)
    res = _sel(h0 & ~r0, _inf_like(res), res)
    one = f.one_rep(qx.shape)
    lifted = _pack(jnp.broadcast_to(qx, one.shape),
                   jnp.broadcast_to(qy, one.shape), one)
    res = _sel(p_inf, lifted, res)
    return res


# ---------------------------------------------------------------------------
# windowed Shamir double-scalar multiplication
# ---------------------------------------------------------------------------

def _take_const(gt_flat: np.ndarray, dig):
    """Constant table [TBL, 2L] x digits [B] -> (x [L, B], y [L, B]).

    One-hot weighted sum: gathers lower poorly on TPU; a small tensordot
    (a [2L, TBL] x [TBL, B] matmul) stays on the fast path.
    """
    oh = (dig[None, :] == jnp.arange(TBL, dtype=dig.dtype)[:, None]
          ).astype(jnp.uint32)
    ge = jnp.tensordot(jnp.asarray(gt_flat.T), oh, axes=[[1], [0]])  # [2L, B]
    return ge[:NLIMBS], ge[NLIMBS:]


def _take_batch(tq, dig):
    """Per-element table [TBL, C, L, B] x digits [B] -> [C, L, B]
    (C = 2 affine coords for the ladders' normalized tables)."""
    oh = (dig[None, :] == jnp.arange(TBL, dtype=dig.dtype)[:, None]
          ).astype(jnp.uint32)
    return jnp.sum(tq * oh[:, None, None, :], axis=0)


def _q_window_table(cv: Curve, qx_r, qy_r):
    """Per-element window table tq[k] = k*Q (Jacobian), k in [0, 16),
    built with a scan so the add body compiles once. Shared by the plain
    and GLV ladders."""
    q1 = _pack(qx_r, qy_r, cv.fp.one_rep(qx_r.shape))

    def tbl_step(prev, _):
        nxt = jac_add(cv, prev, q1)
        return nxt, nxt

    _, rest = jax.lax.scan(tbl_step, q1, None, length=TBL - 2)
    return jnp.concatenate([_inf_like(q1)[None], q1[None], rest], axis=0)


def _q_window_affine(cv: Curve, qx_r, qy_r):
    """Affine Q window table stacked as [TBL, 2, L, B] (x, y): the
    Jacobian table batch-normalized with ONE product-tree inversion over
    all TBL x B Z values, so every ladder add against it is a cheap mixed
    add. Entry 0 (infinity) normalizes to garbage — harmless, because a
    zero window digit skips the add entirely (`_sel(d == 0, ...)`)."""
    f = cv.fp
    tq = _q_window_table(cv, qx_r, qy_r)
    X, Y, Z = tq[:, 0], tq[:, 1], tq[:, 2]  # each [TBL, L, B]
    tbl_n, L, B = X.shape
    zf = jnp.transpose(Z, (1, 0, 2)).reshape(L, tbl_n * B)
    w = zf.shape[-1]
    pad = (1 << (w - 1).bit_length()) - w  # inv_batch's product tree
    if pad:  # needs a power-of-two width; ones invert to ones harmlessly
        zf = jnp.concatenate([zf, f.one_rep((L, pad))], axis=-1)
    zi = f.inv_batch(zf)[..., :tbl_n * B]
    zi = jnp.transpose(zi.reshape(L, tbl_n, B), (1, 0, 2))
    zi2 = f.mul(zi, zi)
    ax, zi3 = _mulk(f, [(X, zi2), (zi2, zi)])
    ay = f.mul(Y, zi3)
    return jnp.stack([ax, ay], axis=1)


def shamir_mult(cv: Curve, k1, k2, qx_r, qy_r):
    """k1*G + k2*Q -> packed Jacobian point (field rep).

    k1, k2: plain canonical scalar limbs [L, B]; qx_r/qy_r: affine Q in
    field rep. 64-step scan, 4-bit windows for both scalars; the Q table
    is batch-normalized to affine so both adds per step are mixed adds.
    """
    if (fp._use_pallas() and k1.shape[-1] % 128 == 0
            and (cv.a_is_zero or cv.a_is_minus3)):
        from . import pallas_ec

        gts = jnp.asarray(cv.g_table)[None]
        d1 = fp.window_digits(k1, WINDOW)[..., ::-1, :]
        d2 = fp.window_digits(k2, WINDOW)[..., ::-1, :]
        digs_all = jnp.stack([d1, d2], axis=1)  # [steps, 2, B]
        negs = jnp.zeros((2, k1.shape[-1]), jnp.uint32)
        q_planes = jnp.stack([qx_r, qy_r])[None]
        return pallas_ec.ladder(cv.fp, cv.a_is_zero, cv.a_is_minus3,
                                NDIGITS, gts, digs_all, negs, q_planes)

    tq2 = _q_window_affine(cv, qx_r, qy_r)  # [TBL, 2, L, B]

    d1 = fp.window_digits(k1, WINDOW)[..., ::-1, :]  # [64, B] MSB-first
    d2 = fp.window_digits(k2, WINDOW)[..., ::-1, :]

    def body(acc, digs):
        dg, dq = digs
        for _ in range(WINDOW):
            acc = jac_double(cv, acc)
        gx_e, gy_e = _take_const(cv.g_table, dg)
        added_g = jac_add_affine(cv, acc, gx_e, gy_e)
        acc = _sel(dg == 0, acc, added_g)
        qe = _take_batch(tq2, dq)
        added_q = jac_add_affine(cv, acc, qe[..., 0, :, :], qe[..., 1, :, :])
        acc = _sel(dq == 0, acc, added_q)
        return acc, None

    init = jnp.zeros((3, NLIMBS) + k1.shape[-1:], jnp.uint32)
    acc, _ = jax.lax.scan(body, init, (d1, d2))
    return acc


# ---------------------------------------------------------------------------
# GLV endomorphism ladder (secp256k1): half-length scalars, 4 tables
# ---------------------------------------------------------------------------

def _mul_shift_384(k, g_limbs):
    """floor(k * g / 2^384) for canonical scalar limbs k [L, B] and a
    256-bit constant g — the GLV rounding step (c_i), done as one wide
    multiply and a limb slice (384 / 16 = limb 24)."""
    cols = fp.mul_wide(k, fp._col(jnp.asarray(g_limbs)))
    exact, _ = fp.carry_prop(cols, 2 * NLIMBS)
    hi = exact[..., 24:, :]  # 8 limbs ~ 2^128
    return fp._pad(hi, 0, NLIMBS - hi.shape[-2])


def _glv_split_device(cv: Curve, k):
    """k [L, B] canonical mod n -> (m1, neg1, m2, neg2): signed halves
    with magnitudes < 2^136, matching refimpl.glv_split + signed mapping."""
    fn_ = cv.fn
    c1 = _mul_shift_384(k, cv.g1_limbs)
    c2 = _mul_shift_384(k, cv.g2_limbs)
    mb1 = fp._col(fn_.encode_int(cv.mb1_int))  # Montgomery-domain consts
    mb2 = fp._col(fn_.encode_int(cv.mb2_int))
    lam = fp._col(fn_.encode_int(cv.glv_lambda))
    k2 = fn_.from_rep(fn_.add(fn_.mul(fn_.to_rep(c1), mb1),
                              fn_.mul(fn_.to_rep(c2), mb2)))
    k1 = fn_.sub(fn_.reduce_loose(k),
                 fn_.from_rep(fn_.mul(fn_.to_rep(k2), lam)))

    half = fp._col(cv.half_n_limbs)
    nl = fp._col(fn_.limbs)

    def signed(x):
        neg_flag = ~fp.geq(half, x)  # x > n/2  <=>  not (n/2 >= x)
        mag, _ = fp.sub_limbs(nl + jnp.zeros_like(x), x)
        return select(neg_flag, mag, x), neg_flag

    m1, n1 = signed(k1)
    m2, n2 = signed(k2)
    return m1, n1, m2, n2


def _neg_y(f, Y, flag):
    """Conditionally negate a field-rep Y coordinate (branch-free)."""
    return select(flag, f.neg(Y), Y)


def glv_shamir_mult(cv: Curve, k1, k2, qx_r, qy_r):
    """k1*G + k2*Q via the endomorphism: both scalars split into signed
    ~128-bit halves, then one 34-step scan over FOUR window tables
    (G, phi(G) as affine constants; Q, phi(Q) per batch element) — 136
    doublings instead of 256. Same complete-by-selection point ops as
    `shamir_mult`, so adversarial inputs stay safe."""
    f = cv.fp
    a1, s1, a2, s2 = _glv_split_device(cv, k1)
    b1, t1, b2, t2 = _glv_split_device(cv, k2)

    def digs(m):
        d = fp.window_digits(m, WINDOW)[..., :GLV_DIGITS, :]
        return d[..., ::-1, :]  # MSB-first

    if (fp._use_pallas() and k1.shape[-1] % 128 == 0
            and (cv.a_is_zero or cv.a_is_minus3)):
        from . import pallas_ec

        beta = fp._col(cv.beta_rep)
        qlx = f.mul(qx_r, beta)
        gts = jnp.stack([jnp.asarray(cv.g_table),
                         jnp.asarray(cv.g_table_endo)])
        digs_all = jnp.stack([digs(a1), digs(b1), digs(a2), digs(b2)],
                             axis=1)  # [steps, 4, B]
        negs = jnp.stack([s1, t1, s2, t2]).astype(jnp.uint32)
        q_planes = jnp.stack([jnp.stack([qx_r, qy_r]),
                              jnp.stack([qlx, qy_r])])
        return pallas_ec.ladder(f, cv.a_is_zero, cv.a_is_minus3,
                                GLV_DIGITS, gts, digs_all, negs, q_planes)

    # per-element tables, batch-normalized affine; phi applies beta to x
    tq2 = _q_window_affine(cv, qx_r, qy_r)  # [TBL, 2, L, B]
    beta = jnp.broadcast_to(fp._col(cv.beta_rep), tq2[:, 0].shape)
    tql2 = jnp.stack([f.mul(tq2[:, 0], beta), tq2[:, 1]], axis=1)

    da1, da2, db1, db2 = digs(a1), digs(a2), digs(b1), digs(b2)

    def body(acc, ds):
        d_g, d_gl, d_q, d_ql = ds
        for _ in range(WINDOW):
            acc = jac_double(cv, acc)
        gx_e, gy_e = _take_const(cv.g_table, d_g)
        added = jac_add_affine(cv, acc, gx_e, _neg_y(f, gy_e, s1))
        acc = _sel(d_g == 0, acc, added)
        gx_e, gy_e = _take_const(cv.g_table_endo, d_gl)
        added = jac_add_affine(cv, acc, gx_e, _neg_y(f, gy_e, s2))
        acc = _sel(d_gl == 0, acc, added)
        qe = _take_batch(tq2, d_q)
        added = jac_add_affine(cv, acc, qe[..., 0, :, :],
                               _neg_y(f, qe[..., 1, :, :], t1))
        acc = _sel(d_q == 0, acc, added)
        qe = _take_batch(tql2, d_ql)
        added = jac_add_affine(cv, acc, qe[..., 0, :, :],
                               _neg_y(f, qe[..., 1, :, :], t2))
        acc = _sel(d_ql == 0, acc, added)
        return acc, None

    init = jnp.zeros((3, NLIMBS) + k1.shape[-1:], jnp.uint32)
    acc, _ = jax.lax.scan(body, init, (da1, da2, db1, db2))
    return acc


def double_mult(cv: Curve, k1, k2, qx_r, qy_r):
    """k1*G + k2*Q — GLV ladder when the curve has the endomorphism,
    plain double-width Shamir otherwise."""
    if cv.has_endo:
        return glv_shamir_mult(cv, k1, k2, qx_r, qy_r)
    return shamir_mult(cv, k1, k2, qx_r, qy_r)


# ---------------------------------------------------------------------------
# verification / recovery kernels
# ---------------------------------------------------------------------------

def _scalar_checks(fn, r, s):
    nl = fp._col(fn.limbs)
    return (~is_zero(r)) & (~is_zero(s)) & (~geq(r, nl)) & (~geq(s, nl))


def _on_curve(cv: Curve, xr, yr):
    f = cv.fp
    rhs = f.add(f.mul(f.sqr(xr), xr),
                jnp.broadcast_to(fp._col(cv.b_rep), xr.shape))
    if not cv.a_is_zero:
        rhs = f.add(rhs, f.mul(jnp.broadcast_to(fp._col(cv.a_rep), xr.shape), xr))
    return eq(f.sqr(yr), rhs)


def _x_matches_mod_n(cv: Curve, X, Z, rscalar):
    """Does the affine x of (X, :, Z) reduce to rscalar mod n?

    Avoids a field inversion: x == r (mod n) iff X == cand * Z^2 in the
    field for cand in {r, r + n} (the second only when r + n < p).
    """
    f, fn_ = cv.fp, cv.fn
    zz = f.sqr(Z)
    m1 = eq(X, f.mul(f.to_rep(rscalar), zz))
    rpn, carry = fp.add_limbs(rscalar, fp._col(fn_.limbs))
    lt_p = (carry == 0) & (~geq(rpn, fp._col(f.limbs)))
    cand2 = select(lt_p, rpn, jnp.zeros_like(rpn))
    m2 = lt_p & eq(X, f.mul(f.to_rep(cand2), zz))
    return m1 | m2


def _tx(a):
    """Public boundary: [B, NLIMBS] -> lane-major [NLIMBS, B]."""
    assert a.ndim == 2 and a.shape[-1] == NLIMBS
    return jnp.transpose(a)


_FUSED_VERIFY_CACHE: list = []


def _use_fused_verify() -> bool:
    """Opt-in for the single-kernel verify (ops.pallas_verify) until its
    device lowering is validated; flip the default once the sweep has
    asserted it on real TPU. Resolved ONCE at first use (the check runs
    at trace time inside the jitted verify, so a late env flip would
    otherwise be frozen out by the jit cache unpredictably — set
    FBTPU_FUSED_VERIFY before the first verify call)."""
    if not _FUSED_VERIFY_CACHE:
        import os

        _FUSED_VERIFY_CACHE.append(
            os.environ.get("FBTPU_FUSED_VERIFY") == "1" and fp._use_pallas())
    return _FUSED_VERIFY_CACHE[0]


@functools.partial(jax.jit, static_argnums=0)
def ecdsa_verify_batch(cv: Curve, e, r, s, qx, qy):
    """Batched ECDSA verify. All args [B, NLIMBS] uint32; -> bool[B].

    e: message digest as 256-bit integer (will be reduced mod n);
    r, s: signature scalars; qx, qy: affine public key (field canonical).
    """
    e, r, s, qx, qy = map(_tx, (e, r, s, qx, qy))
    if (_use_fused_verify() and cv is SECP256K1
            and e.shape[-1] % 128 == 0):
        # gate on the exact singleton: the fused kernel hardcodes
        # secp256k1 constants, and another has_endo curve instance would
        # trip its internal assert inside the jitted trace (ADVICE r4)
        from . import pallas_verify

        return pallas_verify.ecdsa_verify_fused(cv, e, r, s, qx, qy)
    f, fn_ = cv.fp, cv.fn
    ok = _scalar_checks(fn_, r, s)
    pl = fp._col(f.limbs)
    ok &= (~geq(qx, pl)) & (~geq(qy, pl))
    qxr, qyr = f.to_rep(qx), f.to_rep(qy)
    ok &= _on_curve(cv, qxr, qyr)
    ok &= ~(is_zero(qx) & is_zero(qy))

    w = fn_.inv_batch(fn_.to_rep(s))  # Mont(s^-1), batched tree
    u1 = fn_.from_rep(fn_.mul(fn_.to_rep(e), w))
    u2 = fn_.from_rep(fn_.mul(fn_.to_rep(r), w))
    R = double_mult(cv, u1, u2, qxr, qyr)
    X, _, Z = _unpack(R)
    ok &= ~is_zero(Z)
    ok &= _x_matches_mod_n(cv, X, Z, fn_.reduce_loose(r))
    return ok


@functools.partial(jax.jit, static_argnums=0)
def ecdsa_recover_batch(cv: Curve, e, r, s, v):
    """Batched public-key recovery (the reference's per-tx hot op,
    Transaction.h:79 -> wedpr_secp256k1_recover_public_key).

    e, r, s: [B, NLIMBS]; v: [B] uint32 recovery id in [0, 4).
    -> (qx, qy, ok): affine recovered key (canonical limbs, [B, NLIMBS])
    plus validity mask [B].
    """
    e, r, s = map(_tx, (e, r, s))
    if (_use_fused_verify() and cv is SECP256K1
            and e.shape[-1] % 128 == 0):
        from . import pallas_verify

        qx, qy, ok = pallas_verify.ecdsa_recover_fused(cv, e, r, s, v)
        return jnp.transpose(qx), jnp.transpose(qy), ok
    f, fn_ = cv.fp, cv.fn
    ok = _scalar_checks(fn_, r, s) & (v < 4)
    pl = fp._col(f.limbs)

    # x = r + (v >> 1) * n, must stay below p
    hi_bit = ((v >> 1) & 1) == 1
    nbc = jnp.broadcast_to(fp._col(fn_.limbs), r.shape)
    addend = select(hi_bit, nbc, jnp.zeros_like(r))
    xr, carry = fp.add_limbs(r, addend)
    ok &= (carry == 0) & (~geq(xr, pl))
    xr = select(ok, xr, jnp.zeros_like(xr))

    xm = f.to_rep(xr)
    ysq = f.add(f.mul(f.sqr(xm), xm),
                jnp.broadcast_to(fp._col(cv.b_rep), xm.shape))
    if not cv.a_is_zero:
        ysq = f.add(ysq, f.mul(jnp.broadcast_to(fp._col(cv.a_rep), xm.shape), xm))
    y = f.pow_const(ysq, (cv.params.p + 1) // 4)  # sqrt (p = 3 mod 4)
    ok &= eq(f.sqr(y), ysq)
    yc = f.from_rep(y)
    flip = (yc[..., 0, :] & 1) != (v & 1)
    ym = select(flip, f.neg(y), y)

    rinv = fn_.inv_batch(fn_.to_rep(r))
    u1 = fn_.from_rep(fn_.mul(fn_.neg(fn_.to_rep(e)), rinv))  # -e/r mod n
    u2 = fn_.from_rep(fn_.mul(fn_.to_rep(s), rinv))  # s/r mod n
    Q = double_mult(cv, u1, u2, xm, ym)
    X, Y, Z = _unpack(Q)
    ok &= ~is_zero(Z)

    zinv = f.inv_batch(Z)
    zi2 = f.sqr(zinv)
    qx = f.from_rep(f.mul(X, zi2))
    qy = f.from_rep(f.mul(Y, f.mul(zi2, zinv)))
    qx = select(ok, qx, jnp.zeros_like(qx))
    qy = select(ok, qy, jnp.zeros_like(qy))
    return jnp.transpose(qx), jnp.transpose(qy), ok


@functools.partial(jax.jit, static_argnums=0)
def sm2_verify_batch(cv: Curve, e, r, s, qx, qy):
    """Batched SM2 verify (GB/T 32918): R' = e + x(s*G + (r+s)*Q) == r.

    e is the SM3(Z_A || M) digest as a 256-bit integer. All args
    [B, NLIMBS]; -> bool[B].
    """
    e, r, s, qx, qy = map(_tx, (e, r, s, qx, qy))
    if (_use_fused_verify() and cv is SM2P256V1
            and e.shape[-1] % 128 == 0):
        # singleton gate, not a_is_minus3: sm2_verify_fused asserts the
        # SM2 singleton, so e.g. a test-built P-256 must fall through to
        # the XLA path instead of crashing in-trace (ADVICE r4)
        from . import pallas_verify

        return pallas_verify.sm2_verify_fused(cv, e, r, s, qx, qy)
    f, fn_ = cv.fp, cv.fn
    ok = _scalar_checks(fn_, r, s)
    pl = fp._col(f.limbs)
    ok &= (~geq(qx, pl)) & (~geq(qy, pl))
    qxr, qyr = f.to_rep(qx), f.to_rep(qy)
    ok &= _on_curve(cv, qxr, qyr)
    ok &= ~(is_zero(qx) & is_zero(qy))

    rc = fn_.reduce_loose(r)
    t = fn_.add(rc, fn_.reduce_loose(s))
    ok &= ~is_zero(t)
    P = shamir_mult(cv, fn_.reduce_loose(s), t, qxr, qyr)
    X, _, Z = _unpack(P)
    ok &= ~is_zero(Z)
    e_red = fn_.reduce_loose(e)  # e < 2^256 < 2n: one conditional subtract
    c = fn_.sub(rc, e_red)  # candidate x1 mod n
    ok &= _x_matches_mod_n(cv, X, Z, c)
    return ok


# ---------------------------------------------------------------------------
# host conveniences (tests / low-volume paths)
# ---------------------------------------------------------------------------

def limbs(xs) -> jnp.ndarray:
    """List of ints -> [N, NLIMBS] uint32 device array."""
    return jnp.asarray(bigint.batch_to_limbs(xs))


def hash_ints(hashes: list[bytes]) -> jnp.ndarray:
    return limbs([int.from_bytes(h, "big") for h in hashes])
