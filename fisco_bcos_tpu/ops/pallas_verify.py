"""ECDSA verify END-TO-END in one Pallas kernel (secp256k1 GLV form).

ops.pallas_fp fused the field multiplies and ops.pallas_ec the ladder;
what remains of `ec.ecdsa_verify_batch` at the XLA level — scalar checks,
on-curve test, the batched modular inversion of s (product tree + Fermat
power), u1/u2, the GLV split, window digits, and the final x == r (mod n)
test — is still ~100 per-op dispatches plus ~40 pallas launches per call.
This kernel runs the WHOLE verify per block: five [16, B] inputs in, one
boolean lane out.

Everything reuses the value-level building blocks already validated
elsewhere: `pallas_fp.{solinas,mont}_mul_body` / `pow_digits_values`,
`pallas_ec.ladder_values` (bit-exact vs the XLA ladder), and `ops.fp`'s
limb helpers, so the only new logic here is the constant plumbing and the
in-kernel product-tree inversion (same tree shape as fp.inv_batch, per
kernel block).

Reference counterpart: wedpr_secp256k1_verify
(/root/reference/bcos-crypto/bcos-crypto/signature/secp256k1/
Secp256k1Crypto.cpp:57) — one fused batch kernel instead of a per-
signature native call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import fp, pallas_ec, pallas_fp
from .fp import NLIMBS
from .pallas_ec import FieldCtx, TBL, WINDOW

U32 = jnp.uint32
BLK = 256  # ladder tables dominate VMEM (see pallas_ec.LADDER_BLK)

# consts block column layout ([16, 13] uint32)
_C_P, _C_B, _C_BETA, _C_N, _C_NPRIME, _C_R2, _C_ONEM, _C_HALF, \
    _C_G1, _C_G2, _C_MB1, _C_MB2, _C_LAM = range(13)


class _MontCtx(FieldCtx):
    """FieldCtx for the curve-order field plus the domain-conversion
    columns the verify pipeline needs (r2 for to_rep, plain 1 for
    from_rep, canonical reduce)."""

    def __init__(self, field, limbs_col, nprime_col, one_col, r2_col):
        super().__init__(field, limbs_col, nprime_col, one_col)
        self.r2_col = r2_col

    def reduce_loose(self, a):
        d, brw = fp.sub_limbs(a, self.limbs_col)
        return fp.select(brw == 0, d, a)

    def to_rep(self, a):
        return self.mul(self.reduce_loose(a),
                        jnp.broadcast_to(self.r2_col, a.shape))

    def from_rep(self, a):
        one = (jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
               == 0).astype(U32)
        return self.mul(a, one)

    def inv_tree(self, a, digs_ref, nd):
        return inv_tree_values(self, a, digs_ref, nd)


def inv_tree_values(f: FieldCtx, a, digs_ref, nd):
    """Elementwise a^-1 (internal domain) over the block lanes: product
    tree + ONE Fermat power on the root (exponent digits in SMEM). Zero
    lanes pass through as zero, as in fp.inv_batch. Works for both field
    kinds (the domain 1 comes from pallas_ec.field_one)."""
    w = a.shape[-1]
    # the halving splits below mis-pair lanes via broadcasting when the
    # block width is not a power of two — fail loudly instead of computing
    # wrong field inverses (today's verify/recover cap of 256 keeps
    # _pick_blk in {128, 256}, but nothing else enforces that)
    assert w > 0 and (w & (w - 1)) == 0, \
        f"inv_tree_values needs a power-of-two block width, got {w}"
    zero = fp.is_zero(a)
    one_d = pallas_ec.field_one(f, a.shape)
    safe = fp.select(zero, one_d, a)
    levels = []
    cur = safe
    while cur.shape[-1] > 1:
        w = cur.shape[-1] // 2
        left, right = cur[..., :w], cur[..., w:]
        levels.append((left, right))
        cur = f.mul(left, right)
    invp = pallas_fp.pow_digits_values(
        lambda x, y: f.mul(x, y), one_d[..., :1], cur, digs_ref, nd)
    for left, right in reversed(levels):
        inv_l = f.mul(invp, right)
        inv_r = f.mul(invp, left)
        invp = jnp.concatenate([inv_l, inv_r], axis=-1)
    return fp.select(zero, jnp.zeros_like(a), invp)


def _glv_split_values(fn: _MontCtx, c_ref, k):
    """Value port of ec._glv_split_device: canonical k [16, B] ->
    (m1, neg1, m2, neg2) signed halves."""
    def mul_shift_384(kk, gcol):
        cols = fp.mul_wide(kk, jnp.broadcast_to(gcol, kk.shape))
        exact, _ = fp.carry_prop(cols, 2 * NLIMBS)
        hi = exact[..., 24:, :]
        return fp._pad(hi, 0, NLIMBS - hi.shape[-2])

    c1 = mul_shift_384(k, c_ref[:, _C_G1:_C_G1 + 1])
    c2 = mul_shift_384(k, c_ref[:, _C_G2:_C_G2 + 1])
    mb1 = c_ref[:, _C_MB1:_C_MB1 + 1]
    mb2 = c_ref[:, _C_MB2:_C_MB2 + 1]
    lam = c_ref[:, _C_LAM:_C_LAM + 1]
    k2 = fn.from_rep(fn.add(
        fn.mul(fn.to_rep(c1), jnp.broadcast_to(mb1, c1.shape)),
        fn.mul(fn.to_rep(c2), jnp.broadcast_to(mb2, c2.shape))))
    k1 = fn.sub(fn.reduce_loose(k),
                fn.from_rep(fn.mul(fn.to_rep(k2),
                                   jnp.broadcast_to(lam, k2.shape))))

    half = c_ref[:, _C_HALF:_C_HALF + 1]
    nl = fn.limbs_col

    def signed(x):
        neg_flag = ~fp.geq(jnp.broadcast_to(half, x.shape), x)
        mag, _ = fp.sub_limbs(nl + jnp.zeros_like(x), x)
        return fp.select(neg_flag, mag, x), neg_flag

    m1, n1 = signed(k1)
    m2, n2 = signed(k2)
    return m1, n1, m2, n2



def _dig_at(digs_all):
    """ladder_values' digit accessor over an in-kernel [rows, steps, B]
    VALUE (these kernels compute the digits in-kernel, so there is no ref
    to index)."""
    return lambda row, r: jax.lax.dynamic_index_in_dim(
        digs_all[row], r, axis=0, keepdims=False)


def _glv_ladder(f: FieldCtx, fn: "_MontCtx", c_ref, gts_ref, nsteps,
                u1, u2, qx, qy, tbl_ref, opnd_ref):
    """Shared scalars-to-ladder plumbing for verify and recover: GLV-split
    both scalars, build the interleaved digit/negs planes, and run
    ladder_values. qx/qy are canonical field-rep affine Q coordinates."""
    a1, s1, a2, s2 = _glv_split_values(fn, c_ref, u1)
    b1, t1, b2, t2 = _glv_split_values(fn, c_ref, u2)

    def digs(m):
        d = fp.window_digits(m, WINDOW)[..., :nsteps, :]
        return d[..., ::-1, :]

    digs_all = jnp.stack([digs(a1), digs(b1), digs(a2), digs(b2)], axis=0)
    negs = jnp.stack([s1.astype(U32), t1.astype(U32),
                      s2.astype(U32), t2.astype(U32)], axis=0)
    beta = jnp.broadcast_to(c_ref[:, _C_BETA:_C_BETA + 1], qx.shape)
    qlx = f.mul(qx, beta)
    q_planes = jnp.stack([jnp.stack([qx, qy]),
                          jnp.stack([qlx, qy])], axis=0)
    return pallas_ec.ladder_values(f, (True, False), nsteps, 2,
                                   gts_ref[:, :, :], _dig_at(digs_all),
                                   negs, q_planes, tbl_ref, opnd_ref)


def _verify_kernel_body(field_p, field_n, nsteps,
                        invdigs_ref, c_ref, gts_ref, e_ref, r_ref, s_ref,
                        qx_ref, qy_ref, ok_ref, tbl_ref, opnd_ref):
    f = FieldCtx(field_p, c_ref[:, _C_P:_C_P + 1])
    fn = _MontCtx(field_n, c_ref[:, _C_N:_C_N + 1],
                  c_ref[:, _C_NPRIME:_C_NPRIME + 1],
                  c_ref[:, _C_ONEM:_C_ONEM + 1],
                  c_ref[:, _C_R2:_C_R2 + 1])
    e, r, s = e_ref[:, :], r_ref[:, :], s_ref[:, :]
    qx, qy = qx_ref[:, :], qy_ref[:, :]
    nl = fn.limbs_col
    pl_ = f.limbs_col

    ok = ((~fp.is_zero(r)) & (~fp.is_zero(s))
          & (~fp.geq(r, jnp.broadcast_to(nl, r.shape)))
          & (~fp.geq(s, jnp.broadcast_to(nl, s.shape))))
    ok &= ((~fp.geq(qx, jnp.broadcast_to(pl_, qx.shape)))
           & (~fp.geq(qy, jnp.broadcast_to(pl_, qy.shape))))
    def reduce_p(a):  # Solinas plain-domain canonicalize (to_rep)
        d, brw = fp.sub_limbs(a, jnp.broadcast_to(pl_, a.shape))
        return fp.select(brw == 0, d, a)

    qxr = reduce_p(qx)
    qyr = reduce_p(qy)
    b_col = jnp.broadcast_to(c_ref[:, _C_B:_C_B + 1], qx.shape)
    rhs = f.add(f.mul(f.sqr(qxr), qxr), b_col)
    ok &= fp.eq(f.sqr(qyr), rhs)
    ok &= ~(fp.is_zero(qx) & fp.is_zero(qy))

    # w = Mont(s^-1) via the per-block product tree
    w = fn.inv_tree(fn.to_rep(s), invdigs_ref, invdigs_ref.shape[0])
    u1 = fn.from_rep(fn.mul(fn.to_rep(e), w))
    u2 = fn.from_rep(fn.mul(fn.to_rep(r), w))

    acc = _glv_ladder(f, fn, c_ref, gts_ref, nsteps, u1, u2, qxr, qyr,
                      tbl_ref, opnd_ref)
    X, _, Z = acc[0], acc[1], acc[2]
    ok &= ~fp.is_zero(Z)

    # x(R) == r (mod n) without inversion (ec._x_matches_mod_n)
    rc = fn.reduce_loose(r)
    zz = f.sqr(Z)
    m1 = fp.eq(X, f.mul(rc, zz))
    rpn, carry = fp.add_limbs(rc, jnp.broadcast_to(nl, rc.shape))
    lt_p = (carry == 0) & (~fp.geq(rpn, jnp.broadcast_to(pl_, rpn.shape)))
    cand2 = fp.select(lt_p, rpn, jnp.zeros_like(rpn))
    m2 = lt_p & fp.eq(X, f.mul(cand2, zz))
    ok &= (m1 | m2)
    ok_ref[0, :] = ok.astype(U32)


@functools.lru_cache(maxsize=None)
def _verify_call(field_p, field_n, nsteps: int, nd_inv: int, B: int,
                 blk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(invdigs_ref, c_ref, gts_ref, e_ref, r_ref, s_ref,
               qx_ref, qy_ref, ok_ref, tbl_ref, opnd_ref):
        _verify_kernel_body(field_p, field_n, nsteps, invdigs_ref,
                            c_ref[:, :], gts_ref[:, :, :], e_ref, r_ref,
                            s_ref, qx_ref, qy_ref, ok_ref, tbl_ref, opnd_ref)

    spec = pl.BlockSpec((NLIMBS, blk), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, B), U32),
        grid=(B // blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((NLIMBS, 13), lambda i: (0, 0)),
            pl.BlockSpec((2, TBL, 2 * NLIMBS), lambda i: (0, 0, 0)),
            spec, spec, spec, spec, spec,
        ],
        out_specs=pl.BlockSpec((1, blk), lambda i: (0, i)),
        scratch_shapes=pallas_ec.ladder_scratch(2, blk),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def _secp_consts():
    """Host-side consts block for the secp256k1 Curve singleton."""
    from . import ec as _ec

    cv = _ec.SECP256K1
    c = np.zeros((NLIMBS, 13), np.uint32)
    c[:, _C_P] = cv.fp.limbs
    c[:, _C_B] = cv.b_rep
    c[:, _C_BETA] = cv.beta_rep
    c[:, _C_N] = cv.fn.limbs
    c[:, _C_NPRIME] = cv.fn.nprime
    c[:, _C_R2] = cv.fn.r2
    c[:, _C_ONEM] = cv.fn.one_m
    c[:, _C_HALF] = cv.half_n_limbs
    c[:, _C_G1] = cv.g1_limbs
    c[:, _C_G2] = cv.g2_limbs
    c[:, _C_MB1] = cv.fn.encode_int(cv.mb1_int)
    c[:, _C_MB2] = cv.fn.encode_int(cv.mb2_int)
    c[:, _C_LAM] = cv.fn.encode_int(cv.glv_lambda)
    gts = np.stack([cv.g_table, cv.g_table_endo])
    return c, gts


def ecdsa_verify_fused(cv, e, r, s, qx, qy, interpret: bool = False):
    """Full ECDSA verify, one pallas call. Inputs lane-major [16, B]
    canonical; returns bool[B]. Requires the GLV curve (secp256k1)."""
    from . import ec as _ec

    assert cv.has_endo, "fused verify is the GLV (secp256k1) form"
    consts, gts = _secp_consts()
    B = e.shape[-1]
    blk = pallas_fp._pick_blk(B, BLK)
    inv_digits = fp.msb_digits(cv.fn.n_int - 2, 4)
    out = _verify_call(cv.fp, cv.fn, _ec.GLV_DIGITS, len(inv_digits), B,
                       blk, pallas_fp._auto_interpret(interpret))(
        jnp.asarray(inv_digits), jnp.asarray(consts), jnp.asarray(gts),
        e, r, s, qx, qy)
    return out[0].astype(bool)


# ---------------------------------------------------------------------------
# fused end-to-end recover (the txpool's per-transaction hot op)
# ---------------------------------------------------------------------------

def _recover_kernel_body(field_p, field_n, nsteps, sqrt_ref, invn_ref,
                         invp_ref, c_ref, gts_ref, e_ref, r_ref, s_ref,
                         v_ref, qx_ref, qy_ref, ok_ref, tbl_ref, opnd_ref):
    f = FieldCtx(field_p, c_ref[:, _C_P:_C_P + 1])
    fn = _MontCtx(field_n, c_ref[:, _C_N:_C_N + 1],
                  c_ref[:, _C_NPRIME:_C_NPRIME + 1],
                  c_ref[:, _C_ONEM:_C_ONEM + 1],
                  c_ref[:, _C_R2:_C_R2 + 1])
    e, r, s = e_ref[:, :], r_ref[:, :], s_ref[:, :]
    v = v_ref[0, :]
    nl = fn.limbs_col
    pl_ = f.limbs_col

    ok = ((~fp.is_zero(r)) & (~fp.is_zero(s))
          & (~fp.geq(r, jnp.broadcast_to(nl, r.shape)))
          & (~fp.geq(s, jnp.broadcast_to(nl, s.shape)))
          & (v < 4))

    # x = r + (v >> 1) * n, must stay below p
    hi_bit = ((v >> 1) & 1) == 1
    addend = fp.select(hi_bit, jnp.broadcast_to(nl, r.shape),
                       jnp.zeros_like(r))
    xr, carry = fp.add_limbs(r, addend)
    ok &= (carry == 0) & (~fp.geq(xr, jnp.broadcast_to(pl_, xr.shape)))
    xr = fp.select(ok, xr, jnp.zeros_like(xr))

    def reduce_p(a):
        d, brw = fp.sub_limbs(a, jnp.broadcast_to(pl_, a.shape))
        return fp.select(brw == 0, d, a)

    xm = reduce_p(xr)
    b_col = jnp.broadcast_to(c_ref[:, _C_B:_C_B + 1], xm.shape)
    ysq = f.add(f.mul(f.sqr(xm), xm), b_col)
    one_p = pallas_ec.field_one(f, xm.shape)
    y = pallas_fp.pow_digits_values(lambda a, b: f.mul(a, b), one_p, ysq,
                                    sqrt_ref, sqrt_ref.shape[0])
    ok &= fp.eq(f.sqr(y), ysq)
    flip = (y[0, :] & 1) != (v & 1)  # Solinas from_rep is identity
    ym = fp.select(flip, f.neg(y), y)

    rinv = fn.inv_tree(fn.to_rep(r), invn_ref, invn_ref.shape[0])
    u1 = fn.from_rep(fn.mul(fn.neg(fn.to_rep(e)), rinv))  # -e/r mod n
    u2 = fn.from_rep(fn.mul(fn.to_rep(s), rinv))  # s/r mod n

    acc = _glv_ladder(f, fn, c_ref, gts_ref, nsteps, u1, u2, xm, ym,
                      tbl_ref, opnd_ref)
    X, Y, Z = acc[0], acc[1], acc[2]
    ok &= ~fp.is_zero(Z)

    zinv = inv_tree_values(f, Z, invp_ref, invp_ref.shape[0])
    zi2 = f.sqr(zinv)
    qx = f.mul(X, zi2)  # Solinas from_rep is identity
    qy = f.mul(Y, f.mul(zi2, zinv))
    qx_ref[:, :] = fp.select(ok, qx, jnp.zeros_like(qx))
    qy_ref[:, :] = fp.select(ok, qy, jnp.zeros_like(qy))
    ok_ref[0, :] = ok.astype(U32)


@functools.lru_cache(maxsize=None)
def _recover_call(field_p, field_n, nsteps: int, B: int, blk: int,
                  interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(sqrt_ref, invn_ref, invp_ref, c_ref, gts_ref, e_ref,
               r_ref, s_ref, v_ref, qx_ref, qy_ref, ok_ref, tbl_ref, opnd_ref):
        _recover_kernel_body(field_p, field_n, nsteps, sqrt_ref, invn_ref,
                             invp_ref, c_ref[:, :], gts_ref[:, :, :],
                             e_ref, r_ref, s_ref, v_ref, qx_ref, qy_ref,
                             ok_ref, tbl_ref, opnd_ref)

    spec = pl.BlockSpec((NLIMBS, blk), lambda i: (0, i))
    lane = pl.BlockSpec((1, blk), lambda i: (0, i))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((NLIMBS, B), U32),
            jax.ShapeDtypeStruct((NLIMBS, B), U32),
            jax.ShapeDtypeStruct((1, B), U32),
        ),
        grid=(B // blk,),
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((NLIMBS, 13), lambda i: (0, 0)),
            pl.BlockSpec((2, TBL, 2 * NLIMBS), lambda i: (0, 0, 0)),
            spec, spec, spec, lane,
        ],
        out_specs=(spec, spec, lane),
        scratch_shapes=pallas_ec.ladder_scratch(2, blk),
        interpret=interpret,
    )


def ecdsa_recover_fused(cv, e, r, s, v, interpret: bool = False):
    """Full public-key recovery, one pallas call. e/r/s lane-major
    [16, B] canonical, v [B] uint32; returns (qx, qy, ok) lane-major."""
    from . import ec as _ec

    assert cv.has_endo, "fused recover is the GLV (secp256k1) form"
    consts, gts = _secp_consts()
    B = e.shape[-1]
    blk = pallas_fp._pick_blk(B, BLK)
    sqrt_digits = fp.msb_digits((cv.params.p + 1) // 4, 4)
    invn_digits = fp.msb_digits(cv.fn.n_int - 2, 4)
    invp_digits = fp.msb_digits(cv.fp.n_int - 2, 4)
    qx, qy, okv = _recover_call(cv.fp, cv.fn, _ec.GLV_DIGITS, B, blk,
                                pallas_fp._auto_interpret(interpret))(
        jnp.asarray(sqrt_digits), jnp.asarray(invn_digits),
        jnp.asarray(invp_digits), jnp.asarray(consts), jnp.asarray(gts),
        e, r, s, jnp.asarray(v, U32)[None, :])
    return qx, qy, okv[0].astype(bool)


# ---------------------------------------------------------------------------
# fused SM2 verify (GB/T 32918): R' = e + x(s*G + (r+s)*Q) == r
# ---------------------------------------------------------------------------

# SM2 consts block column layout ([16, 10])
_S_P, _S_PNP, _S_PONE, _S_PR2, _S_A, _S_B, _S_N, _S_NNP, _S_NR2, \
    _S_NONE = range(10)


def _sm2_verify_kernel_body(field_p, field_n, nsteps, c_ref, gts_ref,
                            e_ref, r_ref, s_ref, qx_ref, qy_ref, ok_ref,
                            tbl_ref, opnd_ref):
    f = _MontCtx(field_p, c_ref[:, _S_P:_S_P + 1],
                 c_ref[:, _S_PNP:_S_PNP + 1],
                 c_ref[:, _S_PONE:_S_PONE + 1],
                 c_ref[:, _S_PR2:_S_PR2 + 1])
    fn = _MontCtx(field_n, c_ref[:, _S_N:_S_N + 1],
                  c_ref[:, _S_NNP:_S_NNP + 1],
                  c_ref[:, _S_NONE:_S_NONE + 1],
                  c_ref[:, _S_NR2:_S_NR2 + 1])
    e, r, s = e_ref[:, :], r_ref[:, :], s_ref[:, :]
    qx, qy = qx_ref[:, :], qy_ref[:, :]
    nl = fn.limbs_col
    pl_ = f.limbs_col

    ok = ((~fp.is_zero(r)) & (~fp.is_zero(s))
          & (~fp.geq(r, jnp.broadcast_to(nl, r.shape)))
          & (~fp.geq(s, jnp.broadcast_to(nl, s.shape))))
    ok &= ((~fp.geq(qx, jnp.broadcast_to(pl_, qx.shape)))
           & (~fp.geq(qy, jnp.broadcast_to(pl_, qy.shape))))
    qxr, qyr = f.to_rep(qx), f.to_rep(qy)
    a_col = jnp.broadcast_to(c_ref[:, _S_A:_S_A + 1], qx.shape)
    b_col = jnp.broadcast_to(c_ref[:, _S_B:_S_B + 1], qx.shape)
    rhs = f.add(f.add(f.mul(f.sqr(qxr), qxr), f.mul(a_col, qxr)), b_col)
    ok &= fp.eq(f.sqr(qyr), rhs)
    ok &= ~(fp.is_zero(qx) & fp.is_zero(qy))

    rc = fn.reduce_loose(r)
    sc = fn.reduce_loose(s)
    t = fn.add(rc, sc)
    ok &= ~fp.is_zero(t)

    def digs(m):
        d = fp.window_digits(m, WINDOW)[..., :nsteps, :]
        return d[..., ::-1, :]

    digs_all = jnp.stack([digs(sc), digs(t)], axis=0)
    negs = jnp.zeros((2,) + sc.shape[-1:], U32)
    q_planes = jnp.stack([jnp.stack([qxr, qyr])], axis=0)
    # a plain FieldCtx: the point ops are jits and take the ctx as a pytree
    acc = pallas_ec.ladder_values(
        FieldCtx(field_p, f.limbs_col, f.nprime_col, f.one_col),
        (False, True), nsteps, 1, gts_ref[:, :, :], _dig_at(digs_all),
        negs, q_planes, tbl_ref, opnd_ref)
    X, _, Z = acc[0], acc[1], acc[2]
    ok &= ~fp.is_zero(Z)

    # x1 mod n == (r - e) mod n, inversion-free (ec._x_matches_mod_n)
    e_red = fn.reduce_loose(e)
    c = fn.sub(rc, e_red)
    zz = f.sqr(Z)
    m1 = fp.eq(X, f.mul(f.to_rep(c), zz))
    rpn, carry = fp.add_limbs(c, jnp.broadcast_to(nl, c.shape))
    lt_p = (carry == 0) & (~fp.geq(rpn, jnp.broadcast_to(pl_, rpn.shape)))
    cand2 = fp.select(lt_p, rpn, jnp.zeros_like(rpn))
    m2 = lt_p & fp.eq(X, f.mul(f.to_rep(cand2), zz))
    ok &= (m1 | m2)
    ok_ref[0, :] = ok.astype(U32)


@functools.lru_cache(maxsize=None)
def _sm2_verify_call(field_p, field_n, nsteps: int, B: int, blk: int,
                     interpret: bool):
    from jax.experimental import pallas as pl

    def kernel(c_ref, gts_ref, e_ref, r_ref, s_ref, qx_ref, qy_ref,
               ok_ref, tbl_ref, opnd_ref):
        _sm2_verify_kernel_body(field_p, field_n, nsteps, c_ref[:, :],
                                gts_ref[:, :, :], e_ref, r_ref, s_ref,
                                qx_ref, qy_ref, ok_ref, tbl_ref, opnd_ref)

    spec = pl.BlockSpec((NLIMBS, blk), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, B), U32),
        grid=(B // blk,),
        in_specs=[
            pl.BlockSpec((NLIMBS, 10), lambda i: (0, 0)),
            pl.BlockSpec((1, TBL, 2 * NLIMBS), lambda i: (0, 0, 0)),
            spec, spec, spec, spec, spec,
        ],
        out_specs=pl.BlockSpec((1, blk), lambda i: (0, i)),
        scratch_shapes=pallas_ec.ladder_scratch(1, blk),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=None)
def _sm2_consts():
    from . import ec as _ec

    cv = _ec.SM2P256V1
    c = np.zeros((NLIMBS, 10), np.uint32)
    c[:, _S_P] = cv.fp.limbs
    c[:, _S_PNP] = cv.fp.nprime
    c[:, _S_PONE] = cv.fp.one_m
    c[:, _S_PR2] = cv.fp.r2
    c[:, _S_A] = cv.a_rep
    c[:, _S_B] = cv.b_rep
    c[:, _S_N] = cv.fn.limbs
    c[:, _S_NNP] = cv.fn.nprime
    c[:, _S_NR2] = cv.fn.r2
    c[:, _S_NONE] = cv.fn.one_m
    return c, cv.g_table[None]


def sm2_verify_fused(cv, e, r, s, qx, qy, interpret: bool = False):
    """Full SM2 verify, one pallas call. Inputs lane-major [16, B]."""
    from . import ec as _ec

    assert cv is _ec.SM2P256V1, "consts block is the SM2 curve's"
    consts, gts = _sm2_consts()
    B = e.shape[-1]
    blk = pallas_fp._pick_blk(B, BLK)
    out = _sm2_verify_call(cv.fp, cv.fn, _ec.NDIGITS, B, blk,
                           pallas_fp._auto_interpret(interpret))(
        jnp.asarray(consts), jnp.asarray(gts), e, r, s, qx, qy)
    return out[0].astype(bool)
