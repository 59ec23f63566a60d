"""Fused double-scalar-mult ladders: the whole EC ladder in ONE Pallas call.

After ops.pallas_fp moved the field multiplies into fused kernels, the
remaining verify cost is the XLA-level glue of the windowed ladder: every
point add/double is ~15 non-mul vector ops plus ~10 pallas-mul launches,
executed 34-64 times per scan, each a round trip through HBM. This module
runs the ENTIRE ladder — window-table build, doublings, table selects,
conditional adds — inside one pallas_call with the accumulator and tables
VMEM-resident.

Design choices:
* **Jacobian window tables** (not batch-normalized affine): the in-kernel
  table build is then 14 point adds and needs NO field inversion; the
  ladder uses the complete-by-selection full `jac_add`. Op count is within
  ~10% of the affine variant while dropping the product-tree + Fermat
  machinery from the kernel.
* Value-level point ops mirror ops.ec's complete-by-selection exactly
  (doubling and infinity cases computed and selected), so adversarial
  inputs behave identically to the XLA path.
* One kernel shape serves both ladders: secp256k1's GLV form (2 constant
  G tables + 2 per-element Q tables, 34 steps) and the plain Shamir form
  (1 + 1, 64 steps, used by SM2).

Reference counterpart: the scalar-mult inner loops behind
wedpr_secp256k1_verify / recover (/root/reference/bcos-crypto/bcos-crypto/
signature/secp256k1/Secp256k1Crypto.cpp:57,85) — rebuilt as one fused
batch kernel instead of per-signature scalar code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import fp, pallas_fp
from .fp import NLIMBS

WINDOW = 4
TBL = 1 << WINDOW

U32 = jnp.uint32


# ---------------------------------------------------------------------------
# value-level field helpers (limbs_col passed explicitly; Mosaic-safe)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class FieldCtx:
    """A field bound to in-kernel constant columns.

    Wraps the host `fp._FieldBase` (for .terms / python ints) with traced
    [16, 1] modulus columns read from the kernel's const input. A pytree
    (columns are leaves, the host field is static) so the point ops below
    can be inner jits that take it as an argument.
    """

    def __init__(self, field: "fp._FieldBase", limbs_col, nprime_col=None,
                 one_col=None):
        self.field = field
        self.limbs_col = limbs_col
        self.nprime_col = nprime_col
        self.one_col = one_col  # Montgomery-domain 1 (Mont fields only)
        self.solinas = isinstance(field, fp.SolinasField)

    def tree_flatten(self):
        return (self.limbs_col, self.nprime_col, self.one_col), self.field

    @classmethod
    def tree_unflatten(cls, field, cols):
        return cls(field, *cols)

    def mul(self, a, b):
        if self.solinas:
            return pallas_fp.solinas_mul_body(self.field, a, b,
                                              self.limbs_col)
        return pallas_fp.mont_mul_body(self.field, a, b, self.limbs_col,
                                       self.nprime_col)

    def sqr(self, a):
        return self.mul(a, a)

    def add(self, a, b):
        s, c = fp.add_limbs(a, b)
        d, brw = fp.sub_limbs(s, self.limbs_col)
        return fp.select((c == 1) | (brw == 0), d, s)

    def sub(self, a, b):
        d, brw = fp.sub_limbs(a, b)
        d2, _ = fp.add_limbs(d, self.limbs_col + jnp.zeros_like(a))
        return fp.select(brw == 1, d2, d)

    def neg(self, a):
        d, _ = fp.sub_limbs(self.limbs_col + jnp.zeros_like(a), a)
        return fp.select(fp.is_zero(a), a, d)


# ---------------------------------------------------------------------------
# value-level Jacobian point ops (packed [3, 16, B]), mirroring ops.ec
# ---------------------------------------------------------------------------

def _pack(X, Y, Z):
    return jnp.stack([X, Y, Z], axis=0)


def _unpack(P):
    return P[0], P[1], P[2]

def _psel(cond, a, b):
    return jnp.where(cond[None, None, :], a, b)


# Inner jits, like the mul bodies in pallas_fp: traced once per shape, one
# equation per call site, inlined by the Mosaic lowering.

@functools.partial(jax.jit, static_argnames=("a_is_zero", "a_is_minus3"))
def vjac_double(f: FieldCtx, P, a_is_zero: bool, a_is_minus3: bool,
                a_col=None):
    X, Y, Z = _unpack(P)
    two_y = f.add(Y, Y)
    if a_is_zero:
        XX = f.mul(X, X)
        YY = f.mul(Y, Y)
        XYY = f.mul(X, YY)
        YYYY = f.mul(YY, YY)
        Z3 = f.mul(two_y, Z)
        M = f.add(f.add(XX, XX), XX)
    elif a_is_minus3:
        YY = f.mul(Y, Y)
        ZZ = f.mul(Z, Z)
        XYY = f.mul(X, YY)
        YYYY = f.mul(YY, YY)
        Z3 = f.mul(two_y, Z)
        T = f.mul(f.sub(X, ZZ), f.add(X, ZZ))
        M = f.add(f.add(T, T), T)
    else:
        XX = f.mul(X, X)
        YY = f.mul(Y, Y)
        ZZ = f.mul(Z, Z)
        XYY = f.mul(X, YY)
        YYYY = f.mul(YY, YY)
        Z3 = f.mul(two_y, Z)
        aZ4 = f.mul(jnp.broadcast_to(a_col, X.shape), f.mul(ZZ, ZZ))
        M = f.add(f.add(f.add(XX, XX), XX), aZ4)
    S = f.add(XYY, XYY)
    S = f.add(S, S)
    MM = f.mul(M, M)
    X3 = f.sub(MM, f.add(S, S))
    y8 = f.add(YYYY, YYYY)
    y8 = f.add(y8, y8)
    y8 = f.add(y8, y8)
    Y3 = f.sub(f.mul(M, f.sub(S, X3)), y8)
    return _pack(X3, Y3, Z3)


@functools.partial(jax.jit, static_argnames=("a_is_zero", "a_is_minus3"))
def vjac_add(f: FieldCtx, P, Q, a_is_zero: bool, a_is_minus3: bool,
             a_col=None):
    """P + Q, both Jacobian, complete by selection (mirrors ec.jac_add)."""
    X1, Y1, Z1 = _unpack(P)
    X2, Y2, Z2 = _unpack(Q)
    p_inf = fp.is_zero(Z1)
    q_inf = fp.is_zero(Z2)
    Z1Z1 = f.mul(Z1, Z1)
    Z2Z2 = f.mul(Z2, Z2)
    U1 = f.mul(X1, Z2Z2)
    U2 = f.mul(X2, Z1Z1)
    S1 = f.mul(f.mul(Y1, Z2), Z2Z2)
    S2 = f.mul(f.mul(Y2, Z1), Z1Z1)
    H = f.sub(U2, U1)
    R = f.sub(S2, S1)
    h0 = fp.is_zero(H)
    r0 = fp.is_zero(R)
    HH = f.mul(H, H)
    RR = f.mul(R, R)
    HHH = f.mul(H, HH)
    V = f.mul(U1, HH)
    X3 = f.sub(f.sub(RR, HHH), f.add(V, V))
    Y3 = f.sub(f.mul(R, f.sub(V, X3)), f.mul(S1, HHH))
    Z3 = f.mul(f.mul(Z1, Z2), H)
    res = _pack(X3, Y3, Z3)
    dbl = vjac_double(f, P, a_is_zero, a_is_minus3, a_col)
    res = _psel(h0 & r0, dbl, res)
    res = _psel(h0 & ~r0, jnp.zeros_like(res), res)
    res = _psel(q_inf, P, res)
    res = _psel(p_inf, Q, res)
    return res


def _take_const_table(gt, dig):
    """Constant G table [TBL, 2*NLIMBS] x digit [B] -> (x, y) [16, B]
    one-hot select (no tensordot: integer dots have no Mosaic path)."""
    out = None
    for k in range(TBL):
        oh = (dig == U32(k)).astype(U32)[None, :]  # [1, B]
        term = gt[k][:, None] * oh  # [2L, B]
        out = term if out is None else out + term
    return out[:NLIMBS], out[NLIMBS:]


def _take_jac_table(tq, dig):
    """Per-element table [TBL, 3, 16, B] (value or ref) x digit [B] ->
    [3, 16, B]."""
    out = None
    for k in range(TBL):
        oh = (dig == U32(k)).astype(U32)[None, None, :]
        term = tq[k] * oh
        out = term if out is None else out + term
    return out


# ---------------------------------------------------------------------------
# the fused ladder kernel
# ---------------------------------------------------------------------------

def field_one(f: FieldCtx, shape):
    """Field-rep 1 of the given [16, B] shape: plain 1 for Solinas (iota
    mask — .at[].set is a scatter Mosaic rejects), Montgomery R mod n
    (the ctx's one_col) otherwise."""
    if f.solinas:
        return (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                == 0).astype(U32)
    return jnp.broadcast_to(f.one_col, shape)


def ladder_values(f: FieldCtx, curve_flags, nsteps, n_pairs,
                  gts, dig_at, negs, q_planes, tbl_ref, opnd_ref):
    """The ladder on VALUES (callable from any kernel).

    n_pairs: 1 (plain Shamir: G+Q) or 2 (GLV: G, phiG, Q, phiQ).
    gts:  [n_pairs, TBL, 2*NLIMBS] constant affine G tables
    dig_at: (row, step) -> [B] MSB-first window digit of that step; rows
          INTERLEAVED per pair: [g, q] (n_pairs=1) or
          [g, q, g_endo, q_endo] (n_pairs=2) — pair p reads rows
          2p (constant-table plane) and 2p+1 (per-element plane). A
          callable because the step index is dynamic: a kernel reads it
          from a ref (Mosaic has no dynamic_slice on values)
    negs: [2*n_pairs, B] sign flags (uint32 0/1), same row order
    q_planes: [n_pairs, 2, 16, B] affine Q (and beta*Q) in field rep
    tbl_ref, opnd_ref: the VMEM scratch of `ladder_scratch` — per-element
          Jacobian window tables [n_pairs, TBL, 3, 16, B] and one step's
          addends [2*n_pairs, 3, 16, B]
    -> packed Jacobian accumulator [3, 16, B].

    Every repeated point op runs as a fori_loop over a scratch ref, so the
    kernel holds ONE add body for the tables, one double and one add body
    per step: Mosaic's compile time follows the inlined multiply count
    (~0.2 s each), and unrolled this kernel held 176 of them.
    """
    a_is_zero, a_is_minus3 = curve_flags
    B = q_planes.shape[-1]
    one_col = field_one(f, (NLIMBS, B))

    def add(P, Q):
        return vjac_add(f, P, Q, a_is_zero, a_is_minus3)

    # per-element Jacobian window tables tbl[p, k] = k*Q_p
    for p in range(n_pairs):
        q1 = _pack(q_planes[p, 0], q_planes[p, 1], one_col)
        tbl_ref[p, 0] = jnp.zeros_like(q1)
        tbl_ref[p, 1] = q1

    def build(i, carry):
        p, k = i // (TBL - 2), i % (TBL - 2) + 2
        tbl_ref[p, k] = add(tbl_ref[p, k - 1], tbl_ref[p, 1])
        return carry

    jax.lax.fori_loop(0, n_pairs * (TBL - 2), build, 0)

    def neg_y(P, flag):
        X, Y, Z = _unpack(P)
        return _pack(X, fp.select(flag == 1, f.neg(Y), Y), Z)

    def step(r, acc):
        acc = jax.lax.fori_loop(
            0, WINDOW,
            lambda _, a: vjac_double(f, a, a_is_zero, a_is_minus3), acc)
        for p in range(n_pairs):
            # constant G-plane addend (affine entry, lifted to Jacobian)
            dg = dig_at(2 * p, r)
            gx, gy = _take_const_table(gts[p], dg)
            gy = fp.select(negs[2 * p] == 1, f.neg(gy), gy)
            lift = _pack(gx, gy, one_col)
            opnd_ref[2 * p] = _psel(dg == 0, jnp.zeros_like(lift),
                                    lift)  # skip -> infinity
            # per-element Q-plane addend
            qe = _take_jac_table(tbl_ref.at[p], dig_at(2 * p + 1, r))
            opnd_ref[2 * p + 1] = neg_y(qe, negs[2 * p + 1])
        return jax.lax.fori_loop(0, 2 * n_pairs,
                                 lambda i, a: add(a, opnd_ref[i]), acc)

    init = jnp.zeros((3, NLIMBS, B), U32)
    return jax.lax.fori_loop(0, nsteps, step, init)


def ladder_scratch(n_pairs: int, blk: int) -> list:
    """scratch_shapes for the (tbl_ref, opnd_ref) pair of `ladder_values`."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((n_pairs, TBL, 3, NLIMBS, blk), U32),
            pltpu.VMEM((2 * n_pairs, 3, NLIMBS, blk), U32)]


@functools.lru_cache(maxsize=None)
def _ladder_call(field: "fp._FieldBase", a_is_zero: bool, a_is_minus3: bool,
                 nsteps: int, n_pairs: int, B: int, blk: int,
                 interpret: bool):
    from jax.experimental import pallas as pl

    solinas = isinstance(field, fp.SolinasField)

    def kernel(c_ref, gts_ref, digs_ref, negs_ref, q_ref, o_ref, tbl_ref,
               opnd_ref):
        f = FieldCtx(field, c_ref[:, 0:1],
                     None if solinas else c_ref[:, 1:2],
                     None if solinas else c_ref[:, 2:3])
        o_ref[:, :, :] = ladder_values(
            f, (a_is_zero, a_is_minus3), nsteps, n_pairs, gts_ref[:, :, :],
            lambda row, r: digs_ref[r][row], negs_ref[:, :],
            q_ref[:, :, :, :], tbl_ref, opnd_ref)

    ncols = 3 if not isinstance(field, fp.SolinasField) else 2
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((3, NLIMBS, B), U32),
        grid=(B // blk,),
        in_specs=[
            pl.BlockSpec((NLIMBS, ncols), lambda i: (0, 0)),
            pl.BlockSpec((n_pairs, TBL, 2 * NLIMBS), lambda i: (0, 0, 0)),
            pl.BlockSpec((nsteps, 2 * n_pairs, blk), lambda i: (0, 0, i)),
            pl.BlockSpec((2 * n_pairs, blk), lambda i: (0, i)),
            pl.BlockSpec((n_pairs, 2, NLIMBS, blk), lambda i: (0, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec((3, NLIMBS, blk), lambda i: (0, 0, i)),
        scratch_shapes=ladder_scratch(n_pairs, blk),
        interpret=interpret,
    )


# block size: tables are the VMEM hog — n_pairs * TBL * 3 * 16 * blk * 4 B
# (GLV: 2 * 16 * 3 * 16 * 256 * 4 = 1.5 MB at blk=256) plus temporaries.
LADDER_BLK = 256


def ladder(field, a_is_zero, a_is_minus3, nsteps, gts, digs, negs, q_planes,
           interpret: bool = False):
    """Run the fused ladder. digs: [nsteps, 2*n_pairs, B] MSB-first window
    digits (step-major, so the kernel reads a step with one dynamic index
    on the leading axis); other shapes as in `ladder_values`. Returns the
    packed Jacobian accumulator [3, 16, B]."""
    n_pairs = gts.shape[0]
    B = q_planes.shape[-1]
    blk = pallas_fp._pick_blk(B, LADDER_BLK)
    if isinstance(field, fp.SolinasField):
        consts = pallas_fp.field_consts(field)
    else:
        consts = np.zeros((NLIMBS, 3), np.uint32)
        consts[:, :2] = pallas_fp.field_consts(field)
        consts[:, 2] = field.one_m  # Montgomery-domain 1 for affine lifts
    return _ladder_call(field, a_is_zero, a_is_minus3, nsteps, n_pairs, B,
                        blk, pallas_fp._auto_interpret(interpret))(
        jnp.asarray(consts), gts, digs, negs, q_planes)
