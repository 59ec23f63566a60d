"""Pallas-fused 256-bit field multiplies for the EC kernels.

Why this exists: the XLA path in `ops.fp` computes a 256x256-bit product as
one [16, 16, B] outer product reduced along anti-diagonals with a
pad-and-reshape shear (`fp._diag_sum`). That shape is compile-friendly but
runtime-hostile on TPU — the reshapes force vreg relayouts and the [16,16,B]
intermediate (67 MB at B=64k) round-trips HBM several times per multiply.
Measured on a TPU v5 lite, one batched field multiply costs ~2.6 ms at
B=64k where the pure-compute floor is ~20-70 us.

Here the product is an unrolled row-accumulation entirely inside one Pallas
kernel: 16 broadcast multiplies of exact 16-bit limbs accumulated into 34
redundant columns held in VMEM/vregs, then the modular reduction (Solinas
fold or Montgomery REDC) and the carry collapse, all fused — one HBM read
per operand, one write for the result, no reshapes, no [16,16,B] tensor.

The column-accumulation bodies (`solinas_mul_body` / `mont_mul_body`) are
pure jnp-on-values code, so larger fused kernels (Jacobian point ops, the
full ladder step) can inline them; `pl.pallas_call` wrappers here cover the
standalone-multiply case behind `fp`'s dispatch flag.

Reference counterpart: same role as ops.fp (the WeDPR/OpenSSL bignum layer
behind /root/reference/bcos-crypto/bcos-crypto/signature/secp256k1/
Secp256k1Crypto.cpp) — this is the TPU-native hot path for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import fp, platform
from .fp import LIMB_BITS, MASK, NLIMBS

# lanes per kernel instance: multiple of 128 (TPU lane width); 512 keeps the
# [34, BLK] column buffer + operands comfortably in VMEM while giving the
# VPU long vectors.
BLK = 512


def _accum_product_cols(a, b):
    """Exact redundant columns of a*b: [16, B] x [16, B] -> [32, B].

    cols[k] = sum_{i+j=k} lo16(a_i*b_j) + sum_{i+j=k-1} hi16(a_i*b_j),
    accumulated with static slice-adds (no reshapes, no [16,16,B] tensor).
    Every column < 16*2^16 + 16*2^16 = 2^21: safe in uint32. Matches
    fp.mul_wide's contract bit-for-bit.
    """
    cols = None
    for i in range(NLIMBS):
        t = a[i : i + 1, :] * b  # [16, B], exact: 16-bit x 16-bit
        # shift-by-pad, not .at[].add: scatter-add has no Mosaic lowering
        contrib = (fp._pad(t & MASK, i, NLIMBS - i)
                   + fp._pad(t >> LIMB_BITS, i + 1, NLIMBS - 1 - i))
        cols = contrib if cols is None else cols + contrib
    return cols


def _accum_product_low_cols(a, b):
    """Low 16 redundant columns of a*b (mod 2^256) — the Montgomery
    half-product. Computed as the full product sliced to 16 columns: the
    ragged-triangle form trips the pallas tracer (varying-shape slice
    updates capture an empty index constant), and the wasted high partials
    are fused multiply-adds the VPU shrugs off."""
    return _accum_product_cols(a, b)[:NLIMBS]


def field_consts(field: "fp._FieldBase") -> np.ndarray:
    """Per-field constant block passed as a kernel INPUT (pallas kernels
    cannot close over array constants): lane-major [NLIMBS, 2] with column
    0 = modulus limbs, column 1 = n' (Montgomery) or zeros (Solinas)."""
    c = np.zeros((NLIMBS, 2), np.uint32)
    c[:, 0] = field.limbs
    if isinstance(field, fp.MontField):
        c[:, 1] = field.nprime
    return c


# The two mul bodies are inner jits: a kernel calls them hundreds of times
# (an EC ladder step is ~130 multiplies of ~400 primitive ops each), and as
# plain functions every call site re-traced the whole body — two minutes of
# Python per (op, bucket) before Mosaic saw anything. As jits each body is
# traced once per operand shape and every call site is one equation; the
# Mosaic lowering inlines them.

@functools.partial(jax.jit, static_argnums=0)
def solinas_mul_body(field: "fp.SolinasField", a, b, limbs_col):
    """a*b mod p for p = 2^256 - c, on jnp values (pallas-inlinable).

    Mirrors fp.SolinasField.mul's three-fold structure, with the product
    columns from `_accum_product_cols` instead of the outer-product shear.
    `limbs_col`: the modulus as a broadcastable [NLIMBS, 1] value.
    """
    cols = _accum_product_cols(a, b)
    low, high = cols[:NLIMBS], cols[NLIMBS:]
    # fold 1: L + H*c. coef < 2^11, H col < 2^21 -> contrib < 2^32.
    t = fp._pad(low, 0, 2)
    for coef, sh in field.terms:
        t = t + fp._pad(high * np.uint32(coef), sh, 2 - sh)
    t_limbs, topc = fp.carry_prop(t, NLIMBS + 2)
    # fold 2: top 2 limbs + sweep carry (3 exact limbs)
    top = jnp.concatenate([t_limbs[..., NLIMBS:, :], topc[..., None, :]],
                          axis=-2)
    r_cols = field._fold_into(t_limbs[..., :NLIMBS, :], top, 3)
    r_limbs, o = fp.carry_prop(r_cols, NLIMBS)
    # fold 3: o in {0,1}
    r2_cols = field._fold_into(r_limbs, o[..., None, :], 1)
    r2_limbs, _ = fp.carry_prop(r2_cols, NLIMBS)
    # reduce_loose inlined against the passed-in modulus column
    d, brw = fp.sub_limbs(r2_limbs, limbs_col)
    return fp.select(brw == 0, d, r2_limbs)


@functools.partial(jax.jit, static_argnums=0)
def mont_mul_body(field: "fp.MontField", a, b, limbs_col, nprime_col):
    """REDC(a*b) on jnp values (pallas-inlinable); mirrors MontField.mul.
    `limbs_col`/`nprime_col`: broadcastable [NLIMBS, 1] constant inputs."""
    z, _ = fp.carry_prop(_accum_product_cols(a, b), 2 * NLIMBS)
    m_cols = _accum_product_low_cols(z[..., :NLIMBS, :],
                                     jnp.broadcast_to(nprime_col, a.shape))
    m, _ = fp.carry_prop(m_cols, NLIMBS)
    s_cols = _accum_product_cols(m, jnp.broadcast_to(limbs_col,
                                                     a.shape)) + z
    s, o = fp.carry_prop(s_cols, 2 * NLIMBS)
    hi = s[..., NLIMBS:, :]
    d, brw = fp.sub_limbs(hi, limbs_col + jnp.zeros_like(a))
    return fp.select((o == 1) | (brw == 0), d, hi)


# ---------------------------------------------------------------------------
# pallas_call wrappers (standalone multiplies)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _mul_call(field: "fp._FieldBase", B: int, blk: int, interpret: bool):
    from jax.experimental import pallas as pl

    solinas = isinstance(field, fp.SolinasField)

    def kernel(c_ref, a_ref, b_ref, o_ref):
        a, b = a_ref[:, :], b_ref[:, :]
        limbs_col = c_ref[:, 0:1]
        if solinas:
            o_ref[:, :] = solinas_mul_body(field, a, b, limbs_col)
        else:
            o_ref[:, :] = mont_mul_body(field, a, b, limbs_col,
                                        c_ref[:, 1:2])

    grid = B // blk
    spec = pl.BlockSpec((NLIMBS, blk), lambda i: (0, i))
    cspec = pl.BlockSpec((NLIMBS, 2), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((NLIMBS, B), jnp.uint32),
        grid=(grid,),
        in_specs=[cspec, spec, spec],
        out_specs=spec,
        interpret=interpret,
    )


def _pick_blk(B: int, cap: int = BLK) -> int:
    """Largest 128-multiple block size <= cap that DIVIDES B — a grid of
    B//blk full blocks covers every lane (a floor-divided grid would
    silently drop the tail: B=640 with blk=512 left lanes 512-639
    uncomputed). NOTE: the result is only guaranteed to be a multiple of
    128, NOT a power of two (B=384 under cap 512 returns 384) — callers
    that need power-of-two widths (the product-tree inversion's halving
    splits, pallas_verify.inv_tree_values) must enforce that themselves;
    inv_tree_values asserts it. Shared by every pallas module; raises for
    batches that are not lane-aligned (callers gate on B % 128 == 0)."""
    blk = min(cap, B)
    while blk > 128 and B % blk:
        blk //= 2
    if blk < 128 or B % blk:
        raise ValueError(f"B={B} is not a 128-lane multiple (cap {cap})")
    return blk


def pallas_ok(shape) -> bool:
    """Standalone-kernel eligibility: 2-D lane-major [16, B] with B a
    multiple of 128 (partial blocks would need masking)."""
    return (len(shape) == 2 and shape[0] == NLIMBS
            and shape[1] % 128 == 0 and shape[1] > 0)


def _auto_interpret(interpret: bool) -> bool:
    """Mosaic compiles the kernel on TPU or the call raises; anywhere else
    (CPU tests calling a kernel directly) the pallas interpreter runs it."""
    return interpret or not platform.on_tpu()


def mul(field: "fp._FieldBase", a, b, interpret: bool = False):
    """Fused modular multiply; caller guarantees `pallas_ok(a.shape)`."""
    B = a.shape[-1]
    blk = _pick_blk(B)
    return _mul_call(field, B, blk, _auto_interpret(interpret))(
        jnp.asarray(field_consts(field)), a, b)


@functools.lru_cache(maxsize=None)
def _mul_const_call(field: "fp._FieldBase", B: int, blk: int,
                    interpret: bool):
    """Variant with a [16, 1] second operand (to_rep/from_rep constants):
    the column rides in every block's spec instead of being broadcast to a
    full HBM-sized [16, B] input."""
    from jax.experimental import pallas as pl

    solinas = isinstance(field, fp.SolinasField)

    def kernel(c_ref, a_ref, b_ref, o_ref):
        a = a_ref[:, :]
        b = jnp.broadcast_to(b_ref[:, :], a.shape)
        limbs_col = c_ref[:, 0:1]
        if solinas:
            o_ref[:, :] = solinas_mul_body(field, a, b, limbs_col)
        else:
            o_ref[:, :] = mont_mul_body(field, a, b, limbs_col,
                                        c_ref[:, 1:2])

    spec = pl.BlockSpec((NLIMBS, blk), lambda i: (0, i))
    one = pl.BlockSpec((NLIMBS, 1), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((NLIMBS, B), jnp.uint32),
        grid=(B // blk,),
        in_specs=[pl.BlockSpec((NLIMBS, 2), lambda i: (0, 0)), spec, one],
        out_specs=spec,
        interpret=interpret,
    )


def mul_const(field: "fp._FieldBase", a, b_col, interpret: bool = False):
    """a [16, B] times a single column b_col [16, 1]."""
    B = a.shape[-1]
    blk = _pick_blk(B)
    return _mul_const_call(field, B, blk, _auto_interpret(interpret))(
        jnp.asarray(field_consts(field)), a, b_col)


@functools.lru_cache(maxsize=None)
def _mul_call_stacked(field: "fp._FieldBase", K: int, B: int, blk: int,
                      interpret: bool):
    """Stacked variant for [K, 16, B] operands (the `_mulk` pattern):
    grid over (K, B/blk), each instance multiplying one [16, blk] pair."""
    from jax.experimental import pallas as pl

    solinas = isinstance(field, fp.SolinasField)

    def kernel(c_ref, a_ref, b_ref, o_ref):
        a, b = a_ref[0], b_ref[0]
        limbs_col = c_ref[:, 0:1]
        if solinas:
            o_ref[0] = solinas_mul_body(field, a, b, limbs_col)
        else:
            o_ref[0] = mont_mul_body(field, a, b, limbs_col, c_ref[:, 1:2])

    spec = pl.BlockSpec((1, NLIMBS, blk), lambda k, i: (k, 0, i))
    cspec = pl.BlockSpec((NLIMBS, 2), lambda k, i: (0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((K, NLIMBS, B), jnp.uint32),
        grid=(K, B // blk),
        in_specs=[cspec, spec, spec],
        out_specs=spec,
        interpret=interpret,
    )


def mul_stacked(field: "fp._FieldBase", a, b, interpret: bool = False):
    """[K, 16, B] fused multiply (one grid step per stacked pair)."""
    K, B = a.shape[0], a.shape[-1]
    blk = _pick_blk(B)
    return _mul_call_stacked(field, K, B, blk, _auto_interpret(interpret))(
        jnp.asarray(field_consts(field)), a, b)


# ---------------------------------------------------------------------------
# fused fixed-exponent power (recover's sqrt, Fermat inversions)
# ---------------------------------------------------------------------------

def pow_digits_values(mul, one, a, digs_ref, nd: int, W: int = 4):
    """Windowed a^e on VALUES, exponent as `nd` MSB-first W-bit digits in
    an SMEM ref (callable from any kernel): window table built with
    2^W - 2 multiplies, then fori over the digits. The table entry for a
    digit is picked by a select chain on the scalar — Mosaic has no
    dynamic_slice on values."""
    entries = [one, a]
    for _ in range((1 << W) - 2):
        entries.append(mul(entries[-1], a))

    def pick(d):
        out = entries[0]
        for k in range(1, len(entries)):
            out = jnp.where(d == k, entries[k], out)
        return out

    def body(i, acc):
        for _ in range(W):
            acc = mul(acc, acc)
        return mul(acc, pick(digs_ref[i]))

    return jax.lax.fori_loop(1, nd, body, pick(digs_ref[0]))


@functools.lru_cache(maxsize=None)
def _pow_call(field: "fp._FieldBase", nd: int, B: int, blk: int,
              interpret: bool):
    """a^e with e delivered as `nd` 4-bit SMEM digits (MSB-first).

    The XLA pow_const is a 64-step scan of ~5 multiplies — ~320 per-op
    dispatches per call on this backend. Here: window table (16 entries)
    built in-kernel, then one fori_loop; a single pallas call.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    solinas = isinstance(field, fp.SolinasField)

    def kernel(digs_ref, c_ref, a_ref, o_ref):
        a = a_ref[:, :]
        limbs_col = c_ref[:, 0:1]
        if solinas:
            mul = lambda x, y: solinas_mul_body(field, x, y, limbs_col)
            one = (jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
                   == 0).astype(jnp.uint32)
        else:
            npc = c_ref[:, 1:2]
            mul = lambda x, y: mont_mul_body(field, x, y, limbs_col, npc)
            one = jnp.broadcast_to(c_ref[:, 2:3], a.shape)
        o_ref[:, :] = pow_digits_values(mul, one, a, digs_ref, nd)

    ncols = 2 if solinas else 3
    spec = pl.BlockSpec((NLIMBS, blk), lambda i: (0, i))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((NLIMBS, B), jnp.uint32),
        grid=(B // blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((NLIMBS, ncols), lambda i: (0, 0)),
            spec,
        ],
        out_specs=spec,
        interpret=interpret,
    )


def pow_const(field: "fp._FieldBase", a, e: int, interpret: bool = False):
    """Fused a^e (internal domain) for e > 0; caller gates `pallas_ok`."""
    digits = fp.msb_digits(e, 4)  # kernel window W = 4
    nd = len(digits)
    B = a.shape[-1]
    blk = _pick_blk(B)
    if isinstance(field, fp.SolinasField):
        consts = field_consts(field)
    else:
        consts = np.zeros((NLIMBS, 3), np.uint32)
        consts[:, :2] = field_consts(field)
        consts[:, 2] = field.one_m
    return _pow_call(field, nd, B, blk, _auto_interpret(interpret))(
        jnp.asarray(digits), jnp.asarray(consts), a)
