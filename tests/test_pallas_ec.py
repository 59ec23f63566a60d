"""Fused-ladder building blocks: bit-parity with the XLA point ops.

The value-level Jacobian ops used inside the fused ladder kernel must
match ops.ec's complete-by-selection ops exactly — same field, same
selection semantics. Full-ladder parity against the native host path is
asserted on the chip by chip_smoke.py's kernel stage (every bucket, with
tampered rows); here CI pins the per-op contracts cheaply.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from fisco_bcos_tpu.crypto import refimpl
from fisco_bcos_tpu.ops import ec, fp, pallas_ec, pallas_fp

B = 128
CV = ec.SECP256K1
F = CV.fp


@pytest.fixture(scope="module")
def points():
    rng = np.random.default_rng(21)
    pts = [refimpl.ec_mul(refimpl.SECP256K1,
                          int.from_bytes(rng.bytes(32), "big")
                          % refimpl.SECP256K1.n,
                          (refimpl.SECP256K1.gx, refimpl.SECP256K1.gy))
           for _ in range(8)]
    xs = np.stack([fp.to_limbs(pts[i % 8][0]) for i in range(B)], axis=1)
    ys = np.stack([fp.to_limbs(pts[i % 8][1]) for i in range(B)], axis=1)
    xr, yr = np.asarray(F.to_rep(xs)), np.asarray(F.to_rep(ys))
    one = np.asarray(F.one_rep(xr.shape))
    return np.stack([xr, yr, one])


def _run(body, *arrays):
    consts = pallas_fp.field_consts(F)

    def kernel(c_ref, *refs):
        fc = pallas_ec.FieldCtx(F, c_ref[:, 0:1])
        out_ref = refs[-1]
        ins = [r[:, :, :] for r in refs[:-1]]
        out_ref[:, :, :] = body(fc, *ins)

    return np.asarray(pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((3, 16, B), jnp.uint32),
        interpret=True)(consts, *arrays))


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
def test_vjac_double_matches(points):
    got = _run(lambda fc, p: pallas_ec.vjac_double(fc, p, True, False),
               points)
    want = np.asarray(ec.jac_double(CV, jnp.asarray(points)))
    assert (got == want).all()


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
def test_vjac_add_doubling_case(points):
    got = _run(lambda fc, p, q: pallas_ec.vjac_add(fc, p, q, True, False),
               points, points.copy())
    want = np.asarray(ec.jac_add(CV, jnp.asarray(points),
                                 jnp.asarray(points)))
    assert (got == want).all()


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
def test_vjac_add_generic_and_infinity(points):
    q2 = np.asarray(ec.jac_double(CV, jnp.asarray(points)))
    got = _run(lambda fc, p, q: pallas_ec.vjac_add(fc, p, q, True, False),
               points, q2)
    want = np.asarray(ec.jac_add(CV, jnp.asarray(points), jnp.asarray(q2)))
    assert (got == want).all()

    inf = np.zeros_like(points)
    got = _run(lambda fc, p, q: pallas_ec.vjac_add(fc, p, q, True, False),
               points, inf)
    assert (got == points).all()  # P + inf = P


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
def test_sm2_point_ops_match():
    """The a = -3 branch of vjac_double/vjac_add (SM2, Montgomery base
    field) against the XLA ops — the secp tests only cover a = 0."""
    cv = ec.SM2P256V1
    f = cv.fp
    rng = np.random.default_rng(29)
    pts = [refimpl.ec_mul(refimpl.SM2P256V1,
                          int.from_bytes(rng.bytes(32), "big")
                          % refimpl.SM2P256V1.n,
                          (refimpl.SM2P256V1.gx, refimpl.SM2P256V1.gy))
           for _ in range(4)]
    xs = np.stack([fp.to_limbs(pts[i % 4][0]) for i in range(B)], axis=1)
    ys = np.stack([fp.to_limbs(pts[i % 4][1]) for i in range(B)], axis=1)
    xr, yr = np.asarray(f.to_rep(xs)), np.asarray(f.to_rep(ys))
    P = np.stack([xr, yr, np.asarray(f.one_rep(xr.shape))])
    consts = pallas_fp.field_consts(f)
    one_m = np.zeros((16, 1), np.uint32)
    one_m[:, 0] = f.one_m

    def kernel(c_ref, one_ref, p_ref, q_ref, o_ref):
        fc = pallas_ec.FieldCtx(f, c_ref[:, 0:1], c_ref[:, 1:2],
                                one_ref[:, 0:1])
        o_ref[:, :, :] = pallas_ec.vjac_add(
            fc, p_ref[:, :, :], q_ref[:, :, :], False, True)

    q2 = np.asarray(ec.jac_double(cv, jnp.asarray(P)))
    got = np.asarray(pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((3, 16, B), jnp.uint32),
        interpret=True)(consts, one_m, P, q2))
    want = np.asarray(ec.jac_add(cv, jnp.asarray(P), jnp.asarray(q2)))
    assert (got == want).all()


def test_take_tables_match(points):
    rng = np.random.default_rng(3)
    dig = rng.integers(0, 16, (B,), dtype=np.uint32)
    gx, gy = pallas_ec._take_const_table(jnp.asarray(CV.g_table),
                                         jnp.asarray(dig))
    wx, wy = ec._take_const(CV.g_table, jnp.asarray(dig))
    assert (np.asarray(gx) == np.asarray(wx)).all()
    assert (np.asarray(gy) == np.asarray(wy)).all()
