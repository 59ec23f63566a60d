"""Fused end-to-end verify kernel: constant plumbing + piece parity.

The full-verify interpret run is hours on one core, so CI pins what it
can cheaply: the consts-block column layout against the Curve's host
constants (a column mixup is the likeliest silent-wrong-result bug), and
the dispatch gating. The in-kernel pieces (inv_tree, _glv_split_values)
have interpret-mode parity tests gated behind FBTPU_SLOW_TESTS. The
composition has never run on a TPU and does not lower as written
(ROADMAP queue 3 item 6); it stays behind its flag.
"""

import os

import numpy as np
import pytest

from fisco_bcos_tpu.crypto import refimpl
from fisco_bcos_tpu.ops import ec, fp, pallas_verify


def test_consts_block_layout():
    cv = ec.SECP256K1
    c, gts = pallas_verify._secp_consts()
    assert (c[:, pallas_verify._C_P] == cv.fp.limbs).all()
    assert (c[:, pallas_verify._C_B] == cv.b_rep).all()
    assert (c[:, pallas_verify._C_BETA] == cv.beta_rep).all()
    assert (c[:, pallas_verify._C_N] == cv.fn.limbs).all()
    assert (c[:, pallas_verify._C_NPRIME] == cv.fn.nprime).all()
    assert (c[:, pallas_verify._C_R2] == cv.fn.r2).all()
    assert (c[:, pallas_verify._C_ONEM] == cv.fn.one_m).all()
    assert (c[:, pallas_verify._C_HALF] == cv.half_n_limbs).all()
    assert (c[:, pallas_verify._C_G1] == cv.g1_limbs).all()
    assert (c[:, pallas_verify._C_G2] == cv.g2_limbs).all()
    assert (c[:, pallas_verify._C_MB1]
            == cv.fn.encode_int(cv.mb1_int)).all()
    assert (c[:, pallas_verify._C_MB2]
            == cv.fn.encode_int(cv.mb2_int)).all()
    assert (c[:, pallas_verify._C_LAM]
            == cv.fn.encode_int(cv.glv_lambda)).all()
    assert gts.shape == (2, 16, 32)
    assert (gts[0] == cv.g_table).all()
    assert (gts[1] == cv.g_table_endo).all()


def test_fused_verify_gated_off_by_default(monkeypatch):
    monkeypatch.delenv("FBTPU_FUSED_VERIFY", raising=False)
    ec._FUSED_VERIFY_CACHE.clear()
    try:
        assert ec._use_fused_verify() is False
    finally:
        ec._FUSED_VERIFY_CACHE.clear()


def _mont_ctx(c_ref):
    V = pallas_verify
    return V._MontCtx(
        ec.SECP256K1.fn,
        c_ref[:, V._C_N:V._C_N + 1],
        c_ref[:, V._C_NPRIME:V._C_NPRIME + 1],
        c_ref[:, V._C_ONEM:V._C_ONEM + 1],
        c_ref[:, V._C_R2:V._C_R2 + 1])


@pytest.mark.skipif("FBTPU_SLOW_TESTS" not in os.environ,
                    reason="interpret-mode kernel pieces take minutes; "
                           "run with FBTPU_SLOW_TESTS=1")
def test_inv_tree_parity():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cv = ec.SECP256K1
    rng = np.random.default_rng(41)
    B = 8
    vals = ([int.from_bytes(rng.bytes(32), "big") % cv.fn.n_int
             for _ in range(B - 1)] + [0])
    arr = np.stack([fp.to_limbs(v) for v in vals], axis=1)
    consts, _ = pallas_verify._secp_consts()
    inv_digits = fp.msb_digits(cv.fn.n_int - 2, 4)

    def kernel(digs_ref, c_ref, a_ref, o_ref):
        fn = _mont_ctx(c_ref)
        o_ref[:, :] = fn.inv_tree(fn.to_rep(a_ref[:, :]), digs_ref,
                                  digs_ref.shape[0])

    got = np.asarray(pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((16, B), jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(), pl.BlockSpec()],
        interpret=True)(jnp.asarray(inv_digits), jnp.asarray(consts), arr))
    want = np.asarray(cv.fn.inv_batch(cv.fn.to_rep(jnp.asarray(arr))))
    assert (got == want).all()


@pytest.mark.skipif("FBTPU_SLOW_TESTS" not in os.environ,
                    reason="see test_inv_tree_parity")
def test_glv_split_parity():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    cv = ec.SECP256K1
    rng = np.random.default_rng(47)
    B = 8
    kvals = [int.from_bytes(rng.bytes(32), "big") % cv.fn.n_int
             for _ in range(B)]
    karr = np.stack([fp.to_limbs(v) for v in kvals], axis=1)
    consts, _ = pallas_verify._secp_consts()

    def kernel(c_ref, k_ref, o_ref):
        fn = _mont_ctx(c_ref)
        m1, n1, m2, n2 = pallas_verify._glv_split_values(fn, c_ref,
                                                         k_ref[:, :])
        o_ref[0] = m1
        o_ref[1] = m2
        o_ref[2] = jnp.broadcast_to(n1[None, :].astype(jnp.uint32),
                                    m1.shape)
        o_ref[3] = jnp.broadcast_to(n2[None, :].astype(jnp.uint32),
                                    m2.shape)

    got = np.asarray(pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((4, 16, B), jnp.uint32),
        interpret=True)(jnp.asarray(consts), karr))
    w1, wn1, w2, wn2 = ec._glv_split_device(cv, jnp.asarray(karr))
    assert (got[0] == np.asarray(w1)).all()
    assert (got[1] == np.asarray(w2)).all()
    assert (got[2][0].astype(bool) == np.asarray(wn1)).all()
    assert (got[3][0].astype(bool) == np.asarray(wn2)).all()
