"""Pallas-fused field multiplies: bit-parity with the XLA path.

Runs the pallas kernels in interpreter mode (CPU CI); on a real TPU the
same bodies lower through Mosaic. The EC kernel suite (test_ec.py) then
covers the full verify/recover pipeline with the dispatch active.
"""

import numpy as np
import pytest

from fisco_bcos_tpu.ops import fp, pallas_fp

SECP_P = 2**256 - 2**32 - 977
SECP_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
SM2_P = 0xFFFFFFFEFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF00000000FFFFFFFFFFFFFFFF

FIELDS = [
    fp.SolinasField(SECP_P, "secp.p"),
    fp.MontField(SECP_N, "secp.n"),
    fp.MontField(SM2_P, "sm2.p"),
]


def _rand_cols(rng, n, below):
    vals = [int.from_bytes(rng.bytes(32), "big") % below for _ in range(n)]
    return np.stack([fp.to_limbs(v) for v in vals], axis=1)


@pytest.mark.parametrize("field", FIELDS, ids=lambda f: f.name)
def test_mul_matches_xla(field):
    rng = np.random.default_rng(7)
    a = _rand_cols(rng, 256, field.n_int)
    b = _rand_cols(rng, 256, field.n_int)
    want = np.asarray(field.mul_xla(a, b))
    got = np.asarray(pallas_fp.mul(field, a, b, interpret=True))
    assert (want == got).all()


@pytest.mark.parametrize("field", FIELDS[:2], ids=lambda f: f.name)
def test_mul_edge_values(field):
    vals = [0, 1, 2, field.n_int - 1, field.n_int - 2, (1 << 255) % field.n_int]
    vals = (vals * 22)[:128]
    a = np.stack([fp.to_limbs(v) for v in vals], axis=1)
    b = np.ascontiguousarray(a[:, ::-1])
    want = np.asarray(field.mul_xla(a, b))
    got = np.asarray(pallas_fp.mul(field, a, b, interpret=True))
    assert (want == got).all()


def test_mul_stacked_matches_xla():
    field = FIELDS[0]
    rng = np.random.default_rng(9)
    a = np.stack([_rand_cols(rng, 128, field.n_int) for _ in range(3)])
    b = np.stack([_rand_cols(rng, 128, field.n_int) for _ in range(3)])
    want = np.asarray(field.mul_xla(a, b))
    got = np.asarray(pallas_fp.mul_stacked(field, a, b, interpret=True))
    assert (want == got).all()


def test_pallas_ok_gating():
    assert pallas_fp.pallas_ok((16, 128))
    assert pallas_fp.pallas_ok((16, 65536))
    assert not pallas_fp.pallas_ok((16, 100))  # not lane-aligned
    assert not pallas_fp.pallas_ok((16, 1))    # scalar column
    assert not pallas_fp.pallas_ok((8, 128))   # wrong limb count
    assert not pallas_fp.pallas_ok((3, 16, 128))  # stacked handled upstream


def test_mul_non_blk_multiple_covers_all_lanes():
    """B = 640 (a 128-multiple, NOT a 512-multiple) must compute every
    lane — regression for the floor-divided grid dropping the tail."""
    field = FIELDS[0]
    rng = np.random.default_rng(13)
    a = _rand_cols(rng, 640, field.n_int)
    b = _rand_cols(rng, 640, field.n_int)
    want = np.asarray(field.mul_xla(a, b))
    got = np.asarray(pallas_fp.mul(field, a, b, interpret=True))
    assert (want == got).all()  # esp. lanes 512..639


@pytest.mark.parametrize("field", FIELDS[:2], ids=lambda f: f.name)
def test_mul_const_column(field):
    """[16, B] x [16, 1] goes through the constant-column kernel."""
    rng = np.random.default_rng(15)
    a = _rand_cols(rng, 256, field.n_int)
    c = _rand_cols(rng, 1, field.n_int)
    want = np.asarray(field.mul_xla(a, np.broadcast_to(c, a.shape)))
    got = np.asarray(pallas_fp.mul_const(field, a, c, interpret=True))
    assert (want == got).all()


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
@pytest.mark.parametrize("field", FIELDS[:2], ids=lambda f: f.name)
def test_pow_const_fused(field):
    """Fused exponentiation matches the XLA scan path (small exponents in
    CI; the (p+1)/4 sqrt exponent is covered on the chip by
    chip_smoke.py's recover assertions)."""
    rng = np.random.default_rng(17)
    a = _rand_cols(rng, 128, field.n_int)
    if isinstance(field, fp.MontField):
        a = np.asarray(field.to_rep(a))
    for e in (1, 2, 3, 0x1234, 0xFFFF):
        want = np.asarray(field.pow_const(a, e))  # platform cpu: XLA scan
        got = np.asarray(pallas_fp.pow_const(field, a, e, interpret=True))
        assert (want == got).all(), hex(e)


def test_host_value_parity():
    """Pallas product agrees with Python big-int arithmetic, not just the
    XLA path (guards against a shared systematic error)."""
    field = FIELDS[0]
    rng = np.random.default_rng(11)
    vals_a = [int.from_bytes(rng.bytes(32), "big") % SECP_P for _ in range(128)]
    vals_b = [int.from_bytes(rng.bytes(32), "big") % SECP_P for _ in range(128)]
    a = np.stack([fp.to_limbs(v) for v in vals_a], axis=1)
    b = np.stack([fp.to_limbs(v) for v in vals_b], axis=1)
    got = np.asarray(pallas_fp.mul(field, a, b, interpret=True))
    for i in (0, 17, 127):
        want = vals_a[i] * vals_b[i] % SECP_P
        assert fp.from_limbs_np(got[:, i]) == want
