"""Fused varlen batch hashing: parity with the host oracle.

CI keeps interpret-mode work tiny (single-block Keccak batch); multi-block
masking and SM3 are asserted against the native host path on the chip by
chip_smoke.py's kernel stage.
"""

import numpy as np
import pytest

from fisco_bcos_tpu.crypto import refimpl
from fisco_bcos_tpu.ops import keccak, pallas_hash, sm3


def _pack(msgs, pad_fn, rate):
    padded = [pad_fn(m) for m in msgs]
    maxb = max(p.shape[0] for p in padded)
    B = ((len(msgs) + 127) // 128) * 128
    blocks = np.zeros((B, maxb, rate), np.uint8)
    nvalid = np.zeros((B,), np.int32)
    for i, p in enumerate(padded):
        blocks[i, : p.shape[0]] = p
        nvalid[i] = p.shape[0]
    return blocks, nvalid


def test_keccak_varlen_fused_single_block():
    rng = np.random.default_rng(31)
    msgs = [rng.bytes(int(n)) for n in rng.integers(0, 100, 30)] + [b""]
    blocks, nvalid = _pack(msgs, keccak.pad_message_np, keccak.RATE_BYTES)
    got = np.asarray(pallas_hash.keccak256_varlen_fused(
        blocks, nvalid, interpret=True))
    for i, m in enumerate(msgs):
        assert bytes(got[i]) == refimpl.keccak256(m), (i, len(m))


@pytest.mark.skipif("FBTPU_SLOW_TESTS" not in __import__("os").environ,
                    reason="multi-block + SM3 interpret runs are covered "
                           "by chip_smoke.py on the chip")
def test_sm3_varlen_fused():
    rng = np.random.default_rng(33)
    msgs = [rng.bytes(int(n)) for n in rng.integers(0, 80, 16)]
    blocks, nvalid = _pack(msgs, sm3.pad_message_np, sm3.BLOCK_BYTES)
    got = np.asarray(pallas_hash.sm3_varlen_fused(
        blocks, nvalid, interpret=True))
    for i, m in enumerate(msgs):
        assert bytes(got[i]) == refimpl.sm3(m), (i, len(m))
