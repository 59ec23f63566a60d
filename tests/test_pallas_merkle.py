"""Fused whole-tree Merkle kernel: parity with the host oracle.

Interpreter-mode execution of the pallas kernel is slow, so CI keeps the
buckets small (single level + the n<=1 edge). The kernel is NOT on the TPU
dispatch: Mosaic refuses it (see ops/merkle.py); these tests keep its
interpret-mode semantics pinned for the rewrite (ROADMAP queue 1 item 5).
"""

import numpy as np
import pytest

from fisco_bcos_tpu.ops import merkle, pallas_merkle


def _host_root(data, alg):
    return merkle.merkle_levels_host([bytes(x) for x in data], alg)[-1][0]


@pytest.mark.parametrize("n", [1, 5, 16])
def test_keccak_single_level(n):
    rng = np.random.default_rng(5 + n)
    leaves = np.zeros((16, 32), np.uint8)
    data = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    leaves[:n] = data
    got = bytes(np.asarray(pallas_merkle.merkle_root_fused(
        leaves, n, "keccak256", interpret=True)))
    assert got == _host_root(data, "keccak256")


@pytest.mark.skipif("FBTPU_SLOW_TESTS" not in __import__("os").environ,
                    reason="SM3 interpret-mode eval takes ~1h on one core")
def test_sm3_single_level():
    rng = np.random.default_rng(7)
    leaves = np.zeros((16, 32), np.uint8)
    data = rng.integers(0, 256, (13, 32), dtype=np.uint8)
    leaves[:13] = data
    got = bytes(np.asarray(pallas_merkle.merkle_root_fused(
        leaves, 13, "sm3", interpret=True)))
    assert got == _host_root(data, "sm3")


def test_levels_for():
    assert pallas_merkle._levels_for(16) == [1]
    assert pallas_merkle._levels_for(256) == [16, 1]
    assert pallas_merkle._levels_for(10240) == [640, 40, 3, 1]
    assert pallas_merkle._levels_for(65536) == [4096, 256, 16, 1]
