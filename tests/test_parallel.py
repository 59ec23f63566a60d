"""Mesh-sharded crypto plane (fisco_bcos_tpu.parallel).

Runs on the 8-device host-platform mesh (conftest forces
xla_force_host_platform_device_count=8), exercised through the PRODUCT
surface: a CryptoSuite with mesh_devices set must produce bit-identical
results to the host oracle while its arrays live sharded across the mesh.
The JAX kernels run on XLA:CPU here only because the tests ask for it in
code (`allow_cpu=True`).
"""

import numpy as np
import pytest

from fisco_bcos_tpu.crypto import refimpl
from fisco_bcos_tpu.crypto.suite import make_suite


def _workload(suite, n, make_bad=True):
    digests, sigs, pubs = [], [], []
    for i in range(n):
        kp = suite.generate_keypair(bytes([i + 1]) * 16)
        d = suite.hash(b"mesh-tx-%d" % i)
        sigs.append(suite.sign(kp, d))
        digests.append(d)
        pubs.append(kp.pub_bytes)
    if make_bad:  # tamper the last row
        sigs[-1] = sigs[-1][:4] + b"\x5a" + sigs[-1][5:]
    return digests, sigs, pubs


def test_local_mesh_shape():
    from fisco_bcos_tpu.parallel import local_mesh

    mesh = local_mesh(8)
    assert mesh is not None and mesh.devices.size == 8
    assert local_mesh(3).devices.size == 2  # power-of-two prefix
    assert local_mesh(1) is None
    with pytest.raises(RuntimeError, match="16 devices asked for"):
        local_mesh(16)  # more than JAX has: never a quiet unsharded run


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
def test_mesh_suite_verify_and_recover_match_host():
    meshed = make_suite(backend="device", device_min_batch=1,
                        mesh_devices=8, allow_cpu=True)
    host = make_suite(backend="host")
    digests, sigs, pubs = _workload(host, 16)

    ok_m = meshed.verify_batch(digests, sigs, pubs)
    ok_h = host.verify_batch(digests, sigs, pubs)
    assert ok_m.tolist() == ok_h.tolist()
    assert ok_m.tolist() == [True] * 15 + [False]

    pubs_m, okr_m = meshed.recover_batch(digests, sigs)
    pubs_h, okr_h = host.recover_batch(digests, sigs)
    assert okr_m.tolist() == okr_h.tolist()
    assert pubs_m == pubs_h
    assert meshed._mesh_kernels is not None  # the mesh path actually ran


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
def test_mesh_suite_sm2_verify():
    meshed = make_suite(True, backend="device", device_min_batch=1,
                        mesh_devices=8, allow_cpu=True)
    host = make_suite(True, backend="host")
    digests, sigs, pubs = _workload(host, 8)
    ok_m = meshed.verify_batch(digests, sigs, pubs)
    ok_h = host.verify_batch(digests, sigs, pubs)
    assert ok_m.tolist() == ok_h.tolist() == [True] * 7 + [False]


@pytest.mark.slow  # jit-heavy / long round-trip: full-suite tier (VERDICT #7)
def test_mesh_bucket_padding_covers_small_batches():
    """Batches below the mesh size still work (bucket >= mesh width)."""
    meshed = make_suite(backend="device", device_min_batch=1,
                        mesh_devices=8, allow_cpu=True)
    host = make_suite(backend="host")
    digests, sigs, pubs = _workload(host, 3, make_bad=False)
    assert meshed.verify_batch(digests, sigs, pubs).tolist() == [True] * 3


def test_mesh_merkle_root_matches_host():
    """The mesh-sharded Merkle reduction must produce the same root as
    the host oracle for assorted leaf counts (incl. sub-mesh and
    non-power-of-two)."""
    from fisco_bcos_tpu.ops import merkle

    meshed = make_suite(backend="device", device_min_batch=1,
                        mesh_devices=8, allow_cpu=True)
    host = make_suite(backend="host")
    rng = np.random.default_rng(31)
    for n in (1, 3, 8, 17, 40, 64):
        leaves = [rng.bytes(32) for _ in range(n)]
        want = merkle.merkle_levels_host(list(leaves), "keccak256")[-1][0]
        assert meshed.merkle_root(leaves) == want == host.merkle_root(leaves)
