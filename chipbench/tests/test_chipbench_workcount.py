"""The roofline work counts against hand counts, and a share that the
inputs make impossible to pass 100%."""

import pytest
import workcount as wc
from manifest import peaks


def test_hand_counts():
    # 256 doublings of 10 multiplies, 192 additions of 11
    assert wc.SHAMIR_MULS == 2560 + 2112
    assert wc.EXPONENTIATION_MULS == 383
    assert wc.FIELD_MUL_OPS == 2048
    ops, nbytes = wc.ecdsa_recover(1)
    assert ops == (4672 + 2 * 383) * 2048 == 11_137_024
    assert nbytes == 162
    ops, nbytes = wc.sm2_verify(1000)
    assert ops == 1000 * (4672 + 383) * 2048
    assert nbytes == 161_000
    assert wc.ecdsa_recover(4096)[0] == 4096 * wc.ecdsa_recover(1)[0]


def test_merkle_counts():
    assert wc.merkle_nodes(1) == 0
    assert wc.merkle_nodes(16) == 1
    assert wc.merkle_nodes(17) == 2 + 1
    assert wc.merkle_nodes(1000) == 63 + 4 + 1
    ops, nbytes = wc.merkle_root(1000, 1, "keccak256")
    assert ops == 68 * 4 * 24 * 155 * 8
    assert nbytes == 1000 * 32 + 32
    ops2, nbytes2 = wc.merkle_root(3000, 3, "keccak256")
    assert ops2 == 3 * ops and nbytes2 == 3 * nbytes
    assert wc.merkle_root(1000, 1, "sm3")[0] == 68 * 9 * (64 * 40 + 520) * 4
    with pytest.raises(ValueError):
        wc.merkle_root(10, 1, "md5")


def test_share_is_the_larger_bound_and_cannot_pass_100():
    pk = peaks("TPU v5 lite")
    ops, nbytes = wc.ecdsa_recover(1000)
    least = max(ops / pk["int8_ops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    # a kernel cannot be faster than its roofline: at exactly that time the
    # share is 100, and at any time a chip can show it is below
    share, bound = wc.roofline_share(ops, nbytes, least, pk)
    assert share == pytest.approx(100.0) and bound == "ops"
    share, _ = wc.roofline_share(ops, nbytes, 10e-3, pk)
    assert 0 < share < 1
    share, bound = wc.roofline_share(*wc.merkle_root(1000, 1, "keccak256"),
                                     1e-3, pk)
    assert bound == "bytes" and 0 < share < 100


def test_roofline_reader_on_made_up_evidence():
    """The metric files kept ready for the kernel cells read a share above
    0 and far below 100 from plausible evidence, and nothing from none."""
    import json
    import os

    from conftest import BENCH
    from manifest import Manifest

    man = Manifest()
    status = lambda n: {"crypto": {"ops": {  # noqa: E731
        "recover": {"deviceItems": 1000 * n},
        "verify": {"deviceItems": 1000 * n},
        "merkle": {"deviceItems": 2000 * n, "deviceCalls": 2 * n}}}}
    ev = {"trace": {"programs": {"jit_ecdsa_recover_batch": 0.2,
                                 "jit_sm2_verify_batch": 0.2,
                                 "jit__merkle_root_bucketed": 0.01},
                    "busy_s": 0.41, "window_s": 10.0},
          "trace_status": {"before": status(1), "after": status(11)},
          "peaks": peaks("TPU v5 lite"), "hash_name": "keccak256"}
    for name in ("recover_roofline", "sm2_verify_roofline",
                 "merkle_roofline"):
        spec = json.load(open(os.path.join(BENCH, "metrics", name + ".json")))
        read = man.reader(spec["reader"])
        share = read(ev, spec)
        assert 0 < share < 1, (name, share)
        assert read(dict(ev, trace=None), spec) is None
        none = dict(ev, trace_status={"before": status(1),
                                      "after": status(1)})
        assert read(none, spec) is None
    idle = man.reader("trace_idle")
    assert idle(ev, {}) == pytest.approx(95.9)
    assert idle({"trace": None}, {}) is None
