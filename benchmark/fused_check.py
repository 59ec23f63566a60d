#!/usr/bin/env python3
"""Device validation + timing for the FBTPU_FUSED_VERIFY kernels.

Run on the chip (`chiprun -- python benchmark/fused_check.py`). Compares the fused end-to-end verify /
recover / SM2-verify kernels against the default (fused-ladder) path by
VALUE on the same batch, then times both. Exit 0 = fused kernels are
bit-correct; the printed JSON says whether they are also faster (the
signal for flipping the dispatch default).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def main() -> None:
    import jax

    import bench as bench_mod
    from fisco_bcos_tpu.crypto import refimpl
    from fisco_bcos_tpu.ops import ec, pallas_verify

    B = int(os.environ.get("FUSED_CHECK_BATCH", "16384"))
    out = {"batch": B, "backend": jax.devices()[0].platform}

    e, r, s, v, qx, qy = bench_mod.build_sig_args(refimpl.SECP256K1, B)
    el, rl, sl = (np.asarray(x).T for x in (e, r, s))
    qxl, qyl = np.asarray(qx).T, np.asarray(qy).T

    # default path (fused-ladder dispatch)
    dt_def, ok_def = bench_mod.timed_device(
        ec.ecdsa_verify_batch, ec.SECP256K1, e, r, s, qx, qy)
    assert bool(np.asarray(ok_def).all()), "default verify rejected sigs"

    # fused end-to-end kernel, same inputs
    t0 = time.perf_counter()
    ok_f = jax.block_until_ready(pallas_verify.ecdsa_verify_fused(
        ec.SECP256K1, el, rl, sl, qxl, qyl))
    compile_s = time.perf_counter() - t0
    dt_f, ok_f2 = bench_mod.timed_device(
        pallas_verify.ecdsa_verify_fused, ec.SECP256K1, el, rl, sl,
        qxl, qyl)
    assert (np.asarray(ok_f) == np.asarray(ok_def)).all(), \
        "fused verify disagrees with default on valid sigs"
    # negative parity
    e_bad = el.copy()
    e_bad[0, 0] ^= 1
    okb = np.asarray(jax.block_until_ready(pallas_verify.ecdsa_verify_fused(
        ec.SECP256K1, e_bad, rl, sl, qxl, qyl)))
    assert (not okb[0]) and bool(okb[1:].all()), "fused tamper check failed"
    out["verify"] = {"default_ms": round(dt_def * 1e3, 1),
                     "fused_ms": round(dt_f * 1e3, 1),
                     "fused_compile_s": round(compile_s, 1),
                     "fused_sigs_per_sec": round(B / dt_f, 1),
                     "speedup": round(dt_def / dt_f, 2)}

    # recover
    dt_rd, rec_d = bench_mod.timed_device(
        ec.ecdsa_recover_batch, ec.SECP256K1, e, r, s, v)
    dt_rf, rec_f = bench_mod.timed_device(
        pallas_verify.ecdsa_recover_fused, ec.SECP256K1, el, rl, sl,
        np.asarray(v))
    assert (np.asarray(rec_f[0]).T == np.asarray(rec_d[0])).all(), \
        "fused recover qx mismatch"
    assert (np.asarray(rec_f[1]).T == np.asarray(rec_d[1])).all(), \
        "fused recover qy mismatch"
    out["recover"] = {"default_ms": round(dt_rd * 1e3, 1),
                      "fused_ms": round(dt_rf * 1e3, 1),
                      "fused_sigs_per_sec": round(B / dt_rf, 1),
                      "speedup": round(dt_rd / dt_rf, 2)}

    # sm2
    es, rs, ss, _vs, qxs, qys = bench_mod.build_sig_args(
        refimpl.SM2P256V1, B, sm=True)
    esl, rsl, ssl = (np.asarray(x).T for x in (es, rs, ss))
    qxsl, qysl = np.asarray(qxs).T, np.asarray(qys).T
    dt_sd, ok_sd = bench_mod.timed_device(
        ec.sm2_verify_batch, ec.SM2P256V1, es, rs, ss, qxs, qys)
    dt_sf, ok_sf = bench_mod.timed_device(
        pallas_verify.sm2_verify_fused, ec.SM2P256V1, esl, rsl, ssl,
        qxsl, qysl)
    assert (np.asarray(ok_sf) == np.asarray(ok_sd)).all(), \
        "fused sm2 disagrees"
    out["sm2_verify"] = {"default_ms": round(dt_sd * 1e3, 1),
                         "fused_ms": round(dt_sf * 1e3, 1),
                         "fused_sigs_per_sec": round(B / dt_sf, 1),
                         "speedup": round(dt_sd / dt_sf, 2)}

    out["flip_default"] = all(out[k]["speedup"] > 1.0
                              for k in ("verify", "recover", "sm2_verify"))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
