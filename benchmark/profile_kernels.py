#!/usr/bin/env python3
"""Per-kernel scan-step cost breakdown for the EC verify ladder.

VERDICT r3 #1/#2: the gap from the measured 2.95x (r2, 16k batch) to the
10x target needs EVIDENCE about where a verify's time goes. This harness
times the ladder's building blocks in isolation on the live backend and
prints a breakdown (all per-batch-element-step, amortized):

  field_mul        one Montgomery/Solinas field multiply
  jac_double       point doubling (the 136 per verify)
  jac_add_affine   mixed add (the 4x34 per GLV verify)
  select_const     G-table one-hot tensordot select
  select_batch     per-element Q-table select
  table_build      per-element window table + batch normalization
  inv_batch        the scalar-field inversion tree (s^-1)
  glv_ladder       the full 34-step scan (everything combined)
  verify_e2e       whole ecdsa_verify_batch

The ladder model cost (doublings + adds + selects) vs the measured
glv_ladder/verify time shows whether the kernel is compute-bound or
losing time to fusion/layout overheads.

Usage: python benchmark/profile_kernels.py [--batch 16384] [--iters 5]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16384)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--json", action="store_true",
                    help="print one JSON line instead of a table")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import bench as bench_mod
    from fisco_bcos_tpu.crypto import refimpl
    from fisco_bcos_tpu.ops import ec, fp

    backend = jax.devices()[0].platform
    B = args.batch
    cv = ec.SECP256K1
    f = cv.fp

    e, r, s, v, qx, qy = bench_mod.build_sig_args(refimpl.SECP256K1, B)
    # lane-major operands for the sub-kernels
    exm = jnp.transpose(jnp.asarray(e))
    qxm, qym = jnp.transpose(jnp.asarray(qx)), jnp.transpose(jnp.asarray(qy))
    qxr, qyr = f.to_rep(qxm), f.to_rep(qym)
    P = jnp.stack([qxr, qyr, f.one_rep(qxr.shape)])
    dig = jnp.asarray(np.random.default_rng(7).integers(
        0, ec.TBL, B, dtype=np.uint32))

    def timed(fn, *a):
        g = jax.jit(fn)
        out = g(*a)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = g(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters

    rows: dict[str, float] = {}

    rows["field_mul"] = timed(lambda a, b: f.mul(a, b), qxr, qyr)
    rows["jac_double"] = timed(lambda p: ec.jac_double(cv, p), P)
    rows["jac_add_affine"] = timed(
        lambda p, x, y: ec.jac_add_affine(cv, p, x, y), P, qxr, qyr)
    rows["select_const"] = timed(
        lambda d: ec._take_const(cv.g_table, d), dig)
    tq2 = jax.jit(lambda x, y: ec._q_window_affine(cv, x, y))(qxr, qyr)
    jax.block_until_ready(tq2)
    rows["select_batch"] = timed(lambda t, d: ec._take_batch(t, d), tq2, dig)
    rows["table_build"] = timed(
        lambda x, y: ec._q_window_affine(cv, x, y), qxr, qyr)
    rows["inv_batch_n"] = timed(
        lambda a: cv.fn.inv_batch(cv.fn.to_rep(a)), exm)
    u1 = cv.fn.reduce_loose(exm)
    rows["glv_ladder"] = timed(
        lambda a, b, x, y: ec.glv_shamir_mult(cv, a, b, x, y),
        u1, u1, qxr, qyr)
    rows["verify_e2e"] = timed(
        lambda *a: ec.ecdsa_verify_batch(cv, *a), e, r, s, qx, qy)

    # fused-kernel units (pallas path; fall back silently if disabled)
    if fp._use_pallas():
        from fisco_bcos_tpu.ops import pallas_fp

        rows["pl_mul"] = timed(lambda a, b: pallas_fp.mul(f, a, b),
                               qxr, qyr)
        rows["pl_pow_sqrt"] = timed(
            lambda a: pallas_fp.pow_const(f, a, (f.n_int + 1) // 4), qxr)
        rows["glv_split"] = timed(
            lambda k: jnp.stack(ec._glv_split_device(cv, k)[::2]), u1)
        from fisco_bcos_tpu.ops import merkle as _mk
        leaves = jnp.asarray(np.random.default_rng(9).integers(
            0, 256, (10000, 32), dtype=np.uint8))
        rows["merkle_10k"] = timed(lambda l: _mk.merkle_root(l), leaves)

    # ladder cost model at WINDOW=4/GLV_DIGITS=34: does measured time
    # match the sum of its parts? (mismatch => fusion/layout overhead)
    model = (ec.GLV_DIGITS * ec.WINDOW * rows["jac_double"]
             + ec.GLV_DIGITS * 4 * rows["jac_add_affine"]
             + ec.GLV_DIGITS * 2 * rows["select_const"]
             + ec.GLV_DIGITS * 2 * rows["select_batch"]
             + rows["table_build"])
    out = {
        "backend": backend,
        "batch": B,
        "ms": {k: round(v * 1e3, 3) for k, v in rows.items()},
        "ladder_model_ms": round(model * 1e3, 3),
        "ladder_measured_ms": round(rows["glv_ladder"] * 1e3, 3),
        "model_ratio": round(rows["glv_ladder"] / model, 3) if model else 0,
        "verify_sigs_per_sec": round(B / rows["verify_e2e"], 1),
    }
    if args.json:
        print(json.dumps(out))
        return
    print(f"backend={backend} batch={B}")
    for k, ms in out["ms"].items():
        print(f"  {k:<16} {ms:>10.3f} ms")
    print(f"  ladder model {out['ladder_model_ms']:.3f} ms vs measured "
          f"{out['ladder_measured_ms']:.3f} ms "
          f"(ratio {out['model_ratio']})")
    print(f"  verify: {out['verify_sigs_per_sec']} sigs/s")


if __name__ == "__main__":
    main()
