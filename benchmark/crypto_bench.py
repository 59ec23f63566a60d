#!/usr/bin/env python3
"""Crypto throughput benchmark — counterpart of the reference's
bcos-crypto/demo/perf_demo.cpp (sign/verify/hash ops/sec) extended with the
BASELINE.json batch configs: secp256k1 + SM2 batch verify/recover at
1k/16k/64k signatures on the device kernels.

Usage: python benchmark/crypto_bench.py [--batches 1024,16384,65536]
       [--suite ecdsa|sm|both] [--recover]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mk_batch(params, refimpl, batch, with_pub):
    import numpy as np
    rng = np.random.default_rng(11)
    base = []
    for i in range(8):
        sk, pub = refimpl.keygen(params, bytes([i + 3]) * 32)
        digest = refimpl.keccak256(rng.bytes(64))
        if params.name.startswith("sm2"):
            r, s = refimpl.sm2_sign(sk, digest)
            v = 0
        else:
            r, s, v = refimpl.ecdsa_sign(params, sk, digest)
        base.append((int.from_bytes(digest, "big"), r, s, v, pub))
    cols = list(zip(*(base[i % 8] for i in range(batch))))
    return cols


def bench_kernel(name, fn, args_dev, batch, iters=3):
    import jax

    out = fn(*args_dev)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args_dev)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    return {"kernel": name, "batch": batch, "sigs_per_sec": round(batch / dt, 1),
            "ms": round(dt * 1000, 2)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="1024,16384,65536")
    ap.add_argument("--suite", default="both",
                    choices=["ecdsa", "sm", "both"])
    ap.add_argument("--recover", action="store_true")
    ap.add_argument("--host-ops", action="store_true",
                    help="also time host-side single sign/verify/hash")
    args = ap.parse_args()

    import jax
    import numpy as np

    from fisco_bcos_tpu.crypto import refimpl
    from fisco_bcos_tpu.ops import bigint, ec

    batches = [int(b) for b in args.batches.split(",")]
    results = []

    for batch in batches:
        if args.suite in ("ecdsa", "both"):
            e, r, s, v, pubs = _mk_batch(refimpl.SECP256K1, refimpl, batch,
                                         True)
            el = jax.device_put(bigint.batch_to_limbs(e))
            rl = jax.device_put(bigint.batch_to_limbs(r))
            sl = jax.device_put(bigint.batch_to_limbs(s))
            qx = jax.device_put(bigint.batch_to_limbs([p[0] for p in pubs]))
            qy = jax.device_put(bigint.batch_to_limbs([p[1] for p in pubs]))
            results.append(bench_kernel(
                "secp256k1_verify",
                lambda *a: ec.ecdsa_verify_batch(ec.SECP256K1, *a),
                (el, rl, sl, qx, qy), batch))
            if args.recover:
                vl = jax.device_put(np.asarray(v, np.uint32))
                results.append(bench_kernel(
                    "secp256k1_recover",
                    lambda *a: ec.ecdsa_recover_batch(ec.SECP256K1, *a),
                    (el, rl, sl, vl), batch))
        if args.suite in ("sm", "both"):
            e, r, s, v, pubs = _mk_batch(refimpl.SM2P256V1, refimpl, batch,
                                         True)
            el = jax.device_put(bigint.batch_to_limbs(e))
            rl = jax.device_put(bigint.batch_to_limbs(r))
            sl = jax.device_put(bigint.batch_to_limbs(s))
            qx = jax.device_put(bigint.batch_to_limbs([p[0] for p in pubs]))
            qy = jax.device_put(bigint.batch_to_limbs([p[1] for p in pubs]))
            results.append(bench_kernel(
                "sm2_verify",
                lambda *a: ec.sm2_verify_batch(ec.SM2P256V1, *a),
                (el, rl, sl, qx, qy), batch))

    if args.host_ops:
        params = refimpl.SECP256K1
        sk, pub = refimpl.keygen(params, b"x" * 32)
        digest = refimpl.keccak256(b"bench")
        t0 = time.perf_counter()
        n = 20
        for _ in range(n):
            refimpl.ecdsa_sign(params, sk, digest)
        results.append({"kernel": "host_sign",
                        "ops_per_sec": round(n / (time.perf_counter() - t0), 1)})
        t0 = time.perf_counter()
        n = 2000
        for _ in range(n):
            refimpl.keccak256(b"x" * 256)
        results.append({"kernel": "host_keccak256_256B",
                        "ops_per_sec": round(n / (time.perf_counter() - t0), 1)})

    print(json.dumps({"metric": "crypto_throughput", "results": results}))


if __name__ == "__main__":
    main()
