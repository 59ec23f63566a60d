#!/usr/bin/env python3
"""Full BASELINE device sweep with incremental persistence.

Runs the complete BASELINE.md config grid — secp256k1 verify+recover at
1k/16k/64k, SM2 verify at 1k/16k/64k, Keccak256 Merkle root at 10k/64k
leaves, plus small-batch points (64/256/1024) for the host/device
crossover — and rewrites --out after EVERY config via atomic rename, so a
run cut short keeps everything measured so far.

Configs are ordered headline-first (64k secp verify/recover, 64k SM2).
Run it on the chip (`chiprun -- python benchmark/device_sweep.py`; the
default --out lies under chiprun_out/, which the tool brings back). On a
machine where JAX reports no TPU it exits non-zero: these are device
numbers.

Reference counterpart: benchmark/merkleBench.cpp + bcos-crypto/demo/
perf_demo.cpp (the reference's CPU harnesses for the same grid).
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


def _now() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        _REPO, "chiprun_out", "device_sweep.json"))
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--skip-done", action="store_true",
                    help="skip configs already recorded for this backend")
    args = ap.parse_args()

    import jax

    import bench as bench_mod
    from fisco_bcos_tpu.crypto import refimpl
    from fisco_bcos_tpu.ops import ec, merkle

    backend = jax.devices()[0].platform
    if backend != "tpu":
        sys.exit(f"device_sweep: JAX reports platform {backend!r}; "
                 f"these are device numbers and there is no CPU fallback")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    record: dict = {"backend": backend, "updated_at": _now(), "configs": {}}
    if os.path.exists(args.out):
        try:
            prev = json.load(open(args.out))
            if prev.get("backend") == backend:
                record["configs"] = prev.get("configs", {})
        except Exception:
            pass

    print(f"sweep: backend={backend} out={args.out}", flush=True)

    def build_args(params, batch_n, sm=False):
        return bench_mod.build_sig_args(params, batch_n, sm=sm)

    def timed(fn, *fargs):
        return bench_mod.timed_device(fn, *fargs, iters=args.iters)

    def save(name: str, payload: dict) -> None:
        payload["measured_at"] = record["updated_at"] = _now()
        record["configs"][name] = payload
        with open(args.out + ".tmp", "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
        os.replace(args.out + ".tmp", args.out)
        print(f"sweep: {name}: {payload}", flush=True)

    # CPU OpenSSL divisor for vs_baseline (same measurement as bench.py)
    if not (args.skip_done and "cpu_baseline" in record["configs"]):
        base, cores, src = bench_mod._measure_cpu_baseline()
        save("cpu_baseline", {"sigs_per_sec": round(base, 1),
                              "cores": cores, "source": src})

    # -- EC configs, headline-first ----------------------------------------
    ec_grid = [
        ("secp_verify_65536", "secp", "verify", 65536),
        ("secp_recover_65536", "secp", "recover", 65536),
        ("sm2_verify_65536", "sm2", "verify", 65536),
        ("secp_verify_16384", "secp", "verify", 16384),
        ("sm2_verify_16384", "sm2", "verify", 16384),
        ("secp_recover_16384", "secp", "recover", 16384),
        ("secp_verify_1024", "secp", "verify", 1024),
        ("sm2_verify_1024", "sm2", "verify", 1024),
        ("secp_recover_1024", "secp", "recover", 1024),
        # small batches: locate the host/device crossover
        ("secp_verify_256", "secp", "verify", 256),
        ("secp_verify_64", "secp", "verify", 64),
    ]
    failures = []
    for name, curve, op, batch in ec_grid:
        if args.skip_done and name in record["configs"]:
            continue
        try:
            sm = curve == "sm2"
            params = refimpl.SM2P256V1 if sm else refimpl.SECP256K1
            cv = ec.SM2P256V1 if sm else ec.SECP256K1
            e, r, s, v, qx, qy = build_args(params, batch, sm=sm)
            if op == "verify":
                fn = ec.sm2_verify_batch if sm else ec.ecdsa_verify_batch
                dt, ok = timed(fn, cv, e, r, s, qx, qy)
                assert bool(np.asarray(ok).all()), \
                    f"{name}: kernel rejected sigs"
                # negative: a tampered digest must be rejected (guards a
                # kernel defect that weakens a check into always-true)
                e_bad = np.asarray(e).copy()
                e_bad[0, 0] ^= 1
                okb = np.asarray(fn(cv, e_bad, r, s, qx, qy))
                assert (not okb[0]) and bool(okb[1:].all()), \
                    f"{name}: tampered sig accepted"
            else:
                dt, rec = timed(ec.ecdsa_recover_batch, cv, e, r, s, v)
                assert bool(np.asarray(rec[2]).all()), \
                    f"{name}: recover failed"
                # value-level: recovered keys must equal the signers'
                assert (np.asarray(rec[0]) == np.asarray(qx)).all() and \
                       (np.asarray(rec[1]) == np.asarray(qy)).all(), \
                    f"{name}: recovered wrong public keys"
            save(name, {"sigs_per_sec": round(batch / dt, 1),
                        "batch": batch, "ms": round(dt * 1e3, 2)})
        except Exception as exc:  # keep sweeping: one bad config (or a
            failures.append(name)  # lowering gap) must not erase the rest
            print(f"sweep: {name} FAILED: {exc!r}", flush=True)

    # -- Merkle configs ----------------------------------------------------
    rng = np.random.default_rng(11)
    for name, nleaves in [("merkle_keccak_10000", 10000),
                          ("merkle_keccak_65536", 65536),
                          ("merkle_sm3_10000", 10000)]:
        if args.skip_done and name in record["configs"]:
            continue
        try:
            alg = "sm3" if "sm3" in name else "keccak256"
            leaves = rng.integers(0, 256, (nleaves, 32), dtype=np.uint8)
            leaves_d = jax.device_put(leaves)
            dt, root = timed(merkle.merkle_root, leaves_d, alg)
            # parity vs host oracle on a small prefix
            host_root = merkle.merkle_levels_host(
                [bytes(x) for x in leaves[:64]], alg)[-1][0]
            dev_small = bytes(np.asarray(merkle.merkle_root(leaves[:64],
                                                            alg)))
            assert dev_small == host_root, \
                f"{name}: device/host root mismatch"
            save(name, {"ms_per_root": round(dt * 1e3, 2),
                        "leaves": nleaves,
                        "leaves_per_sec": round(nleaves / dt, 1)})
        except Exception as exc:
            failures.append(name)
            print(f"sweep: {name} FAILED: {exc!r}", flush=True)

    # -- derived: crossover estimate ---------------------------------------
    cfgs = record["configs"]
    floor = 5391.3  # native/ncrypto 1-core measured floor (BENCH_r03)
    crossover = None
    for b in (64, 256, 1024, 16384, 65536):
        c = cfgs.get(f"secp_verify_{b}")
        if c and c["sigs_per_sec"] > floor:
            crossover = b
            break
    save("crossover", {"device_min_batch_suggest": crossover,
                       "native_floor_sigs_per_sec": floor})
    print(f"sweep: DONE (failures: {failures or 'none'})", flush=True)
    if failures:
        sys.exit(3)


if __name__ == "__main__":
    main()
