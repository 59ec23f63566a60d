#!/usr/bin/env python3
"""chip_smoke — the served chain path, end to end, on one TPU chip.

The quickest proof that the system still starts on the chip. In order:

  probe    one child asks JAX where it runs and exits. No TPU -> this
           program exits non-zero here, before any stage, printing no
           result.
  native   the tracked native/build/*.so load and pass their source-hash
           stamps (a stale library is a failure, not a drop to pure Python).
  kernels  one child that exits before the chain starts (a chip belongs to
           one process at a time): every device op THROUGH CryptoSuite at
           the production buckets — secp256k1 recover + verify and SM2
           verify at 512 / 4096 / 16384 and one 65,536 call (4 x CHUNK),
           Keccak + SM3 hash_batch at 1,000 tx-sized messages (and one
           deploy-sized), Keccak + SM3 merkle_root at 1,000 / 10,000 /
           65,536 leaves — each compared byte for byte with the native host
           path, tampered rows included. Its compiles fill the persistent
           cache node0 then starts from.
  chain    tools/build_chain.py -n 4 --consensus pbft, WAL storage,
           secp256k1, 100k prefunded accounts; four `python -m
           fisco_bcos_tpu` daemons over real TCP. node0 owns the chip
           ([crypto] backend = auto); nodes 1-3 are backend = host under
           JAX_PLATFORMS=cpu, so PBFT only commits headers the device node
           and the host nodes agree on. A JSON-RPC client sends 6,000
           signed transfers to node0 in concurrent 1,000-tx batches, fetches
           every receipt, compares node0's header hashes with node1's at
           every height and the final balances with a plain sequential
           replay, reads node0's `crypto` status (platform tpu, device
           recover items >= txs, a device Merkle root per block, ZERO
           compilations after ready) and stops all four with SIGTERM.

Any stage failing fails the run; nothing here catches a stage's failure to
carry on. The parent never initialises a JAX backend. Compile seconds,
start-to-ready and cache hits are reported as set-up; nothing here is a
speed. The last line of stdout is one JSON object.

`--rehearse-cpu` is the only CPU path: the same control flow at a tiny
size on XLA:CPU (no EC kernels — they take minutes to compile there), every
line tagged platform=cpu. It is what tier-1 runs; it proves nothing about
the chip.
"""

from __future__ import annotations

import os
import sys

# the parent holds no chip: pinned before anything can import jax. What
# the outside had set goes back into the children that take the chip
# (this same file, run with --stage, and node0).
_OUTSIDE_JAX_PLATFORMS = os.environ.get("JAX_PLATFORMS")
if "--stage" not in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import configparser  # noqa: E402
import http.client  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

_REPO = os.path.dirname(os.path.abspath(__file__))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

BATCH = 1000           # txs per JSON-RPC batch to node0 == tx_count_limit
READ_BATCH = 256       # reads go to every node: the default [rpc] max_batch
_TAG = "[device not probed yet]"


def say(msg: str) -> None:
    print(f"{_TAG} {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def sizes(rehearse: bool) -> dict:
    if rehearse:
        return {"ec": (), "hash": 8, "merkle": (20,), "kinds": ("ecdsa",),
                "txs": 48, "batch": 24, "accounts": 256, "senders": 2}
    return {"ec": (512, 4096, 16384, 65536), "hash": 1000,
            "merkle": (1000, 10000, 65536), "kinds": ("ecdsa", "sm"),
            "txs": 6000, "batch": BATCH, "accounts": 100_000, "senders": 3}


# -- children ----------------------------------------------------------------

def chip_env(rehearse: bool) -> dict:
    """Env of a child that takes the chip: the parent's CPU pin stripped
    (kept in the rehearsal, where there is no chip to take)."""
    env = dict(os.environ)
    if not rehearse:
        env.pop("JAX_PLATFORMS")
        if _OUTSIDE_JAX_PLATFORMS is not None:
            env["JAX_PLATFORMS"] = _OUTSIDE_JAX_PLATFORMS
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def stage_probe() -> None:
    """Child: where does JAX run? One JSON line."""
    import jax
    import jaxlib

    try:
        import libtpu
        libtpu_v = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_v = "absent"
    devs = jax.devices()
    print(json.dumps({
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": libtpu_v}), flush=True)


def stage_kernels(seed: int, rehearse: bool) -> None:
    """Child: every device op through CryptoSuite vs the native host path.
    Ops run in parallel threads so their compiles overlap (tracing holds
    the GIL, XLA's compile does not); compile seconds are attributed by the
    thread jax.monitoring reports them on."""
    from concurrent.futures import ThreadPoolExecutor

    from jax import monitoring

    from fisco_bcos_tpu.crypto.suite import COMPILE_LOG, CryptoSuite

    sz = sizes(rehearse)
    tl = threading.local()
    compile_s: dict = {}
    lock = threading.Lock()

    def on_compile(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            label = getattr(tl, "label", "other")
            with lock:
                compile_s[label] = compile_s.get(label, 0.0) + secs

    monitoring.register_event_duration_secs_listener(on_compile)
    suites = {k: (CryptoSuite(k, backend="device", allow_cpu=rehearse),
                  CryptoSuite(k, backend="host")) for k in sz["kinds"]}

    def first_call(label: str, fn):
        tl.label = label
        t0 = time.monotonic()
        out = fn()
        wall = time.monotonic() - t0
        tl.label = "other"
        say(f"kernels: {label}: first call {wall:.1f} s wall, "
            f"{compile_s.get(label, 0.0):.1f} s in XLA/Mosaic compile "
            f"(set-up, not speed)")
        return out

    def signed_rows(kind: str, uniq: int):
        """uniq signed (digest, sig, pub) rows from the seed, with a
        tampered signature, a tampered digest and an all-zero signature."""
        _dev, host = suites[kind]
        r = random.Random(seed * 7919 + (kind == "sm"))
        kps = [host.generate_keypair(r.randbytes(16)) for _ in range(uniq)]
        digs = [host.hash(r.randbytes(64)) for _ in range(uniq)]
        sigs = [host.sign(kp, d) for kp, d in zip(kps, digs)]
        pubs = [kp.pub_bytes for kp in kps]
        sigs[3] = sigs[3][:5] + bytes([sigs[3][5] ^ 0x40]) + sigs[3][6:]
        digs[5] = bytes([digs[5][0] ^ 1]) + digs[5][1:]
        sigs[7] = bytes(len(sigs[7]))
        return digs, sigs, pubs

    def ec_op(kind: str, op: str) -> None:
        dev, host = suites[kind]
        name = f"{'sm2' if kind == 'sm' else 'secp256k1'}.{op}"
        uniq = min(sz["ec"])
        digs, sigs, pubs = signed_rows(kind, uniq)
        # the host answer on the unique rows; larger batches tile them
        if op == "recover":
            want_pubs, want_ok = host.recover_batch(digs, sigs)
            want_ok = list(want_ok)
            check(want_pubs[0] == pubs[0] and want_pubs[7] is None
                  and want_pubs[5] != pubs[5],
                  f"{name}: host reference is not what the rows imply")
        else:
            want_ok = list(host.verify_batch(digs, sigs, pubs))
            check(want_ok[0] and not (want_ok[3] or want_ok[5]
                                      or want_ok[7]),
                  f"{name}: host reference is not what the rows imply")
        for n in sz["ec"]:
            reps = n // uniq
            D, S, P = digs * reps, sigs * reps, pubs * reps
            if op == "recover":
                call = lambda: dev.recover_batch(D, S)  # noqa: E731
            else:
                call = lambda: dev.verify_batch(D, S, P)  # noqa: E731
            got = first_call(f"{name}@{n}", call)
            if op == "recover":
                check(got[0] == want_pubs * reps,
                      f"{name}@{n}: recovered keys differ from the host's")
                got = got[1]
            check(list(got) == want_ok * reps,
                  f"{name}@{n}: verdicts differ from the host's")
            say(f"kernels: {name}@{n}: {sum(want_ok) * reps} accepted, "
                f"{(uniq - sum(want_ok)) * reps} rejected, byte-equal to "
                f"the native host path")

    def hash_op(kind: str) -> None:
        dev, host = suites[kind]
        name = "keccak256" if kind == "ecdsa" else "sm3"
        r = random.Random(seed * 31 + (kind == "sm"))
        msgs = [r.randbytes(r.randrange(150, 260))
                for _ in range(sz["hash"])]
        got = first_call(f"{name}.hash_batch@{len(msgs)}",
                         lambda: dev.hash_batch(msgs))
        check(got == host.hash_batch(msgs),
              f"{name}.hash_batch: digests differ from the host's")
        # one contract-deploy-sized message in the batch: it takes the
        # host hasher, the rest stay on the compiled shape
        before = COMPILE_LOG.snapshot()["compiles"]
        mixed = msgs[:-1] + [r.randbytes(24_000)]
        check(dev.hash_batch(mixed) == host.hash_batch(mixed),
              f"{name}.hash_batch: deploy-sized batch differs")
        check(rehearse or COMPILE_LOG.snapshot()["compiles"] == before,
              f"{name}.hash_batch: a deploy-sized message compiled")
        say(f"kernels: {name}.hash_batch@{len(msgs)}: byte-equal to the "
            f"native host path (+ one 24 kB message, no new compile)")

    def merkle_op(kind: str) -> None:
        dev, host = suites[kind]
        name = "keccak256" if kind == "ecdsa" else "sm3"
        r = random.Random(seed * 131 + (kind == "sm"))
        for n in sz["merkle"]:
            leaves = [r.randbytes(32) for _ in range(n)]
            got = first_call(f"{name}.merkle_root@{n}",
                             lambda: dev.merkle_root(leaves))
            check(got == host.merkle_root(leaves),
                  f"{name}.merkle_root@{n}: root differs from the host's")
            say(f"kernels: {name}.merkle_root@{n}: byte-equal to the "
                f"native host path")

    jobs = []
    if sz["ec"]:
        jobs += [lambda: ec_op("ecdsa", "recover"),
                 lambda: ec_op("ecdsa", "verify"),
                 lambda: ec_op("sm", "verify")]
    else:
        say("kernels: EC kernels skipped (rehearsal: minutes of XLA:CPU "
            "compile each)")
    for k in sz["kinds"]:
        jobs += [lambda k=k: hash_op(k), lambda k=k: merkle_op(k)]
    with ThreadPoolExecutor(len(jobs)) as pool:
        for fut in [pool.submit(j) for j in jobs]:
            fut.result()  # re-raises the first failure
    for k, (dev, _h) in suites.items():
        st = dev.status()
        check(st["pallas"] == ("off" if rehearse else "compiled"),
              f"pallas mode {st['pallas']}")
        say(f"kernels: {k}: device calls "
            + ", ".join(f"{op} {v['deviceCalls']}/{v['deviceItems']} items"
                        for op, v in st["ops"].items() if v["deviceCalls"]))
    log = COMPILE_LOG.snapshot()
    say(f"kernels: {log['compiles']} compiles, {log['compileSeconds']} s, "
        f"persistent cache {log['cacheHits']} hits / {log['cacheMisses']} "
        f"written (set-up, not speed)")
    print(json.dumps({"stage": "kernels", "ok": True, **log}), flush=True)


def run_child(stage: str, args, env: dict, timeout: float) -> dict:
    """Run `chip_smoke.py --stage <stage>` to its end; relay its lines;
    -> the JSON object on its last line. Non-zero exit fails the run."""
    cmd = [sys.executable, os.path.abspath(__file__), "--stage", stage,
           "--seed", str(args.seed)]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, cwd=_REPO)
    timer = threading.Timer(timeout, proc.kill)
    timer.start()
    last, tail = "", []
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            tail = (tail + [line])[-40:]
            if line.startswith(("[", "{")):
                last = line
                if line.startswith("["):
                    print(line, flush=True)
        rc = proc.wait()
    finally:
        timer.cancel()
    if rc != 0:
        for ln in tail:
            print(f"    | {ln}", file=sys.stderr, flush=True)
        raise SmokeFailure(f"stage {stage}: exit code {rc}")
    return json.loads(last)


# -- chain stage (parent: host code only) ------------------------------------

def native_libs() -> None:
    import ctypes

    from fisco_bcos_tpu.utils.nativelib import check_src_hash

    for name in ("ncrypto", "nevm", "bcoskv"):
        lib = ctypes.CDLL(os.path.join(_REPO, "native", "build",
                                       f"lib{name}.so"))
        src = os.path.join(_REPO, "native", name, f"{name}.cpp")
        check(os.path.exists(src) and check_src_hash(lib, name, src),
              f"native/build/lib{name}.so does not match {src}")
    from fisco_bcos_tpu.crypto import nativeec, nativehash

    check(nativeec.available() and nativehash.keccak256_batch() is not None,
          "native host crypto did not bind")
    say("native: libncrypto, libnevm, libbcoskv loaded, source-hash "
        "stamps match")


def free_port_run(n: int, seed: int) -> int:
    """-> base of n consecutive TCP ports that are free right now
    (build_chain numbers a chain's ports consecutively)."""
    import socket

    rng = random.Random(seed ^ os.getpid())
    for _ in range(200):
        base = rng.randrange(20000, 60000)
        socks = []
        try:
            for p in range(base, base + n):
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", p))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise SmokeFailure(f"no run of {n} free ports found")


def make_txs(seed: int, n: int, accounts: int) -> tuple[list, list]:
    """n signed transfers between prefunded accounts, from the seed ->
    (wire hex, [(src, dst, amount)]). `transfer` at the DagTransfer
    address: the reference's userTransfer benchmark call."""
    from fisco_bcos_tpu.crypto.suite import make_suite
    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.protocol import Transaction

    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"chip-smoke-client-%d" % seed)
    rng = random.Random(seed)
    wires, moves = [], []
    for i in range(n):
        a = rng.randrange(accounts)
        b = (a + 1 + rng.randrange(accounts - 1)) % accounts
        src, dst, amt = b"acct-%07d" % a, b"acct-%07d" % b, 1 + i % 7
        data = pc.encode_call(
            "transfer", lambda w: w.blob(src).blob(dst).u64(amt))
        tx = Transaction(to=pc.DAG_TRANSFER_ADDRESS, input=data,
                         nonce=f"smoke-{seed}-{i}", block_limit=500)
        wires.append("0x" + tx.sign(suite, kp).encode().hex())
        moves.append((src, dst, amt))
    return wires, moves


def replay(moves: list, start: int) -> dict:
    """The plain reference: apply the transfers one after another."""
    bal: dict = {}
    for src, dst, amt in moves:
        check(bal.get(src, start) >= amt, "replay: overdraft in the tx list")
        bal[src] = bal.get(src, start) - amt
        bal[dst] = bal.get(dst, start) + amt
    return bal


class Cluster:
    """Four node daemons as OS processes; stop() always reaps them."""

    def __init__(self, info: dict, rehearse: bool):
        self.info = info
        self.rehearse = rehearse
        self.procs: list = []
        self.t_start: list = []

    def start(self) -> None:
        for i, n in enumerate(self.info["nodes"]):
            # node0 takes the chip; nodes 1-3 keep this parent's CPU pin
            env = chip_env(self.rehearse or i != 0)
            self.t_start.append(time.monotonic())
            self.procs.append(subprocess.Popen(
                [sys.executable, "-m", "fisco_bcos_tpu", n["dir"],
                 "--log-file", os.path.join(n["dir"], "node.log")],
                env=env, cwd=_REPO, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL))

    def client(self, i: int):
        from fisco_bcos_tpu.sdk.client import SdkClient

        return SdkClient(
            f"http://127.0.0.1:{self.info['nodes'][i]['rpc_port']}",
            timeout=120.0)

    def wait_ready(self, i: int, timeout: float) -> tuple[float, dict]:
        """-> (seconds from process start to the first answered status,
        that status). The RPC port opens only after the crypto warm-up."""
        cli = self.client(i)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            check(self.procs[i].poll() is None,
                  f"node{i} exited with code {self.procs[i].returncode} "
                  f"before it was ready")
            try:
                st = cli.request("getSystemStatus", [])
                return time.monotonic() - self.t_start[i], st
            except (OSError, http.client.HTTPException):
                time.sleep(0.25)
        raise SmokeFailure(f"node{i} not ready after {timeout:.0f} s")

    def log_tail(self, i: int, n: int = 12) -> None:
        path = os.path.join(self.info["nodes"][i]["dir"], "node.log")
        if os.path.exists(path):
            with open(path, errors="replace") as f:
                for ln in f.readlines()[-n:]:
                    print(f"    node{i} | {ln.rstrip()}", file=sys.stderr)

    def stop(self) -> list:
        """SIGTERM all, wait, SIGKILL stragglers -> exit codes (None for
        a process that had to be killed)."""
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        codes = []
        for p in self.procs:
            try:
                codes.append(p.wait(timeout=45))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
                codes.append(None)
        return codes


def batched(cli, calls: list, size: int) -> list:
    out = []
    for o in range(0, len(calls), size):
        for resp in cli.request_batch(calls[o:o + size]):
            check("error" not in resp, f"rpc error: {resp.get('error')}")
            out.append(resp["result"])
    return out


def stage_chain(args, device: dict, workdir: str) -> None:
    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.storage import make_storage
    from fisco_bcos_tpu.testing.scenario import (ACCOUNT_BALANCE,
                                                 ScenarioSpec,
                                                 prefund_storage)
    from fisco_bcos_tpu.tool.config import _load_node_parts

    rehearse = args.rehearse_cpu
    sz = sizes(rehearse)
    base = free_port_run(8, args.seed)
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "build_chain.py"),
         "-n", "4", "-o", workdir, "--consensus", "pbft",
         "--rpc-base-port", str(base), "--p2p-base-port", str(base + 4),
         "--crypto-backend", "auto,host,host,host"],
        check=True, capture_output=True, text=True)
    info = json.loads(out.stdout)
    if sz["batch"] > 256:
        # a client batch must reach the ingest lane as ONE device-sized
        # cohort, and the default [rpc] max_batch (256) is below the
        # default [crypto] device_min_batch (512)
        ini = os.path.join(info["nodes"][0]["dir"], "config.ini")
        cp = configparser.ConfigParser()
        cp.read(ini)
        cp["rpc"]["max_batch"] = str(sz["batch"])
        with open(ini, "w") as f:
            cp.write(f)
    spec = ScenarioSpec("hot-key", accounts=sz["accounts"])
    for n in info["nodes"]:
        cfg = _load_node_parts(n["dir"], None)[0]
        st = make_storage(cfg.storage_backend, cfg.storage_path)
        rows = prefund_storage(st, spec)
        st.close()
    say(f"chain: 4 nodes built, {rows} accounts prefunded per node, "
        f"node0 backend=auto, nodes 1-3 backend=host")

    cluster = Cluster(info, rehearse)
    try:
        cluster.start()
        t0 = time.monotonic()
        wires, moves = make_txs(args.seed, sz["txs"], sz["accounts"])
        say(f"chain: {len(wires)} transfers signed while the nodes start "
            f"({time.monotonic() - t0:.1f} s)")
        for i in (1, 2, 3):
            cluster.wait_ready(i, 120)
        ready_s, st0 = cluster.wait_ready(0, 900)
        c0 = st0["crypto"]
        say(f"chain: node0 ready {ready_s:.1f} s after start (crypto "
            f"warm-up {c0['readySeconds']} s, {c0['compilesAtReady']} "
            f"compiles, persistent cache {c0['cacheHits']} hits / "
            f"{c0['cacheMisses']} written; set-up, not speed)")
        check(c0["platform"] == device["platform"]
              and c0["deviceKind"] == device["kind"],
              f"node0 runs on {c0['platform']}/{c0['deviceKind']}")
        check(rehearse or c0["cacheHits"] > 0,
              "node0 started without one persistent-cache hit from the "
              "kernel stage")

        # -- load: concurrent batches over JSON-RPC to node0 ---------------
        group = info["group_id"]
        batches = [wires[o:o + sz["batch"]]
                   for o in range(0, len(wires), sz["batch"])]
        hashes: list = [None] * len(batches)
        errors: list = []

        def sender(k: int) -> None:
            cli = cluster.client(0)
            try:
                for b in range(k, len(batches), sz["senders"]):
                    res = batched(cli, [("sendTransaction",
                                         [group, "", w, False, False])
                                        for w in batches[b]], sz["batch"])
                    hashes[b] = [r["transactionHash"] for r in res]
            except Exception as exc:  # noqa: BLE001 — re-raised below
                errors.append(exc)

        threads = [threading.Thread(target=sender, args=(k,))
                   for k in range(sz["senders"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        tx_hashes = [h for b in hashes for h in b]
        check(len(set(tx_hashes)) == sz["txs"], "duplicate or missing txs")
        say(f"chain: {len(tx_hashes)} txs admitted by node0 in "
            f"{len(batches)} batches of {sz['batch']}")

        # -- every receipt, from node0 -------------------------------------
        cli0, cli1 = cluster.client(0), cluster.client(1)
        receipts: dict = {}
        deadline = time.monotonic() + 600
        while len(receipts) < len(tx_hashes):
            check(time.monotonic() < deadline,
                  f"{len(receipts)}/{len(tx_hashes)} receipts after 600 s")
            todo = [h for h in tx_hashes if h not in receipts]
            got = batched(cli0, [("getTransactionReceipt", [group, "", h])
                                 for h in todo], READ_BATCH)
            receipts.update({h: r for h, r in zip(todo, got) if r})
            if len(receipts) < len(tx_hashes):
                time.sleep(0.5)
        bad = [r for r in receipts.values() if r["status"] != 0]
        check(not bad, f"{len(bad)} receipts with non-zero status, e.g. "
                       f"{bad[:1]}")
        height = max(r["blockNumber"] for r in receipts.values())
        say(f"chain: {len(receipts)} receipts, all status 0, heights 1.."
            f"{height}")

        # -- node0 (device) vs node1 (host): every header ------------------
        check(cli0.get_block_number() >= height, "node0 behind its receipts")
        deadline = time.monotonic() + 120
        while cli1.get_block_number() < height:
            check(time.monotonic() < deadline, "node1 never reached node0")
            time.sleep(0.25)
        calls = [("getBlockHashByNumber", [group, "", n])
                 for n in range(height + 1)]
        h0, h1 = (batched(c, calls, READ_BATCH) for c in (cli0, cli1))
        check(all(h0) and h0 == h1,
              f"node0 and node1 disagree on a header hash: "
              f"{[n for n in range(height + 1) if h0[n] != h1[n]][:3]}")
        blocks = batched(cli0, [("getBlockByNumber", [group, "", n, False,
                                                      True])
                                for n in range(1, height + 1)], 100)
        counts = [len(b["transactions"]) for b in blocks]
        check(sum(counts) == sz["txs"] and max(counts) <= BATCH,
              f"block tx counts {counts}")
        say(f"chain: node0 == node1 at all {height + 1} heights; blocks "
            f"hold {counts} txs")

        # -- balances vs the sequential replay, on both nodes --------------
        want = replay(moves, ACCOUNT_BALANCE)
        accts = sorted(want)
        calls = [("call", [group, "", "0x" + pc.DAG_TRANSFER_ADDRESS.hex(),
                           "0x" + pc.encode_call(
                               "balanceOf", lambda w, a=a: w.blob(a)).hex()])
                 for a in accts]
        for name, cli in (("node0", cli0), ("node1", cli1)):
            got = {a: int(r["output"][2:], 16)
                   for a, r in zip(accts, batched(cli, calls, READ_BATCH))}
            diff = [a for a in accts if got[a] != want[a]]
            check(not diff, f"{name}: {len(diff)} balances differ from the "
                            f"replay, e.g. {diff[:3]}")
        say(f"chain: {len(accts)} touched balances equal the sequential "
            f"replay on node0 and node1")

        # -- where node0's crypto ran --------------------------------------
        c = cli0.request("getSystemStatus", [])["crypto"]
        ops = c["ops"]
        side = "host" if rehearse else "device"
        say(f"chain: node0 crypto status: platform={c['platform']} "
            f"pallas={c['pallas']} "
            + " ".join(f"{op}={v['deviceItems']}dev/{v['hostItems']}host"
                       for op, v in ops.items())
            + f" compiles={c['compiles']} afterReady="
              f"{c['compilesAfterReady']}")
        check(c["platform"] == device["platform"], "node0 moved platform")
        check(ops["recover"][f"{side}Items"] >= sz["txs"],
              f"node0 recovered {ops['recover'][f'{side}Items']} signatures "
              f"on the {side} path, {sz['txs']} txs were sent")
        check(ops["merkle"][f"{side}Calls"] >= len(counts),
              f"{ops['merkle'][f'{side}Calls']} {side} Merkle roots for "
              f"{len(counts)} blocks")
        check(c["compilesAfterReady"] == 0,
              f"{c['compilesAfterReady']} compilations after node0 "
              f"reported ready")
        check(c["pallas"] == ("off" if rehearse else "compiled"),
              f"pallas mode {c['pallas']}")
    except BaseException:
        for i in range(len(cluster.procs)):
            cluster.log_tail(i)
        raise
    finally:
        codes = cluster.stop()
    check(codes == [0, 0, 0, 0], f"daemon exit codes after SIGTERM: {codes}")
    say("chain: SIGTERM stopped all four daemons with exit code 0")


def main() -> int:
    global _TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal of the control flow (tier-1); "
                         "without it there is no CPU path")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--stage", choices=["probe", "kernels"],
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    try:
        import fisco_bcos_tpu  # noqa: F401 — the checkout must be here
    except ImportError as exc:
        print(f"chip_smoke: not in a bcos-tpu checkout: {exc}",
              file=sys.stderr)
        return 2
    if args.stage == "probe":
        stage_probe()
        return 0
    if args.stage == "kernels":
        _TAG = os.environ["CHIP_SMOKE_TAG"]
        stage_kernels(args.seed, args.rehearse_cpu)
        return 0

    t_all = time.monotonic()
    try:
        dev = run_child("probe", args, chip_env(args.rehearse_cpu), 180)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    _TAG = (f"[platform={dev['platform']} kind={dev['kind']} "
            f"count={dev['count']} jax={dev['jax']} jaxlib={dev['jaxlib']} "
            f"libtpu={dev['libtpu']}]")
    want = "cpu" if args.rehearse_cpu else "tpu"
    if dev["platform"] != want:
        print(f"chip_smoke: JAX reports platform {dev['platform']!r} "
              f"({dev['kind']}), this run needs {want!r}: no accelerator, "
              f"no result", file=sys.stderr)
        return 1
    os.environ["CHIP_SMOKE_TAG"] = _TAG
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        native_libs()
        t0 = time.monotonic()
        run_child("kernels", args, chip_env(args.rehearse_cpu), 900)
        say(f"kernels: stage done in {time.monotonic() - t0:.0f} s; the "
            f"chip is free again")
        t0 = time.monotonic()
        stage_chain(args, dev, workdir)
        say(f"chain: stage done in {time.monotonic() - t0:.0f} s")
    except (SmokeFailure, subprocess.CalledProcessError) as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    say(f"all stages passed in {time.monotonic() - t_all:.0f} s"
        + (" (CPU rehearsal: proves nothing about the chip)"
           if args.rehearse_cpu else ""))
    # the contract's last line: exactly these keys, the device as JAX
    # reported it to the probe; versions are on every tagged line above
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
