"""What the chip bring-up changed, as far as a CPU can show it: where the
compile cache goes, what `auto` / `device` mean without a TPU, what the
`crypto` status block counts, what a failed device call does to the node,
how a JSON-RPC batch reaches the ingest lane, and chip_smoke.py's two exits.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from fisco_bcos_tpu.crypto.suite import (COMPILE_LOG, CryptoSuite,
                                         DeviceError, DeviceUnavailable)
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.protocol import Transaction

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, env_drop=(), timeout=120, cwd=REPO):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def _signed(suite, n, uniq=8):
    kps = [suite.generate_keypair(bytes([i + 1]) * 8) for i in range(uniq)]
    digs = [suite.hash(b"bring-up-%d" % i) for i in range(uniq)]
    sigs = [suite.sign(kp, d) for kp, d in zip(kps, digs)]
    reps = -(-n // uniq)
    return (digs * reps)[:n], (sigs * reps)[:n]


def test_cache_dir_comes_from_outside():
    show = ["-c", "import fisco_bcos_tpu, jax; "
                  "print(jax.config.jax_compilation_cache_dir)"]
    r = _run(show, {"JAX_COMPILATION_CACHE_DIR": "/x"})
    assert r.stdout.strip() == "/x", r.stderr[-500:]
    r = _run(show, env_drop=("JAX_COMPILATION_CACHE_DIR",))
    assert r.stdout.strip() == os.path.join(REPO, ".jax_cache")
    # and nowhere else does the package set a cache path
    src = open(os.path.join(REPO, "fisco_bcos_tpu", "__init__.py")).read()
    assert src.count("jax_compilation_cache_dir") == 1
    assert "FBTPU_JAX_CACHE_DIR" not in src


def test_auto_without_a_tpu_stays_on_the_host():
    suite = CryptoSuite(backend="auto")
    digs, sigs = _signed(suite, 1000)
    before = COMPILE_LOG.snapshot()["compiles"]
    pubs, ok = suite.recover_batch(digs, sigs)
    assert ok.all() and pubs[0] is not None
    st = suite.status()
    assert st["platform"] == "cpu" and st["pallas"] == "off"
    row = st["ops"]["recover"]
    parts = row.pop("hostParts")   # native calls: follows this host's cores
    assert row.pop("hostSplitItems") == (1000 if parts > 1 else 0)
    assert row == {"deviceCalls": 0, "deviceItems": 0,
                   "deviceLanes": 0,
                   "hostCalls": 1, "hostItems": 1000,
                   "packSeconds": 0.0, "callSeconds": 0.0,
                   "unpackSeconds": 0.0}
    assert COMPILE_LOG.snapshot()["compiles"] == before  # nothing to XLA:CPU


def test_device_without_a_tpu_refuses():
    suite = CryptoSuite(backend="device")
    with pytest.raises(DeviceUnavailable, match="platform 'cpu'"):
        suite.prepare()
    with pytest.raises(DeviceUnavailable):
        suite.hash_batch([b"x"])


def test_device_daemon_without_a_tpu_exits_nonzero(tmp_path):
    r = _run([os.path.join(REPO, "tools", "build_chain.py"), "-n", "1",
              "-o", str(tmp_path), "--consensus", "solo",
              "--p2p-base-port", "39871", "--crypto-backend", "device"])
    assert r.returncode == 0, r.stderr[-500:]
    r = _run(["-m", "fisco_bcos_tpu", str(tmp_path / "node0")],
             {"JAX_PLATFORMS": "cpu"}, timeout=60)
    assert r.returncode == 3, (r.returncode, r.stderr[-800:])
    assert "boot-refused" in r.stderr and "no TPU" in r.stderr


def test_hash_batch_bucket_compiles_once_and_is_counted():
    """Two batch sizes >= 512 inside one bucket are ONE program, and the
    status block says how many items went to the JAX kernels."""
    dev = CryptoSuite(backend="device", allow_cpu=True)
    host = CryptoSuite(backend="host")
    rng = np.random.default_rng(3)
    msgs = [rng.bytes(int(rng.integers(1, 130))) for _ in range(900)]
    before = COMPILE_LOG.snapshot()["compiles"]
    assert dev.hash_batch(msgs[:600]) == host.hash_batch(msgs[:600])
    first = COMPILE_LOG.snapshot()["compiles"]
    assert first == before + 1
    assert dev.hash_batch(msgs) == host.hash_batch(msgs)
    # a message past HASH_MAX_BLOCKS rides the host hasher, same program
    mixed = msgs[:599] + [rng.bytes(5000)]
    assert dev.hash_batch(mixed) == host.hash_batch(mixed)
    assert COMPILE_LOG.snapshot()["compiles"] == first
    ops = dev.status()["ops"]["hash"]
    assert (ops["deviceCalls"], ops["deviceItems"]) == (3, 600 + 900 + 599)
    assert (ops["hostCalls"], ops["hostItems"]) == (1, 1)


def test_system_status_has_the_crypto_block():
    node = Node(NodeConfig(crypto_backend="host"))
    try:
        node.suite.hash_batch([b"a", b"b"])
        c = node.system_status()["crypto"]
        assert c["backend"] == "host" and c["platform"] is None
        assert c["ops"]["hash"]["hostItems"] == 2
        assert c["ops"]["hash"]["deviceItems"] == 0
        assert {"compiles", "compileSeconds", "cacheHits", "pallas",
                "deviceKind", "deviceCount"} <= set(c)
    finally:
        node.storage.close() if hasattr(node.storage, "close") else None


def test_failed_device_call_in_a_lane_batch_fails_the_node(monkeypatch):
    """A kernel that does not compile is not 'invalid signatures': the
    lane survives by rejecting the batch, the health plane goes `failed`
    with the reason, and writes are shed."""
    from fisco_bcos_tpu.ops import ec

    suite = CryptoSuite(backend="device", allow_cpu=True)
    node = Node(NodeConfig(min_seal_time=0.0), suite=suite)
    monkeypatch.setattr(suite, "prepare", lambda *a, **k: None)

    def boom(*_a, **_k):
        raise NotImplementedError("Unimplemented primitive in Pallas TPU "
                                  "lowering: dynamic_slice")

    monkeypatch.setattr(ec, "ecdsa_recover_batch", boom)
    node.start()
    try:
        host = CryptoSuite(backend="host")
        kp = host.generate_keypair(b"lane-fail")
        tx = Transaction(
            to=pc.BALANCE_ADDRESS, nonce="lf-1", block_limit=100,
            input=pc.encode_call("register",
                                 lambda w: w.blob(b"lf").u64(1))
        ).sign(host, kp)
        with pytest.raises(DeviceError, match="dynamic_slice"):
            node.ingest.submit_wire(tx.encode(), timeout=30)
        snap = node.health.snapshot()
        assert snap["state"] == "failed"
        assert "dynamic_slice" in snap["faults"]["crypto.device"]["reason"]
        assert node.health.writes_shed()
    finally:
        node.stop()


def test_rpc_batch_enters_the_lane_as_one_cohort():
    """A JSON-RPC batch's sendTransaction entries reach the batch recover
    together — one by one they were a batch of one each."""
    from fisco_bcos_tpu.sdk.client import SdkClient

    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           rpc_port=0))
    node.start()
    try:
        kp = node.suite.generate_keypair(b"cohort")
        wires = ["0x" + Transaction(
            to=pc.BALANCE_ADDRESS, nonce=f"co-{i}", block_limit=100,
            input=pc.encode_call("register", lambda w, i=i: w.blob(
                b"co-%d" % i).u64(1))).sign(node.suite, kp).encode().hex()
            for i in range(40)]
        cli = SdkClient(f"http://127.0.0.1:{node.rpc.port}")
        out = cli.request_batch([("sendTransaction",
                                  ["group0", "", w, False, False])
                                 for w in wires])
        assert all("error" not in r for r in out), out[:2]
        st = node.ingest.stats()
        assert (st["txs_total"], st["batches_total"]) == (40, 1), st
        for r in out:
            rc = node.txpool.wait_for_receipt(
                bytes.fromhex(r["result"]["transactionHash"][2:]), 20)
            assert rc is not None and rc.status == 0
    finally:
        node.stop()


def test_chip_smoke_has_no_cpu_path_without_the_flag(tmp_path):
    smoke = os.path.join(REPO, "chip_smoke.py")
    r = _run([smoke], {"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert r.returncode != 0
    assert "no accelerator, no result" in r.stderr
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    # alone in a directory, without the program: fails as well
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(smoke).read())
    r = subprocess.run([sys.executable, str(lone)], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60,
                       env={k: v for k, v in os.environ.items()
                            if k != "PYTHONPATH"})
    assert r.returncode != 0 and not r.stdout.strip()


def test_chip_smoke_rehearsal_passes_on_cpu():
    r = _run([os.path.join(REPO, "chip_smoke.py"), "--rehearse-cpu"],
             timeout=300)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    lines = r.stdout.splitlines()
    assert all("platform=cpu" in ln for ln in lines[:-1])
    # the driver's contract: exactly these keys, nothing beside them
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    assert "CPU rehearsal" in lines[-2]
    assert any("48 receipts, all status 0" in ln for ln in lines)
