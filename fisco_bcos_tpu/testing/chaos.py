"""ChaosHarness — fault injection against a REAL multi-process chain.

The reference proves its robustness claims on chains of real OS processes
(build_chain.sh + start_all.sh, then kill/partition nodes); this module is
that loop as a library: it generates a deployment with tools/build_chain.py,
runs each node as `python -m fisco_bcos_tpu <node_dir>` (its own process,
real TCP p2p — SM-TLS when the chain is built with certs), talks to the
cluster over real JSON-RPC HTTP, and injects faults:

  * `kill(i)`            — SIGKILL, the kill -9 crash (no flush, no goodbye);
  * `terminate(i)`       — SIGTERM graceful shutdown;
  * `start(i)`           — (re)boot from the node's data directory, which
                           exercises WAL replay + consensus-log recovery +
                           block-sync catch-up;
  * `inject_link(i, j)`  — route the i<->j p2p link through a LinkProxy
                           that adds bounded delay and periodic connection
                           drops (configure BEFORE first start).

Assertion helpers read the chain through the RPC only — the harness never
reaches into node internals, so everything it observes is what a real
operator/SDK would see. Used by tests/test_chaos_e2e.py and the
`tools/sanitize_ci.sh --chaos` stage.
"""

from __future__ import annotations

import json
import os
import random
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def free_port_block(n: int, tries: int = 64) -> int:
    """A base port with n consecutive free ports (test-grade: racy against
    other allocators, so callers get a fresh block per attempt)."""
    for _ in range(tries):
        base = random.randint(20000, 55000)
        socks = []
        try:
            for i in range(n):
                socks.append(socket.create_server(("127.0.0.1", base + i)))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port block found")


class LinkProxy:
    """TCP forwarder for one p2p link: bounded delay, periodic drops, and
    runtime-togglable (a)symmetric blackholes.

    Transparent to SM-TLS (it moves opaque bytes), so it models a slow,
    flapping or PARTITIONED network, not a Byzantine peer: every
    `drop_every` forwarded chunks the connection is cut (both directions),
    which the gateway's reconnect-with-backoff path must absorb; every
    chunk is delayed by `delay` seconds (bounded latency).

    `blackhole(direction)` silently DISCARDS bytes in one or both pump
    directions — "fwd" is dialer->target, "rev" the reverse — modelling a
    gray link where A's frames reach B but B's never reach A. Discarding
    from a TLS/framed stream means the mangled direction's session dies on
    the next delivered byte after `heal()`, so healing also exercises the
    jittered reconnect path, exactly like a real partition healing."""

    def __init__(self, target_host: str, target_port: int,
                 delay: float = 0.0, drop_every: int = 0):
        self.target = (target_host, target_port)
        self.delay = delay
        self.drop_every = drop_every
        self._chunks = 0
        self._lock = threading.Lock()
        self._stopped = False
        self._blackholed: set[str] = set()  # "fwd" / "rev"
        self.discarded = 0  # bytes swallowed by blackholes
        self._listener = socket.create_server(("127.0.0.1", 0))
        self.port = self._listener.getsockname()[1]
        self.drops = 0
        # accept loop starts as the ctor's FINAL statement: every field it
        # (and the pumps it spawns) touches is assigned above, and chaos
        # harness objects are built-then-used inside a single test
        threading.Thread(  # bcoslint: disable=thread-start-in-ctor
            target=self._accept_loop, name="chaos-proxy",
            daemon=True).start()

    # -- partition control (runtime-safe) ----------------------------------
    def blackhole(self, direction: str = "both") -> None:
        """Start discarding bytes: "fwd" (dialer->target), "rev", "both"."""
        assert direction in ("fwd", "rev", "both"), direction
        with self._lock:
            self._blackholed |= ({"fwd", "rev"} if direction == "both"
                                 else {direction})

    def heal(self) -> None:
        with self._lock:
            self._blackholed.clear()

    def heal_after(self, seconds: float) -> threading.Timer:
        """Partition-heal schedule: clear the blackhole after `seconds`."""
        t = threading.Timer(seconds, self.heal)
        t.daemon = True
        t.start()
        return t

    def _accept_loop(self) -> None:
        while not self._stopped:
            try:
                client, _ = self._listener.accept()
            except OSError:
                return
            try:
                upstream = socket.create_connection(self.target, timeout=3)
            except OSError:
                client.close()
                continue
            for a, b, d in ((client, upstream, "fwd"),
                            (upstream, client, "rev")):
                threading.Thread(target=self._pump, args=(a, b, d),
                                 daemon=True).start()

    def _pump(self, src: socket.socket, dst: socket.socket,
              direction: str) -> None:
        while not self._stopped:
            try:
                chunk = src.recv(65536)
            except OSError:
                break
            if not chunk:
                break
            if self.delay:
                time.sleep(self.delay)
            with self._lock:
                self._chunks += 1
                cut = (self.drop_every
                       and self._chunks % self.drop_every == 0)
                if cut:
                    self.drops += 1
                holed = direction in self._blackholed
                if holed:
                    self.discarded += len(chunk)
            if cut:
                break  # fault: sever the whole connection mid-stream
            if holed:
                continue  # fault: one-way blackhole — bytes vanish
            try:
                dst.sendall(chunk)
            except OSError:
                break
        for s in (src, dst):
            try:
                s.close()
            except OSError:
                pass

    def stop(self) -> None:
        self._stopped = True
        try:
            self._listener.close()
        except OSError:
            pass


class ByzantinePeer:
    """A malicious speaker of the p2p wire protocol, aimed at one node's
    gateway seam (chains built WITHOUT TLS — with SM-TLS a stranger cannot
    even finish the transport handshake, which is its own, already-tested
    defense; this peer exercises the post-transport validation layers).

    It completes the plaintext handshake under a fabricated node id and
    then emits the adversarial stream the gateway/front/consensus stack
    must shrug off: garbage frames, corrupt compressed payloads, frames
    spoofing OTHER nodes' identities, consensus-module payloads that decode
    to nothing (the equivocating-pre-prepare/bad-seal-block stand-ins —
    inner signature checks reject anything unsigned-by-a-sealer, so at the
    gateway seam "signed garbage" and "unsigned equivocation" die in the
    same validation layer), and block-sync responses full of junk. The
    assertion is always the same: the chain keeps committing, converges,
    and `getAuditReport` stays clean."""

    def __init__(self, host: str, port: int, node_id: Optional[bytes] = None):
        from fisco_bcos_tpu.net import p2p as _p2p
        self._p2p = _p2p
        self.node_id = node_id or bytes([0xEE]) * 33
        self.sock = socket.create_connection((host, port), timeout=5)
        hello = (_p2p.MAGIC + bytes([_p2p.VERSION, 0]) + self.node_id)
        _p2p._send_frame(self.sock, hello)
        _p2p._recv_frame(self.sock)  # victim's hello

    def _raw(self, frame: bytes) -> bool:
        try:
            self._p2p._send_frame(self.sock, frame)
            return True
        except OSError:
            return False

    def send_garbage(self, n: int = 64) -> None:
        """Random byte soup inside valid length prefixes."""
        rnd = random.Random(0xBAD)
        for _ in range(n):
            self._raw(bytes(rnd.randrange(256)
                            for _ in range(rnd.randrange(1, 512))))

    def send_corrupt_frames(self, dst: bytes, n: int = 32) -> None:
        """Well-formed DATA frames whose compressed payload is garbage."""
        p2p = self._p2p
        rnd = random.Random(0xC0)
        for _ in range(n):
            junk = bytes(rnd.randrange(256) for _ in range(200))
            self._raw(p2p._pack_data(p2p.FLAG_COMPRESSED, p2p.MAX_TTL,
                                     self.node_id, dst, junk))

    def send_spoofed(self, src: bytes, dst: bytes, payload: bytes,
                     n: int = 8) -> None:
        """DATA frames claiming another node's identity as source."""
        p2p = self._p2p
        for _ in range(n):
            self._raw(p2p._pack_data(0, p2p.MAX_TTL, src, dst, payload))

    def send_module_junk(self, dst: bytes, module: int, n: int = 32) -> None:
        """Frames addressed to a real module (consensus pre-prepares,
        block-sync responses) with undecodable/unsigned bodies."""
        p2p = self._p2p
        rnd = random.Random(module)
        for _ in range(n):
            body = struct.pack(">H", module) + bytes(
                rnd.randrange(256) for _ in range(rnd.randrange(8, 300)))
            self._raw(p2p._pack_data(0, p2p.MAX_TTL, self.node_id, dst,
                                     body))

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


class ChaosHarness:
    # defaults tuned for a shared-core CI host running n_nodes full JAX
    # processes: rounds cost ~1 s there, so a mainnet-ish 3 s view timeout
    # produces view-change storms that slow the chain ~3x (every commit
    # pays one-plus view changes); 8 s keeps rounds in-view, and a longer
    # min_seal_time batches the trickle of RPC submits into fewer blocks
    def __init__(self, out_dir: str, n_nodes: int = 4, tls: bool = True,
                 view_timeout: float = 8.0, min_seal_time: float = 0.2,
                 sm_crypto: bool = False,
                 config_overrides: Optional[dict] = None):
        sys.path.insert(0, os.path.join(_REPO_ROOT, "tools"))
        from build_chain import build_chain

        self.out_dir = out_dir
        self.n = n_nodes
        # ONE contiguous block split in two: two independent draws could
        # overlap each other (nothing holds the first block while the
        # second is probed) and hand a port to both RPC and p2p
        base = free_port_block(2 * n_nodes)
        rpc_base, p2p_base = base, base + n_nodes
        self.info = build_chain(
            out_dir, n_nodes, sm_crypto=sm_crypto, consensus="pbft",
            rpc_base_port=rpc_base, p2p_base_port=p2p_base,
            crypto_backend="host", sm_tls=tls)
        self.tls = tls
        for node in self.info["nodes"]:
            self._patch_config(node["dir"], view_timeout=view_timeout,
                               min_seal_time=min_seal_time,
                               **(config_overrides or {}))
        self.procs: list[Optional[subprocess.Popen]] = [None] * n_nodes
        self.proxies: list[LinkProxy] = []

    # -- config surgery ----------------------------------------------------
    def _patch_config(self, node_dir: str, **overrides) -> None:
        from fisco_bcos_tpu.tool.config import (node_config_from_ini,
                                                node_config_to_ini)
        path = os.path.join(node_dir, "config.ini")
        with open(path) as f:
            cfg = node_config_from_ini(f.read())
        for k, v in overrides.items():
            setattr(cfg, k, v)
        with open(path, "w") as f:
            f.write(node_config_to_ini(cfg))

    def inject_link(self, i: int, j: int, delay: float = 0.0,
                    drop_every: int = 0) -> LinkProxy:
        """Interpose a LinkProxy on the i<->j p2p link (call before the
        nodes start). The gateway's deterministic dial direction means only
        the smaller-node-id side dials, so only the dialer's peer entry is
        rewritten to point at the proxy."""
        ids = [bytes.fromhex(n["node_id"]) for n in self.info["nodes"]]
        dialer, target = (i, j) if ids[i] < ids[j] else (j, i)
        tport = self.info["nodes"][target]["p2p_port"]
        proxy = LinkProxy("127.0.0.1", tport, delay=delay,
                          drop_every=drop_every)
        proxy.dialer, proxy.target_node = dialer, target
        self.proxies.append(proxy)
        from fisco_bcos_tpu.tool.config import node_config_from_ini
        node_dir = self.info["nodes"][dialer]["dir"]
        with open(os.path.join(node_dir, "config.ini")) as f:
            peers = node_config_from_ini(f.read()).p2p_peers
        self._patch_config(node_dir, p2p_peers=[
            ("127.0.0.1", proxy.port) if p == tport else (h, p)
            for h, p in peers])
        return proxy

    def partition_link(self, proxy: LinkProxy, src: int,
                       dst: Optional[int] = None) -> None:
        """Asymmetric partition over an injected proxy: drop src->dst
        traffic (dst defaults to the proxy's other endpoint) while the
        reverse direction keeps flowing. Symmetric: proxy.blackhole().
        Heal with proxy.heal() or schedule it with proxy.heal_after()."""
        direction = "fwd" if src == proxy.dialer else "rev"
        proxy.blackhole(direction)

    def byzantine_peer(self, i: int) -> ByzantinePeer:
        """Connect a Byzantine speaker to node i's p2p port (chains built
        with tls=False only — TLS rejects strangers at the transport)."""
        assert not self.tls, "ByzantinePeer needs a tls=False chain"
        return ByzantinePeer("127.0.0.1", self.info["nodes"][i]["p2p_port"])

    def node_id(self, i: int) -> bytes:
        return bytes.fromhex(self.info["nodes"][i]["node_id"])

    # -- process control ---------------------------------------------------
    def start(self, i: int, failpoints: str = "") -> None:
        """(Re)boot node i. `failpoints` arms `site=action;...` at boot
        via the BCOS_FAILPOINTS env (utils/failpoints.py) — how a crash
        matrix plants `crash` actions inside a real OS process."""
        assert self.procs[i] is None or self.procs[i].poll() is not None, \
            f"node{i} already running"
        node_dir = self.info["nodes"][i]["dir"]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # chaos clusters never take the chip
        # test build: the ops endpoint may arm/disarm failpoints at runtime
        env["BCOS_FAILPOINTS_OPS"] = "1"
        if failpoints:
            env["BCOS_FAILPOINTS"] = failpoints
        else:
            env.pop("BCOS_FAILPOINTS", None)
        env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH",
                                                              "")
        out = open(os.path.join(node_dir, "daemon.out"), "ab")
        self.procs[i] = subprocess.Popen(
            [sys.executable, "-m", "fisco_bcos_tpu", node_dir,
             "--log-file", os.path.join(node_dir, "daemon.log")],
            stdout=out, stderr=out, env=env, cwd=_REPO_ROOT)
        out.close()

    def start_all(self) -> None:
        for i in range(self.n):
            self.start(i)

    def kill(self, i: int) -> None:
        """kill -9: no WAL flush, no session goodbyes, pid file left behind."""
        p = self.procs[i]
        if p is not None and p.poll() is None:
            p.send_signal(signal.SIGKILL)
            p.wait(timeout=30)
        self.procs[i] = None

    def wipe_data(self, i: int) -> None:
        """Disk loss: destroy the node's data directory (WAL, snapshots,
        consensus log — everything below [storage] path). The node's keys
        and config survive, so a restart is the disaster-recovery path:
        genesis bootstrap, then catch-up (snap-sync when far behind)."""
        import shutil
        assert self.procs[i] is None or self.procs[i].poll() is not None, \
            f"refusing to wipe node{i} while it is running"
        shutil.rmtree(os.path.join(self.info["nodes"][i]["dir"], "data"),
                      ignore_errors=True)

    def terminate(self, i: int, timeout: float = 30.0) -> int:
        """SIGTERM graceful shutdown; returns the exit code."""
        p = self.procs[i]
        if p is None:
            return 0
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=timeout)
        self.procs[i] = None
        return rc

    def stop_all(self) -> None:
        for i in range(self.n):
            p = self.procs[i]
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGKILL)
        for i in range(self.n):
            p = self.procs[i]
            if p is not None:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass
            self.procs[i] = None
        for proxy in self.proxies:
            proxy.stop()

    # -- RPC-side observation ----------------------------------------------
    def client(self, i: int):
        from fisco_bcos_tpu.sdk.client import SdkClient
        port = self.info["nodes"][i]["rpc_port"]
        return SdkClient(f"http://127.0.0.1:{port}",
                         group=self.info["group_id"])

    def suite(self):
        from fisco_bcos_tpu.crypto.suite import make_suite
        return make_suite(self.info["sm_crypto"], backend="host")

    def wait_rpc_up(self, i: int, timeout: float = 120.0) -> None:
        cli = self.client(i)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                cli.get_block_number()
                return
            except Exception:
                time.sleep(0.25)
        raise TimeoutError(f"node{i} RPC not up within {timeout}s "
                           f"(see {self.info['nodes'][i]['dir']}/daemon.log)")

    def block_number(self, i: int) -> int:
        return self.client(i).get_block_number()

    def block_hash(self, i: int, number: int) -> Optional[str]:
        return self.client(i).request(
            "getBlockHashByNumber", [self.info["group_id"], "", number])

    def state_root(self, i: int, number: int) -> Optional[str]:
        blk = self.client(i).get_block_by_number(number, only_header=True)
        return blk["stateRoot"] if blk else None

    def snapshot_status(self, i: int) -> dict:
        return self.client(i).request(
            "getSnapshotStatus", [self.info["group_id"], ""])

    # -- robustness plane (ops GET routes + audit RPC) ---------------------
    def _ops_get(self, i: int, path: str) -> tuple[int, dict]:
        import urllib.error
        import urllib.request
        url = (f"http://127.0.0.1:{self.info['nodes'][i]['rpc_port']}"
               f"{path}")
        try:
            with urllib.request.urlopen(url, timeout=10) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as exc:  # 503 healthz still has JSON
            return exc.code, json.loads(exc.read() or b"{}")

    def arm_failpoint(self, i: int, site: str, action: str) -> dict:
        """Arm a failpoint on a RUNNING node over its ops endpoint (the
        harness always starts nodes with BCOS_FAILPOINTS_OPS=1)."""
        from urllib.parse import quote
        code, doc = self._ops_get(
            i, f"/failpoints?arm={quote(site + '=' + action)}")
        assert code == 200, (code, doc)
        return doc

    def disarm_failpoints(self, i: int) -> None:
        self._ops_get(i, "/failpoints?disarm=all")

    def failpoints(self, i: int) -> dict:
        return self._ops_get(i, "/failpoints")[1]

    def healthz(self, i: int) -> tuple[int, dict]:
        """-> (http_status, health doc): 200 while ok, 503 degraded."""
        return self._ops_get(i, "/healthz")

    def metrics_text(self, i: int) -> str:
        import urllib.request
        url = (f"http://127.0.0.1:{self.info['nodes'][i]['rpc_port']}"
               "/metrics")
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.read().decode()

    def audit_report(self, i: int) -> dict:
        return self.client(i).request(
            "getAuditReport", [self.info["group_id"], ""])

    def total_txs(self, i: int) -> int:
        return self.client(i).get_total_transaction_count()[
            "transactionCount"]

    def wait_until(self, pred, timeout: float = 60.0,
                   what: str = "condition") -> None:
        deadline = time.monotonic() + timeout
        last_exc: Optional[Exception] = None
        while time.monotonic() < deadline:
            try:
                if pred():
                    return
                last_exc = None
            except Exception as exc:  # RPC flaps during faults are expected
                last_exc = exc
            time.sleep(0.25)
        raise TimeoutError(f"timed out waiting for {what}"
                           + (f" (last error: {last_exc})" if last_exc
                              else ""))

    def wait_converged(self, idxs, min_height: int = 1,
                       timeout: float = 120.0) -> int:
        """Wait until every node in `idxs` reports the SAME head hash at the
        max common height >= min_height; returns that height."""
        result = {}

        def same_head() -> bool:
            numbers = [self.block_number(i) for i in idxs]
            h = min(numbers)
            if h < min_height:
                return False
            hashes = {self.block_hash(i, h) for i in idxs}
            if None in hashes or len(hashes) != 1:
                return False
            result["height"] = h
            return True

        self.wait_until(same_head, timeout=timeout,
                        what=f"nodes {list(idxs)} converged")
        return result["height"]

    def read_daemon_log(self, i: int) -> str:
        path = os.path.join(self.info["nodes"][i]["dir"], "daemon.log")
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            return ""

    def __enter__(self) -> "ChaosHarness":
        return self

    def __exit__(self, *exc) -> None:
        self.stop_all()


def main() -> None:  # pragma: no cover — operator smoke entry
    import argparse
    import tempfile

    ap = argparse.ArgumentParser(
        description="boot a 4-node chaos chain, kill -9 a node, rejoin it")
    ap.add_argument("-o", "--output", default=None)
    ap.add_argument("--no-tls", action="store_true")
    args = ap.parse_args()
    out = args.output or tempfile.mkdtemp(prefix="chaos-chain-")
    with ChaosHarness(out, tls=not args.no_tls) as h:
        h.start_all()
        for i in range(h.n):
            h.wait_rpc_up(i)
        print(json.dumps({"chain": out, "nodes": h.info["nodes"]}, indent=2))
        h.kill(3)
        print("node3 killed (SIGKILL); restarting...")
        h.start(3)
        h.wait_rpc_up(3)
        height = h.wait_converged(range(h.n), min_height=0)
        print(f"converged at height {height}")


if __name__ == "__main__":  # pragma: no cover
    main()
