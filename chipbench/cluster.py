"""The deployment under test: build the chain, start four daemons, stop them.

Copied in shape from chip_smoke.py's chain stage (the original stays where
it is; see PERF.md, Open questions). The parent pins itself to the CPU
before the package import can load JAX and never initialises a backend;
node0 alone gets the outside's JAX environment back and owns the chip.
"""

from __future__ import annotations

import configparser
import http.client
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time

from manifest import workload
from rpc import Rpc, RpcError

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LAUNCHER = os.path.join(HERE, "node0_launcher.py")


class ClusterError(Exception):
    pass


def free_port_run(n: int, seed: int) -> int:
    """-> base of n consecutive TCP ports that are free right now, below
    the range the kernel hands to outgoing connections (32768 up): a dial
    between this look and a daemon's bind cannot take one."""
    rng = random.Random(seed ^ os.getpid())
    for _ in range(200):
        base = rng.randrange(20000, 32000)
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
    raise ClusterError(f"no run of {n} free ports found")


def proc_cpu_seconds(pid: int) -> float:
    """utime + stime of a process, all its threads, from /proc."""
    with open(f"/proc/{pid}/stat") as f:
        rest = f.read().rsplit(")", 1)[1].split()
    return (int(rest[11]) + int(rest[12])) / os.sysconf("SC_CLK_TCK")


class Cluster:
    """Four node daemons as OS processes. `stop()` always reaps them."""

    def __init__(self, config: dict, workdir: str, seed: int,
                 outside_jax_platforms: str | None, rehearse: bool,
                 trace: bool, node_launcher: str | None = None):
        self.config = config
        self.workdir = workdir
        self.rehearse = rehearse
        self.trace = trace
        self.outside = outside_jax_platforms
        # tests start every node through a launcher of theirs to break
        # the timed path underneath; a run never sets this
        self.node_launcher = node_launcher
        self.procs: list[subprocess.Popen] = []
        self.t_start: list[float] = []
        self.trace_dir = os.path.join(workdir, "trace")
        self.info_path = os.path.join(workdir, "node0_device.json")
        self.info = self._build(seed)
        self.group = self.info["group_id"]

    # -- build ---------------------------------------------------------------
    def _build(self, seed: int) -> dict:
        base = free_port_run(8, seed)
        chain_dir = os.path.join(self.workdir, "chain")
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "build_chain.py"),
             "-o", chain_dir, "--rpc-base-port", str(base),
             "--p2p-base-port", str(base + 4), *self.config["build_chain"]],
            capture_output=True, text=True, env=self._env(chip=False))
        if out.returncode != 0:
            raise ClusterError(f"build_chain failed: {out.stderr[-2000:]}")
        info = json.loads(out.stdout)
        for node, sections in self.config.get("config_ini", {}).items():
            ini = os.path.join(info["nodes"][int(node)]["dir"], "config.ini")
            cp = configparser.ConfigParser()
            cp.read(ini)
            for section, kv in sections.items():
                for k, v in kv.items():
                    cp[section][k] = str(v)
            with open(ini, "w") as f:
                cp.write(f)
        self._prefund(info)
        return info

    def _prefund(self, info: dict) -> None:
        """What the configuration's transaction kind wants in every
        node's storage before its first block."""
        from fisco_bcos_tpu.storage import make_storage
        from fisco_bcos_tpu.tool.config import _load_node_parts

        kind = workload(self.config)
        for n in info["nodes"]:
            cfg = _load_node_parts(n["dir"], None)[0]
            st = make_storage(cfg.storage_backend, cfg.storage_path)
            try:
                kind.prefund(st, self.config)
            except ValueError as exc:
                raise ClusterError(str(exc)) from exc
            finally:
                st.close()

    # -- processes -----------------------------------------------------------
    def _env(self, chip: bool) -> dict:
        env = dict(os.environ)
        path = [ROOT, env.get("PYTHONPATH", "")]
        codec = self.config.get("p2p_codec", "zstd")
        if codec == "zlib":  # the program's fallback where zstandard is absent
            path.insert(0, os.path.join(HERE, "no_zstd"))
        elif codec != "zstd":
            raise ClusterError(f"p2p_codec {codec!r} is not zstd or zlib")
        env["PYTHONPATH"] = os.pathsep.join(path)
        env["JAX_PLATFORMS"] = "cpu"
        if chip and not self.rehearse:
            env.pop("JAX_PLATFORMS")
            if self.outside is not None:
                env["JAX_PLATFORMS"] = self.outside
        return env

    def start(self) -> None:
        for i, n in enumerate(self.info["nodes"]):
            daemon = [n["dir"], "--log-file",
                      os.path.join(n["dir"], "node.log")]
            if self.node_launcher is not None:
                cmd = [sys.executable, self.node_launcher, *daemon]
            elif i == 0:
                cmd = [sys.executable, LAUNCHER, "--info", self.info_path]
                if self.trace:
                    cmd += ["--trace-dir", self.trace_dir]
                cmd += ["--", *daemon]
            else:
                cmd = [sys.executable, "-m", "fisco_bcos_tpu", *daemon]
            self.t_start.append(time.monotonic())
            # a daemon logs to its file; what it writes to stderr is a
            # crash, kept for log_tail
            with open(os.path.join(n["dir"], "node.stderr"), "wb") as err:
                self.procs.append(subprocess.Popen(
                    cmd, env=self._env(chip=(i == 0)), cwd=ROOT,
                    stdout=subprocess.DEVNULL, stderr=err))

    def port(self, i: int) -> int:
        return self.info["nodes"][i]["rpc_port"]

    def rpc(self, i: int, timeout: float = 60.0) -> Rpc:
        return Rpc(self.port(i), timeout)

    def wait_ready(self, i: int, timeout: float) -> dict:
        """-> the first answered getSystemStatus. The RPC port opens only
        after the crypto warm-up."""
        cli = self.rpc(i, 10.0)
        deadline = time.monotonic() + timeout
        try:
            while time.monotonic() < deadline:
                rc = self.procs[i].poll()
                if rc is not None:
                    raise ClusterError(f"node{i} exited with code {rc} "
                                       f"before it was ready")
                try:
                    return cli.call("getSystemStatus", [])
                except (OSError, http.client.HTTPException, RpcError):
                    time.sleep(0.25)
        finally:
            cli.close()
        raise ClusterError(f"node{i} not ready after {timeout:.0f} s")

    def wait_all_ready(self) -> dict:
        """The host replicas first, then node0 (it compiles) -> node0's
        status."""
        for i in range(1, len(self.procs)):
            self.wait_ready(i, 180)
        return self.wait_ready(0, 1150)

    def cpu_seconds(self) -> list[float]:
        return [proc_cpu_seconds(p.pid) for p in self.procs]

    def signal_node0(self, sig: int) -> None:
        self.procs[0].send_signal(sig)

    def log_tail(self, i: int, n: int = 15) -> str:
        """The end of node i's log and of its stderr, and whether it runs."""
        out = [f"    node{i} | exit code {self.procs[i].poll()} "
               f"(None: running)\n"]
        for name in ("node.log", "node.stderr"):
            path = os.path.join(self.info["nodes"][i]["dir"], name)
            if os.path.exists(path):
                with open(path, errors="replace") as f:
                    out += [f"    node{i} {name} | {ln}"
                            for ln in f.readlines()[-n:]]
        return "".join(out)

    def stop(self) -> list:
        """SIGTERM all, wait, SIGKILL stragglers -> exit codes (None for a
        process that had to be killed)."""
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
