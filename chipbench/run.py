#!/usr/bin/env python3
"""chipbench — one cell of the served chain, once, on the chip.

    python3 chipbench/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the cell's chain in a fresh directory under TMPDIR, starts four node
daemons (node0 through node0_launcher.py owns the chip; nodes 1-3 run host
crypto on the CPU), signs the window's transactions while node0 compiles,
waits for ready, warms up, measures for --seconds on the client's clock,
reads the answers back from all four nodes and compares them with the
plain reference (reference.py), stops the daemons with SIGTERM and prints
one JSON object as the last line of stdout. With --trace 1 a few seconds
of the window are traced inside node0 and the per-layer metrics are
printed instead of the end-to-end ones.

This process pins itself to the CPU before the package import can load
JAX and never initialises a backend. A run whose node0 does not report
platform `tpu` with the chips the cell asks for exits non-zero and prints
no result; there is no fallback. `--rehearse-cpu` (tests, and the builder
before a chip call) drives the same control flow at a tiny size on the
CPU; its last line says it is no result and carries no device metric.
"""

from __future__ import annotations

import os
import sys
import time

T_PROCESS_START = time.monotonic()
# the parent holds no chip; node0 gets the outside's setting back
OUTSIDE_JAX_PLATFORMS = os.environ.get("JAX_PLATFORMS")
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import answers as answers_mod  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
from cluster import Cluster, ClusterError  # noqa: E402
from manifest import Manifest, ManifestError, peaks  # noqa: E402
from stats import percentile  # noqa: E402
from traffic import make_load  # noqa: E402

TRACE_SECONDS = 10.0       # traced part of the window ...
TRACE_STARTS_AT = 0.25     # ... starting this far into it
REHEARSAL = {"config": {"accounts": 256},
             "traffic": {"batch": 24, "presign_tx_per_s": 2500,
                         "rate_tx_per_s": 20, "connections": 4}}


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


class RunFailure(Exception):
    """The run gives no result (exit code 1)."""


def wait_for_file(path: str, timeout: float) -> bool:
    deadline = time.monotonic() + timeout
    while not os.path.exists(path) and time.monotonic() < deadline:
        time.sleep(0.05)
    return os.path.exists(path)


class Observer:
    """Reads the program's counters over RPC at fixed instants of the
    window, from a thread of its own: node status and process CPU when the
    window opens and closes, and around the traced seconds."""

    def __init__(self, cluster: Cluster, trace: bool):
        self.cluster, self.trace = cluster, trace
        self.snap: dict = {}
        self.error: BaseException | None = None
        self._thread: threading.Thread | None = None

    def take(self, key: str, nodes=(0, 1)) -> None:
        status = {}
        for k in nodes:
            cli = self.cluster.rpc(k, 30.0)
            try:
                status[str(k)] = cli.call("getSystemStatus", [])
            finally:
                cli.close()
        self.snap[key] = {"status": status,
                          "cpu": self.cluster.cpu_seconds()}

    def _run(self, t0: float, t1: float) -> None:
        try:
            if self.trace:
                length = min(TRACE_SECONDS, 0.5 * (t1 - t0))
                time.sleep(max(0.0, t0 + TRACE_STARTS_AT * (t1 - t0)
                               - time.monotonic()))
                self.cluster.signal_node0(signal.SIGUSR1)
                if not wait_for_file(
                        self.cluster.info_path + ".trace_on", 30):
                    raise RunFailure("node0 did not start its trace")
                # the counters' window lies inside the traced one
                self.take("trace_before", (0,))
                time.sleep(length)
                self.take("trace_after", (0,))
                self.cluster.signal_node0(signal.SIGUSR2)
            time.sleep(max(0.0, t1 - time.monotonic()))
            self.take("after")
        except BaseException as exc:  # noqa: BLE001 — re-raised by join()
            self.error = exc

    def start(self, t0: float, t1: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0, t1))
        self._thread.start()

    def join(self) -> None:
        self._thread.join()
        if self.error is not None:
            raise self.error


def read_trace(cluster: Cluster) -> dict | None:
    """The traced seconds, reduced; None where node0 wrote no trace."""
    marker = cluster.info_path + ".trace"
    path = trace_reduce.find_xplane(cluster.trace_dir)
    if not os.path.exists(marker) or path is None:
        log(f"no trace: marker {os.path.exists(marker)}, xplane {path}")
        return None
    with open(marker) as f:
        times = json.load(f)
    if "error" in times:
        log(f"node0's trace failed: {times['error']}")
        return None
    red = trace_reduce.reduce(trace_reduce.load_xplane(path))
    if red is None:
        return None
    red["window_s"] = times["stopped"] - times["started"]
    return red


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal of the control flow; no result")
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0,
                    help="also judge the answers with each guarantee "
                         "broken (the builder's chip runs and the tests; "
                         "the driver's runs leave it off)")
    ap.add_argument("--node-launcher", help=argparse.SUPPRESS)
    args = ap.parse_args()
    try:
        import fisco_bcos_tpu  # noqa: F401 — the checkout must be here
        man = Manifest()
        cell = man.cell(args.workload)
        config = man.config(cell["config"])
        traffic = man.traffic(cell["traffic"])
        man.workload(config)  # both files of its kind
        e2e = man.end_to_end(cell["name"])
        per_layer = man.per_layer(cell["name"])
    except (ImportError, ManifestError, KeyError) as exc:
        log(f"not a runnable checkout: {exc!r}")
        return 2
    rehearse = args.rehearse_cpu
    if rehearse:
        config.update(REHEARSAL["config"])
        traffic.update({k: v for k, v in REHEARSAL["traffic"].items()
                        if k in traffic})

    def on_sigterm(*_):  # the finally blocks stop the daemons
        raise SystemExit(143)

    signal.signal(signal.SIGTERM, on_sigterm)
    workdir = tempfile.mkdtemp(prefix="chipbench_")
    try:
        return run_cell(args, cell, config, traffic, e2e, per_layer,
                        workdir, rehearse)
    except (RunFailure, ClusterError) as exc:
        log(f"FAILED, no result: {exc}")
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def run_cell(args, cell, config, traffic, e2e, per_layer, workdir,
             rehearse) -> int:
    from txgen import TxMaker

    cluster = Cluster(config, workdir, args.seed, OUTSIDE_JAX_PLATFORMS,
                      rehearse, trace=bool(args.trace),
                      node_launcher=args.node_launcher)
    maker = TxMaker(config, args.seed)
    load = make_load(traffic, cluster, maker, args.seed, args.seconds)
    obs = Observer(cluster, bool(args.trace) and not args.node_launcher)
    codes = None
    try:
        cluster.start()
        t = time.monotonic()
        load.presign()
        log(f"{len(load.requests)} transactions signed while the nodes "
            f"start ({time.monotonic() - t:.1f} s)")
        st0 = cluster.wait_all_ready()
        c0 = st0["crypto"]
        log(f"node0 ready: platform={c0['platform']} kind={c0['deviceKind']} "
            f"count={c0['deviceCount']} warm-up {c0.get('readySeconds')} s, "
            f"{c0.get('compilesAtReady')} compiles, cache "
            f"{c0.get('cacheHits')} hits / {c0.get('cacheMisses')} written")
        want = "cpu" if rehearse else "tpu"
        if c0["platform"] != want or (c0["deviceCount"] or 0) < cell["chips"]:
            raise RunFailure(
                f"node0 runs on {c0['platform']!r} x{c0['deviceCount']}, "
                f"this cell needs {want!r} x{cell['chips']}: no accelerator, "
                f"no result")
        load.warm_up()
        obs.take("before")
        t0 = time.monotonic() + 0.1
        setup_s = t0 - T_PROCESS_START
        obs.start(t0, t0 + args.seconds)
        load.run(t0)
        t_closed = time.monotonic()
        obs.join()

        measured = [r for r in load.requests if r.measured]
        sent = [{"hash": r.hash, "move": r.move, "receipt": r.receipt}
                for r in load.requests if r.sent is not None]
        t = time.monotonic()
        ans = answers_mod.gather(cluster, maker, sent, args.seed)
        t_gather = time.monotonic() - t
        if obs.trace:  # node0 may still be writing the trace
            wait_for_file(cluster.info_path + ".trace", 150)
        obs.take("end", (0,))
    except BaseException:
        for i in range(len(cluster.procs)):
            sys.stderr.write(cluster.log_tail(i))
        raise
    finally:
        codes = cluster.stop()

    # -- what only node0 could read: the device, its peak memory, the trace
    if not os.path.exists(cluster.info_path) and not args.node_launcher:
        raise RunFailure(f"node0 left no device record (exit codes {codes})")
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "memory_peak_bytes": 0}
    if os.path.exists(cluster.info_path):
        with open(cluster.info_path) as f:
            device = {k: v for k, v in json.load(f).items()
                      if k != "exit_code"}
    trace = read_trace(cluster) if obs.trace else None

    # -- the comparison ------------------------------------------------------
    t = time.monotonic()
    numbers = reference.judge(config, sent, ans)
    if codes != [0, 0, 0, 0]:  # said, not judged: no answer depends on it
        log(f"daemon exit codes after SIGTERM: {codes}")
    numbers.append({"name": "platform_moved", "limit": 0, "value": int(
        obs.snap["end"]["status"]["0"]["crypto"]["platform"]
        != c0["platform"])})
    correct = reference.is_correct(numbers)
    right = reference.receipts_right(config, sent, ans)
    controls = reference.run_controls(config, sent, ans) \
        if args.controls else None
    t_judge = time.monotonic() - t

    # -- the client's numbers --------------------------------------------------
    t0, t1 = load.t0, load.t1
    # a receipt that says what it has to: the operation done or, where the
    # kind's semantics demand it, refused
    ok = [r for r in measured if r.hash in right]
    in_window = [r for r in ok if r.done <= t1]
    horizon = t_closed
    lat = [(r.done if r.done is not None else horizon) - r.due
           for r in measured if r.due is not None]
    if not lat or not in_window:
        raise RunFailure("the window completed no request")
    # all the work sent in the window over all the time it took: the
    # window runs on to the last of its receipts (a closed loop's last
    # batch, an open loop's last few requests), so that blocks of 1,000
    # do not cut the rate into steps of 2%
    t_last = max(t1, *(r.done for r in ok))
    client = {
        "committed_tps": len(ok) / (t_last - t0),
        "receipt_p50_ms": 1000.0 * percentile(lat, 50),
        "setup_s": setup_s,
    }
    attempted, failed = len(measured), len(measured) - len(ok)
    if load.exhausted:
        raise RunFailure("the senders ran out of signed transactions: "
                         "raise presign_tx_per_s in the traffic file")

    # the per-layer readers run in every run: without a trace those of the
    # device find nothing to read, and a --trace 0 run logs the others
    ev = {
        "cell": cell["name"], "requests": measured,
        "committed": len(in_window), "window_s": t1 - t0,
        "status": {"before": obs.snap["before"]["status"],
                   "after": obs.snap["after"]["status"]},
        "cpu": {"before": obs.snap["before"]["cpu"],
                "after": obs.snap["after"]["cpu"]},
        "blocks": ans["blocks"],
        "height_before": obs.snap["before"]["status"]["0"]["blockNumber"],
        "height_after": obs.snap["after"]["status"]["0"]["blockNumber"],
        "trace": trace,
        "hash_name": "sm3" if config["sm_crypto"] else "keccak256",
        "presign_batches": {"signed": load.window_signed,
                            "taken": load.window_taken},
    }
    if trace is not None and not rehearse:
        ev["trace_status"] = {
            "before": obs.snap["trace_before"]["status"]["0"],
            "after": obs.snap["trace_after"]["status"]["0"]}
        ev["peaks"] = peaks(device["kind"])
    layers = {}
    for m in per_layer:
        v = m["read"](ev, m["spec"])
        if v is not None:
            layers[m["name"]] = {"value": v, "unit": m["unit"]}
    metrics = layers if args.trace else {
        m["name"]: {"value": client[m["name"]], "unit": m["unit"]}
        for m in e2e}

    log("layers " + json.dumps({k: v["value"] for k, v in layers.items()}))
    log(f"correct={correct} attempted={attempted} failed={failed} "
        f"client={json.dumps(client)} slowest {1000 * max(lat):.0f} ms, "
        f"last receipt {t_last - t0:.3f} s, "
        f"gather {t_gather:.1f} s judge {t_judge:.1f} s "
        f"height {ev['height_before']} -> {ev['height_after']} over the "
        f"window, {ans['height']} at the end")
    if controls is not None:
        for name, failed_by in controls.items():
            log(f"control {name}: fails {failed_by or 'NOTHING'}")
    for x in numbers:  # the last lines of stderr: each number and its limit
        log(f"compared {x['name']}: {x['value']} (limit {x['limit']})")

    if rehearse:
        print(json.dumps({
            "rehearsal": True,
            "no_result": "CPU rehearsal: proves nothing about the chip",
            "correct": correct, "attempted": attempted, "failed": failed,
            "client": client, "per_layer": {
                k: v for k, v in metrics.items()
                if not k.endswith(("_roofline", "idle_share"))},
            "controls": controls,
            "compared": {x["name"]: [x["value"], x["limit"]]
                         for x in numbers}}), flush=True)
        return 0
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if args.trace:
        if trace is None:
            raise RunFailure("traced run, and node0 wrote no device trace")
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    if controls is not None:
        out["controls"] = controls
    out["compared"] = {x["name"]: [x["value"], x["limit"]] for x in numbers}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
