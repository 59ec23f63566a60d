#!/usr/bin/env python3
"""Find the highest rate of single sendTransaction requests the cluster
sustains: one process, one set-up, a ladder of fixed open-loop rates on
each door. Not a cell and not run by the driver: the builder runs it once
on the chip, writes half the knee into the traffic file and the table into
PERF.md.

    python3 chipbench/sweep.py --config <name> --traffic <name> --seed <n> \
        --step-seconds 10 --nowait 100,150,... --wait 40,60,...

A rate is sustained (`sustained` below) where at least 98% of what was
offered in a step was committed within it (the last fraction of a second
is still in flight), no request failed, under a quarter of a second's
arrivals were outstanding at the close, and the later half of the step was
not slower than 1.5 times the earlier: the backlog did not grow.
"""

from __future__ import annotations

import os
import sys
import time

OUTSIDE_JAX_PLATFORMS = os.environ.get("JAX_PLATFORMS")
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (HERE, os.path.dirname(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from cluster import Cluster  # noqa: E402
from manifest import Manifest  # noqa: E402
from stats import percentile  # noqa: E402
from traffic import OpenSingles  # noqa: E402


def step(load: OpenSingles, door: str, rate: float, seconds: float,
         tag: int) -> dict:
    first = len(load.requests)
    load.start(door)
    t0, t1 = load.offer(rate, seconds, True, tag)
    left = load.outstanding()
    load.finish()
    reqs = load.requests[first:]
    done = [r for r in reqs if r.done is not None]
    lat = [r.done - r.due for r in done]
    half = t0 + seconds / 2
    early = [r.done - r.due for r in done if r.due < half]
    late = [r.done - r.due for r in done if r.due >= half]
    return {
        "door": door, "offered_tps": len(reqs) / seconds,
        "committed_in_step_tps": sum(1 for r in done if r.done <= t1)
        / seconds,
        "outstanding_at_close": left,
        "failed": len(reqs) - len(done),
        "p50_ms": 1000 * percentile(lat, 50) if lat else None,
        "p95_ms": 1000 * percentile(lat, 95) if lat else None,
        "p50_early_ms": 1000 * percentile(early, 50) if early else None,
        "p50_late_ms": 1000 * percentile(late, 50) if late else None,
        "gen_late_p99_ms": 1000 * percentile(
            [r.sent - r.due for r in reqs if r.sent is not None], 99),
    }


def sustained(row: dict) -> bool:
    return (row["failed"] == 0
            and row["committed_in_step_tps"] >= 0.98 * row["offered_tps"]
            and row["outstanding_at_close"] <= 0.25 * row["offered_tps"]
            and row["p50_late_ms"] is not None
            and row["p50_late_ms"] <= 1.5 * row["p50_early_ms"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True, help="an open-singles mix")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--step-seconds", type=float, default=10.0)
    ap.add_argument("--nowait", default="")
    ap.add_argument("--wait", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    man = Manifest()
    config, traffic = man.config(args.config), man.traffic(args.traffic)
    man.workload(config)  # both files of its kind
    if args.rehearse_cpu:
        config["accounts"] = 256
    from txgen import TxMaker

    workdir = tempfile.mkdtemp(prefix="chipbench_sweep_")
    cluster = Cluster(config, workdir, args.seed, OUTSIDE_JAX_PLATFORMS,
                      args.rehearse_cpu, trace=False)
    rows = []
    try:
        cluster.start()
        st = cluster.wait_all_ready()["crypto"]
        print(f"sweep: node0 platform={st['platform']} "
              f"kind={st['deviceKind']}", flush=True)
        if st["platform"] != ("cpu" if args.rehearse_cpu else "tpu"):
            print("sweep: no accelerator, no table", file=sys.stderr)
            return 1
        load = OpenSingles(traffic, cluster, TxMaker(config, args.seed),
                           args.seed, args.step_seconds)
        load.warm_up()
        load.finish()
        tag = 10
        for door, rates in (("nowait-poll", args.nowait),
                            ("wait", args.wait)):
            for rate in [float(x) for x in rates.split(",") if x]:
                tag += 1
                row = step(load, door, rate, args.step_seconds, tag)
                row["sustained"] = sustained(row)
                rows.append(row)
                print("sweep: " + json.dumps(row), flush=True)
                time.sleep(1.0)
    finally:
        codes = cluster.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"sweep": rows, "platform": st["platform"],
                      "daemon_exit_codes": codes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
