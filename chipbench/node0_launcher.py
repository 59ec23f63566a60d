#!/usr/bin/env python3
"""node0's process: the daemon's entry point, unchanged, in the one process
that owns the chip — plus what only that process can read.

    node0_launcher.py --info FILE [--trace-dir DIR] -- <daemon arguments>

SIGUSR1 starts a `jax.profiler` trace into DIR and SIGUSR2 stops it (the
benchmark brackets a few seconds of the steady window; FILE.trace appears
when the trace is on disk). After the daemon has shut down, FILE gets the
device as JAX reports it and its peak memory. Both run on a thread of
their own: a signal handler only sets an event.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time


def _write(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def _tracer(trace_dir: str, info: str, start: threading.Event,
            stop: threading.Event) -> None:
    start.wait()
    if stop.is_set():  # the daemon ended before any trace was asked for
        return
    try:
        _trace(trace_dir, info, stop)
    except Exception as exc:  # noqa: BLE001 — the benchmark reads the marker
        _write(info + ".trace", {"error": f"{type(exc).__name__}: {exc}"})


def _trace(trace_dir: str, info: str, stop: threading.Event) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # per-call Python events would slow the
    opts.host_tracer_level = 2     # node; JAX's own TraceMe spans stay
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.time()
    _write(info + ".trace_on", {"started": t0})
    stop.wait()
    t1 = time.time()
    jax.profiler.stop_trace()
    _write(info + ".trace", {"started": t0, "stopped": t1,
                             "written": time.time()})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--info", required=True)
    ap.add_argument("--trace-dir")
    ap.add_argument("daemon", nargs="+")
    args = ap.parse_args()
    if args.trace_dir:
        start, stop = threading.Event(), threading.Event()
        signal.signal(signal.SIGUSR1, lambda *_: start.set())
        signal.signal(signal.SIGUSR2, lambda *_: stop.set())
        tracer = threading.Thread(target=_tracer, args=(
            args.trace_dir, args.info, start, stop))
        tracer.start()

    from fisco_bcos_tpu.__main__ import main as daemon_main

    rc = daemon_main(args.daemon)
    if args.trace_dir:
        # a trace still being written is finished before the process ends
        stop.set()
        start.set()
        tracer.join()
    import jax

    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    _write(args.info, {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "exit_code": rc,
        "memory_peak_bytes": max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0)})
    return rc


if __name__ == "__main__":
    sys.exit(main())
