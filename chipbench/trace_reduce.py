"""From a profiler trace to the numbers the benchmark reports.

`load_xplane` turns the `.xplane.pb` that `jax.profiler` wrote into plain
data, {"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns], ...]}]}]}, and `reduce` works on that alone, so it is checked
on a small recorded trace kept as JSON (tests/data).

Device planes are `/device:TPU:<n>`. Busy is the union of the intervals on
a device's `XLA Ops` line (its `XLA Modules` line where a trace has no op
line), averaged over the devices. A device op in the breakdown is a
program on the `XLA Modules` line under its printed name. An idle gap is
charged to the host span (`/host:CPU` lines) that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
GAPS_LOOKED_AT = 200
TOP = 10


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return {"planes": [
        {"name": p.name, "lines": [
            {"name": ln.name, "events": [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for e in ln.events]} for ln in p.lines]}
        for p in data.planes]}


def program_name(event_name: str) -> str:
    """`jit_ecdsa_recover_batch(1234567)` -> `jit_ecdsa_recover_batch`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _line(plane: dict, name: str) -> list:
    return [ev for ln in plane["lines"] if ln["name"] == name
            for ev in ln["events"]]


def reduce(trace: dict) -> dict | None:
    """-> {"busy_s", "span_s", "devices", "programs": {name: seconds,
    summed over devices}, "device_ops": [[name, s]], "idle_gaps":
    [[what the host was doing, s]]} or None where no device plane is in
    the trace."""
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        return None
    starts = [ev[1] for p in trace["planes"] for ln in p["lines"]
              for ev in ln["events"]]
    ends = [ev[1] + ev[2] for p in trace["planes"] for ln in p["lines"]
            for ev in ln["events"]]
    if not starts:
        return None
    t_lo, t_hi = min(starts), max(ends)
    programs: dict = {}
    busy_ns = 0
    busy0: list = []
    for k, plane in enumerate(devices):
        ops = _line(plane, "XLA Ops") or _line(plane, "XLA Modules")
        merged = _union([(s, s + d) for _n, s, d in ops])
        busy_ns += sum(e - s for s, e in merged)
        if k == 0:
            busy0 = merged
        for name, _s, d in _line(plane, "XLA Modules"):
            key = program_name(name)
            programs[key] = programs.get(key, 0) + d
    # gaps on the first device, by what the host was doing
    edges = [t_lo] + [x for iv in busy0 for x in iv] + [t_hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)
    host = [ev for p in trace["planes"] if p["name"] == HOST_PLANE
            for ln in p["lines"] for ev in ln["events"]]
    names = sorted({ev[0] for ev in host})
    index = {n: i for i, n in enumerate(names)}
    which = np.array([index[ev[0]] for ev in host], np.int64)
    h0 = np.array([ev[1] for ev in host], np.float64)
    h1 = h0 + np.array([ev[2] for ev in host], np.float64)
    idle: dict = {}
    for length, g0, g1 in gaps[:GAPS_LOOKED_AT]:
        best = "unattributed"
        if host:
            cover = np.bincount(which, np.clip(
                np.minimum(h1, g1) - np.maximum(h0, g0), 0, None),
                len(names))
            if cover.max() >= 0.1 * length:
                best = names[int(cover.argmax())]
        idle[best] = idle.get(best, 0) + length
    rest = sum(g[0] for g in gaps[GAPS_LOOKED_AT:])
    if rest:
        idle["gaps_not_looked_at"] = rest

    def top(d: dict) -> list:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_ns / 1e9 / len(devices),
            "span_s": (t_hi - t_lo) / 1e9, "devices": len(devices),
            "programs": {k: v / 1e9 for k, v in programs.items()},
            "device_ops": top(programs), "idle_gaps": top(idle)}
