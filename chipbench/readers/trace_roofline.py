"""A kernel's share of its roofline over the traced window.

spec: {"program": regex on the printed program name, "work": a function of
workcount.COUNTERS | "merkle_root", "items": [status paths on node0, summed:
the REAL items the crypto seam sent to the device], "calls": [paths]
(merkle only: how many trees)}. Work is counted per real item by
workcount, whatever implements the kernel; time is the program's device
time in the trace; peaks are peaks.json's. Nothing in the trace, or no
item counted, reads as nothing — never as 0."""

import re

import workcount
from readers_util import delta


def read(ev: dict, spec: dict):
    t = ev.get("trace")
    if not t:
        return None
    pat = re.compile(spec["program"])
    secs = sum(s for name, s in t["programs"].items() if pat.search(name))
    items = delta(ev["trace_status"], spec["items"])
    if secs <= 0 or not items:
        return None
    if spec["work"] == "merkle_root":
        trees = delta(ev["trace_status"], spec["calls"])
        ops, nbytes = workcount.merkle_root(
            int(items), int(trees or 1), ev["hash_name"])
    else:
        ops, nbytes = workcount.COUNTERS[spec["work"]](int(items))
    share, _bound = workcount.roofline_share(ops, nbytes, secs, ev["peaks"])
    return share
