"""A ratio of counters from one node's getSystemStatus, read over RPC
before and after the window. spec: {"node": n, "numerator": [paths],
"denominator": [paths] | "blocks" | "one", "scale": k, "absolute": bool}.
A path is dotted (`crypto.ops.recover.deviceItems`); the paths of a side
are summed; without `absolute` each is the window's delta. `blocks` is the
node's own blockNumber delta. A side that is not there, or a denominator
of 0, reads as nothing."""

from readers_util import delta, get


def _side(ev: dict, spec: dict, paths):
    if paths == "one":
        return 1.0
    if paths == "blocks":
        paths = ["blockNumber"]
    node = str(spec.get("node", 0))
    pair = {k: ev["status"][k][node] for k in ("before", "after")}
    if not spec.get("absolute"):
        return delta(pair, paths)
    vals = [get(pair["after"], p) for p in paths]
    return None if None in vals else float(sum(vals))


def read(ev: dict, spec: dict):
    num = _side(ev, spec, spec["numerator"])
    den = _side(ev, spec, spec["denominator"])
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den
