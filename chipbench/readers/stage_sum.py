"""The window's delta of the listed stages' seconds
(`trace.stages.<name>.seconds` of one node's getSystemStatus), summed,
over the window. spec: {"node": n, "stages": [names], "scale": k}. A stage
that either snapshot lacks reads as nothing."""

from readers_util import delta


def read(ev: dict, spec: dict):
    node = str(spec.get("node", 0))
    pair = {k: ev["status"][k][node] for k in ("before", "after")}
    total = delta(pair, [f"trace.stages.{s}.seconds"
                         for s in spec["stages"]])
    if total is None or not ev.get("window_s"):
        return None
    return spec.get("scale", 1.0) * total / ev["window_s"]
