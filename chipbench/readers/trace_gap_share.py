"""Share of the traced window's idle time that the reducer charged to the
names matching spec["names"] (a regex over `idle_gaps`' names). Nothing
without a trace."""

import re


def read(ev: dict, spec: dict):
    t = ev.get("trace")
    if not t or "idle_gaps" not in t:
        return None
    idle = t["window_s"] - t["busy_s"]
    if idle <= 0:
        return None
    names = re.compile(spec["names"])
    return 100.0 * sum(s for name, s in t["idle_gaps"]
                       if names.search(name)) / idle
