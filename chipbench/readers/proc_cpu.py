"""CPU milliseconds one node's process (all threads, user + system, from
/proc/<pid>/stat) spent per committed transaction of the window.
spec: {"node": n}."""


def read(ev: dict, spec: dict):
    n = spec["node"]
    if not ev["committed"]:
        return None
    return 1000.0 * (ev["cpu"]["after"][n] - ev["cpu"]["before"][n]) \
        / ev["committed"]
