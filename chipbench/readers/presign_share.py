"""The share of the batches signed ahead for the window that the window
took (`traffic.py` ClosedBatch: warm-up batches are in neither count). A
gauge of the harness, not of the program: at 100% the run fails for want of
signed transactions. A traffic kind that signs as it goes has no counts
and reads as nothing."""


def read(ev: dict, spec: dict):
    n = ev.get("presign_batches") or {}
    if not n.get("signed") or n.get("taken") is None:
        return None
    return 100.0 * n["taken"] / n["signed"]
