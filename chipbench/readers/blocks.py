"""Mean transactions per block over the blocks node0 committed in the
window (heights read before and after it; the blocks' lists read back for
the comparison)."""


def read(ev: dict, spec: dict):
    sizes = [len(b["tx_hashes"]) for b in ev["blocks"]
             if ev["height_before"] < b["number"] <= ev["height_after"]]
    return sum(sizes) / len(sizes) if sizes else None
