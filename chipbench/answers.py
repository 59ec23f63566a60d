"""Read back from the nodes what the comparison needs, once the window has
closed: every header hash from every node, the chain's transaction lists
and seal counts from node0, and — for samples drawn from the seed — the
receipts from every node and the state of the touched keys from node0 and
one host replica (which keys an operation touched, the call that reads one
back and what its answer says are the kind's, `maker.kind`).
"""

from __future__ import annotations

import random
import time

SAMPLE_RECEIPTS = 256
SAMPLE_ACCOUNTS = 2048
CATCH_UP_SECONDS = 60.0


def gather(cluster, maker, sent: list[dict], seed: int) -> dict:
    group = cluster.group
    nodes = list(range(len(cluster.procs)))
    clis = {k: cluster.rpc(k, 120.0) for k in nodes}
    try:
        height = clis[0].call("getBlockNumber", [group, ""])
        # a replica may trail node0 by the blocks in flight: late, not wrong
        deadline = time.monotonic() + CATCH_UP_SECONDS
        for k in nodes[1:]:
            while clis[k].call("getBlockNumber", [group, ""]) < height \
                    and time.monotonic() < deadline:
                time.sleep(0.1)
        heights = range(height + 1)
        header_hashes = {k: clis[k].results(
            [("getBlockHashByNumber", [group, "", n]) for n in heights])
            for k in nodes}
        raw = clis[0].results(
            [("getBlockByNumber", [group, "", n, False, True])
             for n in heights[1:]], chunk=32)
        blocks = [{"number": b["number"], "hash": b["hash"],
                   "tx_hashes": list(b["transactions"]),
                   "seals": len(b["signatureList"])} for b in raw]

        rng = random.Random(seed ^ 0x5EED)
        acked = [s for s in sent if s["receipt"] is not None]
        pick = rng.sample(acked, min(SAMPLE_RECEIPTS, len(acked)))
        if acked and acked[-1] not in pick:
            pick.append(acked[-1])
        receipts: dict = {s["hash"]: {} for s in pick}
        for k in nodes:
            got = clis[k].results([("getTransactionReceipt",
                                    [group, "", s["hash"]]) for s in pick])
            for s, rc in zip(pick, got):
                receipts[s["hash"]][k] = rc

        kind = maker.kind
        touched = sorted({a for s in acked for a in kind.touched(s["move"])})
        accts = rng.sample(touched, min(SAMPLE_ACCOUNTS, len(touched)))
        balances = {}
        for k in (0, 1 + seed % (len(nodes) - 1)):
            got = clis[k].results([kind.read_call(group, a) for a in accts])
            balances[k] = {a: kind.decode(r) for a, r in zip(accts, got)}
    finally:
        for c in clis.values():
            c.close()
    return {"height": height, "header_hashes": header_hashes,
            "blocks": blocks, "receipts": receipts, "balances": balances}
