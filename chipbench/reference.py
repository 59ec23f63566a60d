"""The plain reference and the comparison that decides `correct`.

Imports nothing of the program and takes nothing it computed as true: the
transfers are known from the seed, the replay below is a dict, and the
guarantees come from the configuration file. `judge` compares what the
cluster said (the client's receipts and what `answers.gather` read back
from the nodes after the window) with both. Every number is a count of
answers that say the wrong thing, so every limit is 0.

`CONTROLS` are the reference put in the program's place with one stated
guarantee broken; each has to fail at least one number (tests, and
`--controls 1` on the chip).
"""

from __future__ import annotations

import copy


def replay(moves: list, start: int) -> tuple[dict, int]:
    """Apply the transfers one after another -> (balances of the touched
    accounts, transfers that would have overdrawn)."""
    bal: dict = {}
    overdrawn = 0
    for src, dst, amt in moves:
        if bal.get(src, start) < amt:
            overdrawn += 1
            continue
        bal[src] = bal.get(src, start) - amt
        bal[dst] = bal.get(dst, start) + amt
    return bal, overdrawn


def transfer_log(move) -> str:
    src, dst, amt = move
    return "0x" + (src + dst + amt.to_bytes(8, "big")).hex()


def _receipt_says(rc: dict, sent: dict, height: int) -> bool:
    logs = rc.get("logEntries") or []
    return (rc.get("status") == 0
            and rc.get("transactionHash") == sent["hash"]
            and isinstance(rc.get("blockNumber"), int)
            and 1 <= rc["blockNumber"] <= height
            and len(logs) == 1
            and logs[0].get("data") == transfer_log(sent["move"]))


def judge(config: dict, sent: list[dict], answers: dict) -> list[dict]:
    """-> [{"name", "value", "limit"}], each a count that has to be 0.

    sent: every transaction the client sent, in any order:
        {"hash", "move", "receipt" (the client's, or None)}
    answers: see answers.gather.
    """
    g = config["guarantees"]
    height = answers["height"]
    nodes = sorted(answers["header_hashes"])
    by_hash = {s["hash"]: s for s in sent}

    never = sum(1 for s in sent if s["receipt"] is None)
    wrong = sum(1 for s in sent if s["receipt"] is not None
                and not _receipt_says(s["receipt"], s, height))

    # the chain as node0 holds it
    where: dict = {}
    twice = foreign = over = short = 0
    order = []
    for blk in answers["blocks"]:
        if len(blk["tx_hashes"]) > config["block_tx_count_limit"]:
            over += 1
        if blk["seals"] < g["commit_seals"]:
            short += 1
        for h in blk["tx_hashes"]:
            if h in where:
                twice += 1
            elif h not in by_hash:
                foreign += 1
            else:
                where[h] = blk["number"]
                order.append(by_hash[h]["move"])
    misplaced = sum(1 for s in sent if s["receipt"] is not None
                    and where.get(s["hash"]) != s["receipt"].get(
                        "blockNumber"))

    # every replica holds the same chain
    ref = answers["header_hashes"][nodes[0]]
    differ = sum(1 for n in range(height + 1)
                 if not ref[n] or any(answers["header_hashes"][k][n] != ref[n]
                                      for k in nodes))

    # an acknowledged transaction reads back, equal, from 2f+1 nodes
    under = 0
    for h, per_node in answers["receipts"].items():
        mine = by_hash[h]["receipt"]
        same = sum(1 for k in nodes if mine is not None
                   and per_node.get(k) == mine)
        if same < g["replicas_readable"]:
            under += 1

    # state: the sequential replay of the committed order
    want, overdrawn = replay(order, config["prefund_balance"])
    bad_bal = overdrawn
    for per_acct in answers["balances"].values():
        for acct, got in per_acct.items():
            if got != want.get(acct, config["prefund_balance"]):
                bad_bal += 1

    counts = [
        ("never_answered", never),
        ("receipts_wrong", wrong),
        ("acked_not_in_chain", misplaced),
        ("chain_txs_twice_or_foreign", twice + foreign),
        ("blocks_over_tx_limit", over),
        ("blocks_under_quorum_seals", short),
        ("heights_replicas_differ", differ),
        ("receipts_under_quorum_reads", under),
        ("balances_off_replay", bad_bal),
    ]
    return [{"name": n, "value": v, "limit": 0} for n, v in counts]


def is_correct(numbers: list[dict]) -> bool:
    return all(x["value"] <= x["limit"] for x in numbers)


# -- controls: one guarantee broken each --------------------------------------

def _acked(sent: list[dict]) -> dict:
    return next(s for s in reversed(sent) if s["receipt"] is not None)


def lost_acknowledged_write(sent, answers):
    """Durability: a transaction was acknowledged and is not in the chain."""
    h = _acked(sent)["hash"]
    for blk in answers["blocks"]:
        if h in blk["tx_hashes"]:
            blk["tx_hashes"].remove(h)


def replica_diverged(sent, answers):
    """Agreement: one replica holds another block at the last height."""
    k = sorted(answers["header_hashes"])[-1]
    answers["header_hashes"][k][-1] = "0x" + "00" * 32


def read_from_two_only(sent, answers):
    """Quorum reads: an acknowledged receipt is on two nodes, not three."""
    h = next(iter(answers["receipts"]))
    for k in sorted(answers["receipts"][h])[-2:]:
        answers["receipts"][h][k] = None


def one_seal_short(sent, answers):
    """2f+1 commit seals: a block committed with two."""
    answers["blocks"][-1]["seals"] = 2


def lost_update(sent, answers):
    """State: one account's balance misses a transfer it received."""
    per_acct = next(iter(answers["balances"].values()))
    acct = next(iter(per_acct))
    per_acct[acct] -= 1


def wrong_receipt(sent, answers):
    """Answers: a receipt reports another transfer than was sent."""
    s = _acked(sent)
    src, dst, amt = s["move"]
    s["receipt"] = dict(s["receipt"], logEntries=[
        dict(s["receipt"]["logEntries"][0],
             data=transfer_log((src, dst, amt + 1)))])


CONTROLS = {f.__name__: f for f in (
    lost_acknowledged_write, replica_diverged, read_from_two_only,
    one_seal_short, lost_update, wrong_receipt)}


def run_controls(config: dict, sent: list[dict], answers: dict) -> dict:
    """-> {control: the numbers it failed}; an empty list is a control that
    passed, which is a fault of the comparison."""
    out = {}
    for name, breaker in CONTROLS.items():
        s, a = copy.deepcopy(sent), copy.deepcopy(answers)
        breaker(s, a)
        out[name] = [x["name"] for x in judge(config, s, a)
                     if x["value"] > x["limit"]]
    return out
