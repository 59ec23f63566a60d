"""DagTransfer `transfer` — the plain reference of its semantics.

Imports nothing of the program and nothing of the client's file: a
transfer is the tuple the client drew, the replay is a dict, and what a
receipt has to say is spelled out here.
"""

from __future__ import annotations


def _replay(moves: list, start: int) -> tuple[dict, list]:
    bal: dict = {}
    overdrawn = []
    for i, (src, dst, amt) in enumerate(moves):
        if bal.get(src, start) < amt:
            overdrawn.append(i)
            continue
        bal[src] = bal.get(src, start) - amt
        bal[dst] = bal.get(dst, start) + amt
    return bal, overdrawn


def replay(moves: list, start: int) -> tuple[dict, int]:
    """Apply the transfers one after another -> (balances of the touched
    accounts, transfers that would have overdrawn)."""
    bal, overdrawn = _replay(moves, start)
    return bal, len(overdrawn)


def expected(order: list, config: dict) -> tuple[dict, int, list]:
    """The sequential replay of the committed order -> (state of the
    touched keys, what a key holds that no committed operation touched,
    positions in `order` of the operations the semantics refuse)."""
    start = config["prefund_balance"]
    bal, overdrawn = _replay(order, start)
    return bal, start, overdrawn


def transfer_log(move) -> str:
    src, dst, amt = move
    return "0x" + (src + dst + amt.to_bytes(8, "big")).hex()


def receipt_says(rc: dict, move, refused: bool) -> bool:
    """The receipt is this transfer's, done: status 0 and its one log.
    This kind's traffic draws no transfer that overdraws, so one that the
    replay refuses is wrong whatever its receipt says."""
    logs = rc.get("logEntries") or []
    return (not refused and rc.get("status") == 0 and len(logs) == 1
            and logs[0].get("data") == transfer_log(move))


# -- controls: one guarantee of the kind broken each --------------------------

def lost_update(sent, answers):
    """State: one account's balance misses a transfer it received."""
    per_acct = next(iter(answers["balances"].values()))
    acct = next(iter(per_acct))
    per_acct[acct] -= 1


def wrong_receipt(sent, answers):
    """Answers: a receipt reports another transfer than was sent."""
    s = next(s for s in reversed(sent) if s["receipt"] is not None)
    src, dst, amt = s["move"]
    s["receipt"] = dict(s["receipt"], logEntries=[
        dict(s["receipt"]["logEntries"][0],
             data=transfer_log((src, dst, amt + 1)))])


CONTROLS = {f.__name__: f for f in (lost_update, wrong_receipt)}
