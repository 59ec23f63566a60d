"""ParallelOk `transfer` — the plain reference of its semantics.

`_balance[from] -= num; _balance[to] += num` in uint256 arithmetic, which
is unchecked: the source's "overflow is ok", so no transfer is refused.
The call returns nothing and emits no event. Imports nothing of the
program and nothing of the client's file.
"""

from __future__ import annotations

M256 = 1 << 256


def expected(order: list, config: dict) -> tuple[dict, int, list]:
    """The sequential replay of the committed order -> (balances of the
    touched users, what a user holds that no committed transfer touched,
    no refused positions)."""
    start = config["prefund_balance"]
    bal: dict = {}
    for src, dst, amt in order:
        bal[src] = (bal.get(src, start) - amt) % M256
        bal[dst] = (bal.get(dst, start) + amt) % M256
    return bal, start, []


def receipt_says(rc: dict, move, refused: bool) -> bool:
    """The receipt is of a transfer done: status 0, no output, no log."""
    return (not refused and rc.get("status") == 0
            and rc.get("output") in ("0x", "") and not rc.get("logEntries"))


# -- controls: one guarantee of the kind broken each --------------------------

def lost_update(sent, answers):
    """State: one user's balance misses a transfer it took part in."""
    per_user = next(iter(answers["balances"].values()))
    user = next(iter(per_user))
    per_user[user] = (per_user[user] - 1) % M256


def reported_reverted(sent, answers):
    """Answers: a receipt says the call reverted (status 14, REVERT)."""
    s = next(s for s in reversed(sent) if s["receipt"] is not None)
    s["receipt"] = dict(s["receipt"], status=14)


def receipt_with_output(sent, answers):
    """Answers: a receipt carries output, which `transfer` returns none of."""
    s = next(s for s in sent if s["receipt"] is not None)
    s["receipt"] = dict(s["receipt"], output="0x" + "00" * 32)


CONTROLS = {f.__name__: f for f in (lost_update, reported_reverted,
                                    receipt_with_output)}
