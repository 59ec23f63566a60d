"""`register(user, balance)` — the plain reference of its semantics:
a user is registered once, holds what it was opened with, a second
registration of it is refused, and the call logs nothing. Imports
nothing of the program or of the client's file.
"""

from __future__ import annotations


def expected(order: list, config: dict) -> tuple[dict, int, list]:
    """-> (balances of the registered users, what a user holds that no
    committed registration named, positions in `order` of registrations
    of a user already there: those the precompile has to refuse)."""
    state: dict = {}
    again = []
    for i, (user, balance) in enumerate(order):
        if user in state:
            again.append(i)
            continue
        state[user] = balance
    return state, 0, again


def receipt_says(rc: dict, reg, refused: bool) -> bool:
    """Status 0 for a registration done and another for one refused; no
    log either way."""
    return (rc.get("status") != 0) == refused and not rc.get("logEntries")


def lost_registration(sent, answers):
    """State: a user that was acknowledged reads as never registered."""
    per_user = next(iter(answers["balances"].values()))
    per_user[next(iter(per_user))] = 0


def receipt_of_another_call(sent, answers):
    """Answers: a receipt carries a log, which no registration writes."""
    s = next(s for s in reversed(sent) if s["receipt"] is not None)
    s["receipt"] = dict(s["receipt"], logEntries=[{"data": "0x00"}])


def registered_twice(sent, answers):
    """Answers: a registration of a user already there reads as done."""
    s = next(s for s in sent if s["receipt"] is not None
             and s["receipt"].get("status") != 0)
    s["receipt"] = dict(s["receipt"], status=0)


CONTROLS = {f.__name__: f for f in (
    lost_registration, receipt_of_another_call, registered_twice)}
