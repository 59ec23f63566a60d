"""SmallBank — the plain reference of its semantics.

H-Store SmallBank's six transactions over a savings and a checking
balance a customer, signed, in cents, replayed one after another in the
committed order with Python integers:

    getBalance(n)        answers savings + checking; changes nothing
    updateBalance(n, v)  refused if v < 0; else checking += v
    updateSaving(n, v)   refused if savings + v < 0; else savings += v
    sendPayment(a, b, v) refused if checking(a) < v; else a pays b
    writeCheck(n, v)     checking -= v, plus the penalty where
                         savings + checking < v (an overdraft, signed)
    amalgamate(a, b)     savings(a) + checking(a) -> checking(b); a's two
                         balances become 0

A customer not prefunded, and one named twice in a call, is refused. A
refused call is REVERT (status 14), answers nothing and logs nothing; a
done one is status 0, logs nothing, and answers nothing but getBalance's
sum. Opening balances are recomputed from the index here, as the client
draws them. Imports nothing of the program and nothing of the client's
file.

`receipt_says` is handed the operation, not its position in the order:
`expected` keeps what each getBalance of the committed order has to
answer under the operation object itself, which the harness hands both
functions (one tuple a request).
"""

from __future__ import annotations

REVERT = 14
TWO = ("sendPayment", "amalgamate")
_SAID: dict = {}  # id(getBalance operation) -> (operation, its answer)


def _index(name: bytes, accounts: int):
    """The customer's index where it was prefunded; else None."""
    if len(name) != 10 or not name.startswith(b"sb-") \
            or not name[3:].isdigit():
        return None
    i = int(name[3:])
    return i if i < accounts else None


def _mix(x: int) -> int:
    """splitmix64's output function of x."""
    x = (x + 0x9E3779B97F4A7C15) % 2**64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) % 2**64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) % 2**64
    return x ^ (x >> 31)


def _opening(config: dict, i: int) -> list:
    """[savings, checking] of customer i before block 1: each uniform over
    `balance_cents` by splitmix64 of (`prefund_seed`, the row)."""
    lo, hi = config["balance_cents"]
    key = config["prefund_seed"] * 2**32
    return [lo + _mix(key + 2 * i + row) % (hi - lo + 1) for row in (0, 1)]


class _Books(dict):
    """{customer: (savings, checking)} after the replay; `get` of a
    customer no committed call named answers its opening balances."""

    def __init__(self, config: dict):
        super().__init__()
        self.config = config

    def get(self, name, default=None):
        if name in self:
            return self[name]
        i = _index(name, int(self.config["accounts"]))
        return default if i is None else tuple(_opening(self.config, i))


def expected(order: list, config: dict) -> tuple[dict, None, list]:
    """The sequential replay of the committed order -> (both balances of
    every customer it names, None: an unnamed customer's balances are its
    own opening ones, which the mapping answers; positions in `order` of
    the calls the semantics refuse)."""
    accounts = int(config["accounts"])
    penalty = config["penalty_cents"]
    acct: dict = {}
    refused = []
    _SAID.clear()

    def books(name):
        if name not in acct:
            i = _index(name, accounts)
            acct[name] = None if i is None else _opening(config, i)
        return acct[name]

    for pos, o in enumerate(order):
        method, a, b, v = o
        x = books(a)
        y = books(b) if method in TWO else None
        if x is None or (method in TWO and (y is None or a == b)):
            refused.append(pos)
        elif method == "getBalance":
            _SAID[id(o)] = (o, x[0] + x[1])
        elif method == "updateBalance":
            if v < 0:
                refused.append(pos)
            else:
                x[1] += v
        elif method == "updateSaving":
            if x[0] + v < 0:
                refused.append(pos)
            else:
                x[0] += v
        elif method == "sendPayment":
            if x[1] < v:
                refused.append(pos)
            else:
                x[1] -= v
                y[1] += v
        elif method == "writeCheck":
            x[1] -= v + (penalty if x[0] + x[1] < v else 0)
        elif method == "amalgamate":
            y[1] += x[0] + x[1]
            x[0] = x[1] = 0
        else:
            refused.append(pos)
    out = _Books(config)
    out.update({k: tuple(s) for k, s in acct.items() if s is not None})
    return out, None, refused


def _answer(v: int) -> str:
    return "0x" + v.to_bytes(8, "big", signed=True).hex()


def receipt_says(rc: dict, o, refused: bool) -> bool:
    """REVERT with no output for a refusal the replay makes; status 0 for
    a call done, with getBalance's sum at its place in the order as the
    output and no output otherwise; never a log."""
    if rc.get("logEntries"):
        return False
    out = rc.get("output") or "0x"
    if refused:
        return rc.get("status") == REVERT and out == "0x"
    if rc.get("status") != 0:
        return False
    if o[0] != "getBalance":
        return out == "0x"
    said = _SAID.get(id(o))
    return said is not None and said[0] is o and out == _answer(said[1])


# -- controls: one guarantee of the kind broken each --------------------------

def refusal_ignored(sent, answers):
    """Answers: a call the replay refuses (a payment from a drained hot
    account) reads as done, as where the refusals are dropped; in a run
    that refused nothing, a call done reads as refused."""
    acked = [s for s in sent if s["receipt"] is not None]
    s = next((s for s in acked if s["receipt"].get("status") == REVERT),
             acked[-1])
    flipped = 0 if s["receipt"].get("status") == REVERT else REVERT
    s["receipt"] = dict(s["receipt"], status=flipped, output="0x")


def stale_balance(sent, answers):
    """Answers: a getBalance answers one cent off its place in the
    order (a read that missed the write before it)."""
    s = next(s for s in reversed(sent) if s["receipt"] is not None
             and s["move"][0] == "getBalance"
             and s["receipt"].get("status") == 0)
    out = int.from_bytes(bytes.fromhex(s["receipt"]["output"][2:]), "big",
                         signed=True)
    s["receipt"] = dict(s["receipt"], output=_answer(out - 1))


def lost_saving(sent, answers):
    """State: one customer's savings miss a cent that the order left."""
    per_key = next(iter(answers["balances"].values()))
    name = next(iter(per_key))
    s, c = per_key[name]
    per_key[name] = (s - 1, c)


def overdraft_floored(sent, answers):
    """State: a sampled customer's checking reads at least 0, as an
    unsigned balance would hold it (or one cent off where none of the
    sample is overdrawn)."""
    per_key = next(iter(answers["balances"].values()))
    name = min(per_key, key=lambda k: per_key[k][1])
    s, c = per_key[name]
    per_key[name] = (s, max(c, 0) if c < 0 else c + 1)


CONTROLS = {f.__name__: f for f in (refusal_ignored, stale_balance,
                                    lost_saving, overdraft_floored)}
