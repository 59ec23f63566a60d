"""SmallBank over the program's SmallBank precompile — the client's side.

H-Store SmallBank as OLTPBench draws it and BlockBench sends it to a chain
(`smallbank`): six operations in the published mix, every customer named
hot with the configuration's share, amounts in cents. What is in every
node's storage before its first block (each customer's savings and
checking row, drawn from the index and the configuration's
`prefund_seed`), the i-th operation of a seed, its call, the customers it
writes and how one is read back (`getAccount`: both rows, separately) are
here; this file calls into the package, on the CPU. Its plain reference,
`smallbank_reference.py`, does not.
"""

from __future__ import annotations

import random

import numpy as np

from fisco_bcos_tpu.executor.precompiled import (SMALLBANK_ADDRESS,
                                                  SMALLBANK_PENALTY,
                                                  T_SB_CHECKING, T_SB_SAVINGS,
                                                  SmallBankPrecompile,
                                                  encode_call)

# an operation is (method, customer, second customer or b"", amount)
Op = tuple[str, bytes, bytes, int]

TWO = ("sendPayment", "amalgamate")
BATCH = 1 << 16  # rows a storage.set_batch of the prefund


def customer(i: int) -> bytes:
    return b"sb-%07d" % i


def openings(config: dict, start: int, stop: int):
    """(savings, checking) of customers start..stop-1 before block 1, two
    int64 arrays: each uniform over the configuration's `balance_cents`
    range, by splitmix64 of its `prefund_seed` and the row (2i: savings,
    2i + 1: checking)."""
    lo, hi = config["balance_cents"]
    rows = np.uint64(config["prefund_seed"] << 32) | (
        np.arange(2 * start, 2 * stop, dtype=np.uint64))
    x = rows + np.uint64(0x9E3779B97F4A7C15)  # numpy wraps mod 2**64
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x = lo + ((x ^ (x >> np.uint64(31))) % np.uint64(hi - lo + 1)).astype(
        np.int64)
    return x[0::2], x[1::2]


def opening(config: dict, i: int) -> tuple[int, int]:
    """Customer i's (savings, checking) before block 1."""
    s, c = openings(config, i, i + 1)
    return int(s[0]), int(c[0])


def _check(config: dict) -> None:
    if config["penalty_cents"] != SMALLBANK_PENALTY:
        raise ValueError("the program's WriteCheck penalty is not the "
                         "configuration's")
    if not 0 < config["hot_accounts"] < int(config["accounts"]):
        raise ValueError("the hot set has to be a part of the customers")
    if set(config["mix"]) - set(config["amounts_cents"]) != {
            "getBalance", "amalgamate"}:
        raise ValueError("every operation but Balance and Amalgamate "
                         "carries an amount")


def prefund(storage, config: dict) -> None:
    """Both rows of every customer, written into one node's storage
    before its first block, in batches."""
    _check(config)
    accounts = int(config["accounts"])
    for start in range(0, accounts, BATCH):
        stop = min(start + BATCH, accounts)
        names = [customer(i) for i in range(start, stop)]
        for table, amounts in zip((T_SB_SAVINGS, T_SB_CHECKING),
                                  openings(config, start, stop)):
            raw = amounts.astype(">i8").tobytes()  # encode_amount's bytes
            storage.set_batch(table, [(k, raw[8 * j:8 * j + 8])
                                      for j, k in enumerate(names)])
    last = customer(accounts - 1)
    enc = SmallBankPrecompile.encode_amount
    if [storage.get(t, last) for t in (T_SB_SAVINGS, T_SB_CHECKING)] != [
            enc(v) for v in opening(config, accounts - 1)]:
        raise ValueError(f"the storage does not read customer {last!r} "
                         "back as it was written")


def _draw(config: dict, rng: random.Random) -> int:
    hot = config["hot_accounts"]
    if rng.randrange(100) < config["hot_share_pct"]:
        return rng.randrange(hot)
    return hot + rng.randrange(int(config["accounts"]) - hot)


def op(config: dict, seed: int, i: int) -> Op:
    """The i-th operation of this seed: a method by the mix's weights,
    each customer hot with `hot_share_pct` (uniform over the first
    `hot_accounts`) or else uniform over the rest; the two customers of a
    two-customer operation differ; the method's amount (none for Balance
    and Amalgamate)."""
    rng = random.Random((seed << 24) ^ i)
    mix = config["mix"]
    method = rng.choices(list(mix), weights=list(mix.values()))[0]
    a = _draw(config, rng)
    b = b""
    if method in TWO:
        while (j := _draw(config, rng)) == a:
            pass
        b = customer(j)
    return method, customer(a), b, config["amounts_cents"].get(method, 0)


def call(o: Op) -> tuple[bytes, bytes]:
    """-> (to, input) of the transaction that makes this operation."""
    method, a, b, v = o

    def build(w):
        w.blob(a)
        if b:
            w.blob(b)
        if method in ("updateBalance", "updateSaving", "sendPayment",
                      "writeCheck"):
            w.i64(v)

    return SMALLBANK_ADDRESS, encode_call(method, build)


def touched(o: Op) -> tuple[bytes, ...]:
    """The customers whose rows the operation may change (Balance: none)."""
    if o[0] == "getBalance":
        return ()
    return o[1:3] if o[2] else o[1:2]


def read_call(group: str, name: bytes) -> tuple[str, list]:
    """The RPC call that reads one customer's savings and checking."""
    return ("call", [group, "", "0x" + SMALLBANK_ADDRESS.hex(),
                     "0x" + encode_call(
                         "getAccount", lambda w: w.blob(name)).hex()])


def decode(answer: dict):
    """(savings, checking) as `getAccount` answers them; None where the
    call did not."""
    out = answer.get("output") or ""
    if answer.get("status") != 0 or len(out) != 2 + 32:
        return None
    raw = bytes.fromhex(out[2:])
    return (int.from_bytes(raw[:8], "big", signed=True),
            int.from_bytes(raw[8:], "big", signed=True))
