"""`register(user, balance)` of users not yet in the state (and, one in
eight, of one that by then is: a refusal the semantics demand) — the client's
side of a second transaction kind, kept with the tests: java-sdk-demo
ParallelOkPerf's first phase (`userAdd`), through the balance precompile
the program has. `test_chipbench_workload.py` adds it to a copy of the
tree as files and entries alone; no BENCHMARK.json lists it.
"""

from __future__ import annotations

import random

from fisco_bcos_tpu.executor import precompiled as pc

# a registration is (user, opening balance)


def prefund(storage, config: dict) -> None:
    """Nothing: the users are what the transactions bring."""


def op(config: dict, seed: int, i: int) -> tuple[bytes, int]:
    """The i-th registration of this seed: a user of its own and an
    opening balance of 1 to 1,000,000 — but every eighth request names the
    user of the request seven before it, with a balance of its own:
    whichever of the two the chain orders second it has to refuse."""
    rng = random.Random((seed << 24) ^ i)
    named = i - 7 if i % 8 == 7 else i
    return b"user-%d-%07d" % (seed, named), 1 + rng.randrange(1_000_000)


def call(reg: tuple[bytes, int]) -> tuple[bytes, bytes]:
    user, balance = reg
    return pc.BALANCE_ADDRESS, pc.encode_call(
        "register", lambda w: w.blob(user).u64(balance))


def touched(reg: tuple[bytes, int]) -> tuple[bytes]:
    return reg[:1]


def read_call(group: str, user: bytes) -> tuple[str, list]:
    return ("call", [group, "", "0x" + pc.BALANCE_ADDRESS.hex(),
                     "0x" + pc.encode_call(
                         "balanceOf", lambda w: w.blob(user)).hex()])


def decode(answer: dict) -> int:
    return int(answer["output"][2:], 16)
