"""DagTransfer `transfer` between prefunded users — the client's side.

java-sdk-demo ParallelOkPerf's second phase over upstream's
DagTransferPrecompiled: what is in every node's storage before its first
block, the i-th transfer of a seed, its call, the accounts it touches and
how their balances are read back. The client's side of the wire belongs to
the system under test (its call encoding, its precompile's address, its
prefund), so this file calls into the package — on the CPU. Its plain
reference, `dagtransfer_reference.py`, does not.
"""

from __future__ import annotations

import random

from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.testing.scenario import (ACCOUNT_BALANCE, ScenarioSpec,
                                             prefund_storage)

# a transfer is (source account, destination account, amount)
Move = tuple[bytes, bytes, int]


def prefund(storage, config: dict) -> None:
    """The prefunded users, written into one node's storage before its
    first block (upstream's perf test registers them first)."""
    if ACCOUNT_BALANCE != config["prefund_balance"]:
        raise ValueError("the program prefunds another balance than "
                         "the configuration states")
    rows = prefund_storage(
        storage, ScenarioSpec("hot-key", accounts=config["accounts"]))
    if rows != config["accounts"]:
        raise ValueError(f"prefunded {rows} accounts")


def op(config: dict, seed: int, i: int) -> Move:
    """The i-th transfer of this seed: from and to uniform over the
    prefunded users, never equal; the amount cycles 1-7."""
    accounts = int(config["accounts"])
    rng = random.Random((seed << 24) ^ i)
    a = rng.randrange(accounts)
    b = (a + 1 + rng.randrange(accounts - 1)) % accounts
    return b"acct-%07d" % a, b"acct-%07d" % b, 1 + i % 7


def call(move: Move) -> tuple[bytes, bytes]:
    """-> (to, input) of the transaction that makes this transfer."""
    src, dst, amt = move
    return pc.DAG_TRANSFER_ADDRESS, pc.encode_call(
        "transfer", lambda w: w.blob(src).blob(dst).u64(amt))


def touched(move: Move) -> tuple[bytes, bytes]:
    """The keys whose state the transfer changes: both accounts."""
    return move[:2]


def read_call(group: str, account: bytes) -> tuple[str, list]:
    """The RPC call that reads one key back."""
    return ("call", [group, "", "0x" + pc.DAG_TRANSFER_ADDRESS.hex(),
                     "0x" + pc.encode_call(
                         "balanceOf", lambda w: w.blob(account)).hex()])


def decode(answer: dict) -> int:
    """What `read_call`'s answer says the key holds."""
    return int(answer["output"][2:], 16)
