"""ParallelOk `transfer` between prefunded users — the client's side.

java-sdk-demo ParallelOkPerf in its `parallelok` (Solidity) mode: the
demo contract `ParallelOk.sol` (`fisco_bcos_tpu/testing/parallelok.py`,
hand-assembled in the compiler's shape), called through the EVM and the
native interpreter. What `add` and `enableParallel()` leave in every
node's storage before its first block — the contract's code, its ABI with
the parallel annotations, one `_balance` row a user — is written by
`prefund`; the i-th transfer of a seed is DagTransfer's draw, word for
word; a balance reads back over RPC `call` of `balanceOf(name)`. This file
calls into the package, on the CPU; its plain reference,
`parallelok_reference.py`, does not.
"""

from __future__ import annotations

import random

from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.executor.evm import T_CODE, T_STORE
from fisco_bcos_tpu.testing import parallelok as po

# a transfer is (source user, destination user, amount)
Move = tuple[bytes, bytes, int]

# the selectors are Keccak's: `call` is given no configuration, so the
# kind refuses an SM chain (whose selectors are SM3's) in `prefund`
_KECCAK = make_suite(False, backend="host").hash


def prefund(storage, config: dict) -> None:
    """The deployed contract and the users `add` would have `set`, written
    into one node's storage before its first block."""
    if config["sm_crypto"]:
        raise ValueError("parallelok encodes Keccak selectors; an SM "
                         "chain's are SM3's")
    accounts, balance = int(config["accounts"]), config["prefund_balance"]
    rows = po.deploy(storage, (b"acct-%07d" % i for i in range(accounts)),
                     balance, _KECCAK)
    if rows != accounts:
        raise ValueError(f"set {rows} balances of {accounts}")
    if storage.get(T_CODE, po.ADDRESS) != po.runtime_code(_KECCAK) or \
            storage.get(T_STORE, po.slot_key(b"acct-%07d" % (accounts - 1),
                                             _KECCAK)) \
            != balance.to_bytes(32, "big"):
        raise ValueError("the storage does not read back the deployment")


def op(config: dict, seed: int, i: int) -> Move:
    """The i-th transfer of this seed: from and to uniform over the
    prefunded users, never equal; the amount cycles 1-7 (DagTransfer's)."""
    accounts = int(config["accounts"])
    rng = random.Random((seed << 24) ^ i)
    a = rng.randrange(accounts)
    b = (a + 1 + rng.randrange(accounts - 1)) % accounts
    return b"acct-%07d" % a, b"acct-%07d" % b, 1 + i % 7


def call(move: Move) -> tuple[bytes, bytes]:
    """-> (to, input): `transfer(string,string,uint256)`, ABI-encoded."""
    return po.ADDRESS, po.encode("transfer", *move, hash_fn=_KECCAK)


def touched(move: Move) -> tuple[bytes, bytes]:
    """The keys whose state the transfer changes: both users."""
    return move[:2]


def read_call(group: str, name: bytes) -> tuple[str, list]:
    """The RPC call that reads one user's balance back."""
    return ("call", [group, "", "0x" + po.ADDRESS.hex(), "0x" + po.encode(
        "balanceOf", name, hash_fn=_KECCAK).hex()])


def decode(answer: dict):
    """The uint256 `balanceOf` returns; None where the call did not."""
    out = answer.get("output") or ""
    if answer.get("status") != 0 or len(out) != 2 + 64:
        return None
    return int(out[2:], 16)
