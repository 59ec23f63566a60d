"""Signed DagTransfer transfers from the seed.

The client's side of the wire belongs to the system under test (its
transaction encoding and its native signer), so this is the one place the
load generator calls into the package — with the host suite, on the CPU.
What the reference needs of a transaction (who pays whom how much, and the
hash the client sent it under) is returned beside the wire bytes.
"""

from __future__ import annotations

import random

# a transfer is (source account, destination account, amount)
Move = tuple[bytes, bytes, int]


class TxMaker:
    def __init__(self, config: dict, seed: int):
        from fisco_bcos_tpu.crypto.suite import make_suite
        from fisco_bcos_tpu.executor import precompiled as pc
        from fisco_bcos_tpu.protocol import Transaction

        self._pc, self._Transaction = pc, Transaction
        self.suite = make_suite(bool(config["sm_crypto"]), backend="host")
        self.keypair = self.suite.generate_keypair(
            b"chipbench-client-%d" % seed)
        self.accounts = int(config["accounts"])
        self.seed = seed

    def move(self, i: int) -> Move:
        """The i-th transfer of this seed: from and to uniform over the
        prefunded users, never equal; the amount cycles 1-7."""
        rng = random.Random((self.seed << 24) ^ i)
        a = rng.randrange(self.accounts)
        b = (a + 1 + rng.randrange(self.accounts - 1)) % self.accounts
        return b"acct-%07d" % a, b"acct-%07d" % b, 1 + i % 7

    def make(self, i: int, block_limit: int) -> tuple[str, str, Move]:
        """-> (wire hex, tx hash hex, move) of the i-th transfer."""
        src, dst, amt = mv = self.move(i)
        data = self._pc.encode_call(
            "transfer", lambda w: w.blob(src).blob(dst).u64(amt))
        tx = self._Transaction(
            to=self._pc.DAG_TRANSFER_ADDRESS, input=data,
            nonce=f"cb-{self.seed}-{i}", block_limit=block_limit)
        tx.sign(self.suite, self.keypair)
        return ("0x" + tx.encode().hex(),
                "0x" + tx.hash(self.suite).hex(), mv)

    def balance_call(self, group: str, account: bytes) -> tuple[str, list]:
        pc = self._pc
        return ("call", [group, "", "0x" + pc.DAG_TRANSFER_ADDRESS.hex(),
                         "0x" + pc.encode_call(
                             "balanceOf", lambda w: w.blob(account)).hex()])
