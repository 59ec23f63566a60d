"""Signed transactions of the configuration's kind, from the seed.

The client's side of the wire belongs to the system under test (its
transaction encoding and its native signer), so this is where the load
generator calls into the package — with the host suite, on the CPU. What a
transaction is (the operation drawn, its `to` and input, the keys it
touches and how they are read back) is the kind's, `workloads/<kind>.py`;
nonce, block limit, signing and encoding are the frame's. What the
reference needs of a transaction (the operation, and the hash the client
sent it under) is returned beside the wire bytes.
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _beside(name: str):
    """A module of this directory, by its path: tier-1's tests load this
    file by path into a process whose `sys.path`, which is left alone,
    does not hold the harness."""
    path = os.path.join(HERE, f"{name}.py")
    for key in (name, f"chipbench_{name}"):
        mod = sys.modules.get(key)
        if mod is not None and getattr(mod, "__file__", None) == path:
            return mod
    spec = importlib.util.spec_from_file_location(f"chipbench_{name}", path)
    mod = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TxMaker:
    def __init__(self, config: dict, seed: int):
        from fisco_bcos_tpu.crypto.suite import make_suite
        from fisco_bcos_tpu.protocol import Transaction

        self._Transaction = Transaction
        self.kind = _beside("manifest").workload(config)
        self.config = config
        self.suite = make_suite(bool(config["sm_crypto"]), backend="host")
        self.keypair = self.suite.generate_keypair(
            b"chipbench-client-%d" % seed)
        self.seed = seed

    def move(self, i: int) -> tuple:
        """The i-th operation of this seed, a plain tuple."""
        return self.kind.op(self.config, self.seed, i)

    def make(self, i: int, block_limit: int) -> tuple[str, str, tuple]:
        """-> (wire hex, tx hash hex, operation) of the i-th transaction."""
        mv = self.move(i)
        to, data = self.kind.call(mv)
        tx = self._Transaction(
            to=to, input=data,
            nonce=f"cb-{self.seed}-{i}", block_limit=block_limit)
        tx.sign(self.suite, self.keypair)
        return ("0x" + tx.encode().hex(),
                "0x" + tx.hash(self.suite).hex(), mv)

    def balance_call(self, group: str, key: bytes) -> tuple[str, list]:
        return self.kind.read_call(group, key)
