#!/usr/bin/env python3
"""A node daemon with the timed path broken underneath — tests only.

    CHIPBENCH_FAULT=<fault> faulty_node.py <daemon arguments>

Every node of the cluster starts through this file, so the replicas agree
with each other and only the plain reference can tell. Faults:

  state_unchanged   a transfer answers status 0, logs itself and moves
                    nothing (a step that returns its state unchanged)
  answer_altered    a transfer credits one unit more than it debits (an
                    answer altered where it is produced)
  half_batch        every second sendTransaction of a JSON-RPC batch is
                    acknowledged with a receipt and never reaches the pool
                    (half of the batch left out)
"""

import os
import sys


def _break(fault: str) -> None:
    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.protocol import LogEntry

    cls = pc.BalancePrecompile
    if fault in ("state_unchanged", "answer_altered"):
        extra = 1 if fault == "answer_altered" else 0

        def _transfer(self, ctx, r, w):
            src, dst, amount = r.blob(), r.blob(), r.u64()
            self.touch(ctx, pc.T_BALANCE.encode() + src,
                       pc.T_BALANCE.encode() + dst)
            if extra:
                self._set(ctx, src, self._get(ctx, src) - amount)
                self._set(ctx, dst, self._get(ctx, dst) + amount + extra)
            ctx.logs.append(LogEntry(
                address=ctx.to, topics=[b"transfer"],
                data=src + dst + amount.to_bytes(8, "big")))
            w.u32(0)

        cls._transfer = _transfer
        return
    if fault != "half_batch":
        raise SystemExit(f"faulty_node: unknown fault {fault!r}")

    import contextlib

    from fisco_bcos_tpu.codec.wire import Reader
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.rpc import server

    impl = server.JsonRpcImpl
    real_cohort, real_send = impl.cohort, impl.send_transaction

    @contextlib.contextmanager
    def cohort(self, payload):
        self._tl.left_out = {e["params"][2] for e in payload[1::2]
                             if e.get("method") == "sendTransaction"}
        with real_cohort(self, payload[0::2]):
            yield
        self._tl.left_out = set()

    def send_transaction(self, group, node_name="", tx_hex="", *a, **kw):
        if tx_hex not in getattr(self._tl, "left_out", ()):
            return real_send(self, group, node_name, tx_hex, *a, **kw)
        tx = Transaction.decode(server._unhex(tx_hex))
        r = Reader(tx.input)
        r.text()
        src, dst, amt = r.blob(), r.blob(), r.u64()
        return {"version": 0, "blockNumber": 1, "status": 0, "gasUsed": "0",
                "transactionHash": server._hex(tx.hash(self.node.suite)),
                "contractAddress": "", "output": "0x00000000", "message": "",
                "logEntries": [{
                    "address": server._hex(tx.to), "topics": ["0x"],
                    "data": server._hex(src + dst + amt.to_bytes(8, "big"))}]}

    impl.cohort, impl.send_transaction = cohort, send_transaction


if __name__ == "__main__":
    _break(os.environ["CHIPBENCH_FAULT"])
    from fisco_bcos_tpu.__main__ import main

    sys.exit(main(sys.argv[1:]))
