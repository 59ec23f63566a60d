#!/usr/bin/env python3
"""A node daemon whose SmallBank `sendPayment` skips its refusal: a
payment larger than the payer's checking goes through and overdraws it —
`faulty_node.py`'s way, for the kind `smallbank`. Every node starts
through this file, so the replicas agree and only the reference can tell.
"""

import sys


def _break() -> None:
    from fisco_bcos_tpu.executor import precompiled as pc

    def _send_payment(self, ctx, r, w):
        a, (sa, ca), b, (sb, cb) = self._pair(ctx, r)
        v = r.i64()
        self._put(ctx, a, sa, ca - v)
        self._put(ctx, b, sb, cb + v)

    pc.SmallBankPrecompile._send_payment = _send_payment


if __name__ == "__main__":
    _break()
    from fisco_bcos_tpu.__main__ import main

    sys.exit(main(sys.argv[1:]))
