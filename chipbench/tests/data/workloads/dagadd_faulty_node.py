#!/usr/bin/env python3
"""A node daemon whose `register` answers status 0 and opens the user with
one unit more than was sent (an answer altered where it is produced) —
`../../faulty_node.py`'s way, for the kind `dagadd`. Every node starts
through this file, so the replicas agree and only the reference can tell.
"""

import sys


def _break() -> None:
    from fisco_bcos_tpu.executor import precompiled as pc

    real = pc.BalancePrecompile._set

    def _set(ctx, account, amount):
        real(ctx, account, amount + 1)

    pc.BalancePrecompile._set = staticmethod(_set)


if __name__ == "__main__":
    _break()
    from fisco_bcos_tpu.__main__ import main

    sys.exit(main(sys.argv[1:]))
