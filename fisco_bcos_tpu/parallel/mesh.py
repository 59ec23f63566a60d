"""Device-mesh plane: shard the crypto batch over local chips.

This is the framework's ICI communication backend (SURVEY §2 "distributed
communication backend" + §5's 64k-block scaling analogue): the reference
spreads its per-tx signature work across CPU cores with a tbb parallel
loop sized by `txpool.verify_worker_num`
(/root/reference/bcos-txpool/bcos-txpool/sync/TransactionSync.cpp:516-537,
 /root/reference/bcos-tool/bcos-tool/NodeConfig.cpp:486); here the same
scaling axis is the TPU **device mesh** — one `jax.sharding.Mesh` over the
host's chips with the batch data-parallel on a "dp" axis. XLA inserts the
ICI collectives; the kernels themselves are unchanged. Scope: the three
SIGNATURE kernels (verify / SM2 verify / recover) and the MERKLE-ROOT
reduction are sharded — they dominate block validation. The signature
kernels are elementwise over the batch except the batched-inversion
product tree, whose upper levels become cross-shard collectives; the
Merkle tree's upper levels cross shards the same way (the
sequence-parallel analogue). Per-message hashing stays single-device.

`CryptoSuite(mesh_devices=N)` routes its device path through `MeshKernels`;
`__graft_entry__.dryrun_multichip` exercises the same sharding on whatever
devices JAX has; the tests run it on an 8-device host-platform mesh
(tests/conftest.py).
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np


def local_mesh(n_devices: Optional[int] = None):
    """-> Mesh over the largest power-of-two prefix of `n_devices` local
    devices (default: all of them) on a 1-D "dp" axis; None when fewer
    than two were asked for (the unsharded path). Asked for more devices
    than JAX has, it raises — a node configured for 4 chips that finds 1
    must not quietly run unsharded."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n < 2:
        return None
    if n > len(devs):
        raise RuntimeError(
            f"mesh over {n} devices asked for, JAX has {len(devs)} "
            f"({devs[0].platform})")
    n = 1 << (n.bit_length() - 1)
    return Mesh(np.array(devs[:n]), ("dp",))


class MeshKernels:
    """Sharded jit wrappers for the EC signature kernels.

    Compiled executables are cached per (kernel, curve) — shapes vary only
    by the suite's bucket sizes, which jit caches internally. Batch sizes
    must be divisible by the mesh size (the suite pads buckets, all powers
    of two >= the mesh size).
    """

    def __init__(self, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.n_devices = mesh.devices.size
        self._data = NamedSharding(mesh, P("dp", None))  # [B, L] arrays
        self._flat = NamedSharding(mesh, P("dp"))  # [B] arrays
        self._jits: dict = {}
        self._lock = threading.Lock()
        self._jax = jax

    def _get(self, name: str, fn, n_mat: int, n_flat: int, out_spec,
             static_argnums=0, in_shardings=None):
        """Sharded jit of fn, cached by name. Default arg layout: a static
        leading curve arg, then n_mat [B, L] args and n_flat [B] args;
        pass explicit static_argnums/in_shardings for other shapes."""
        with self._lock:
            got = self._jits.get(name)
            if got is None:
                if in_shardings is None:
                    in_shardings = (self._data,) * n_mat \
                        + (self._flat,) * n_flat
                got = self._jax.jit(
                    fn.__wrapped__ if hasattr(fn, "__wrapped__") else fn,
                    static_argnums=static_argnums,
                    in_shardings=in_shardings,
                    out_shardings=out_spec)
                self._jits[name] = got
            return got

    def _put(self, arrs, shardings):
        return [self._jax.device_put(a, s) for a, s in zip(arrs, shardings)]

    def verify(self, curve, e, r, s, qx, qy):
        from ..ops import ec

        fn = self._get("ecdsa_verify", ec.ecdsa_verify_batch, 5, 0,
                       self._flat)
        args = self._put((e, r, s, qx, qy), (self._data,) * 5)
        return fn(curve, *args)

    def sm2_verify(self, curve, e, r, s, qx, qy):
        from ..ops import ec

        fn = self._get("sm2_verify", ec.sm2_verify_batch, 5, 0, self._flat)
        args = self._put((e, r, s, qx, qy), (self._data,) * 5)
        return fn(curve, *args)

    def recover(self, curve, e, r, s, v):
        from ..ops import ec

        fn = self._get("ecdsa_recover", ec.ecdsa_recover_batch, 3, 1,
                       (self._data, self._data, self._flat))
        args = self._put((e, r, s), (self._data,) * 3) + self._put(
            (v,), (self._flat,))
        return fn(curve, *args)

    def merkle_root(self, leaves, n, alg: str):
        """Sharded width-16 tree reduction: leaves split on "dp", the
        log-depth reduction's upper levels cross shards (the
        sequence-parallel analogue of SURVEY §2's parallelism table)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..ops import merkle

        rep = NamedSharding(self.mesh, P())
        fn = self._get(f"merkle-{alg}", merkle._merkle_root_bucketed,
                       0, 0, rep, static_argnums=(2,),
                       in_shardings=(self._data, rep))
        leaves = self._jax.device_put(leaves, self._data)
        return fn(leaves, n, alg)
