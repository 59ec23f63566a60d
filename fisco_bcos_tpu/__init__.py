"""fisco_bcos_tpu — a TPU-native consortium-blockchain framework.

A ground-up rebuild of the capability surface of FISCO-BCOS (reference:
/root/reference, C++20) designed TPU-first:

- The crypto plane — the per-transaction/per-consensus-message hot path of the
  reference (Transaction::verify, PBFT checkSignature, block Merkle roots) —
  is *batch-native*: secp256k1/SM2 ECDSA verification & public-key recovery
  and Keccak256/SM3 Merkle hashing run as vmapped JAX kernels on TPU, sharded
  over a device mesh for large blocks.
- The node runtime (txpool, sealer, PBFT, scheduler/executor, ledger, storage,
  gateway, RPC) is an async Python/C++ stack mirroring the reference's module
  interfaces (bcos-framework/bcos-framework/*/...Interface.h), with native C++
  components where the reference is native-critical.

Subpackage map (reference analogue in parentheses):
  ops/        device kernels: bigint, EC, Keccak, SM3, Merkle (bcos-crypto internals)
  crypto/     CryptoSuite / SignatureCrypto / Hash, batch-first (bcos-crypto interfaces)
  codec/      ABI + scale-like codecs (bcos-codec)
  protocol/   Transaction/Block/Receipt/BlockHeader (bcos-framework protocol + bcos-tars-protocol)
  storage/    KV storage with 2PC, state overlays (bcos-storage, bcos-table)
  ledger/     chain schema on storage (bcos-ledger)
  txpool/     pending-tx store + TPU batch validator (bcos-txpool)
  sealer/     proposal batching (bcos-sealer)
  consensus/  PBFT engine (bcos-pbft)
  sync/       block sync (bcos-sync)
  scheduler/  block execution orchestration, DAG/DMC (bcos-scheduler)
  executor/   transaction execution + precompiles (bcos-executor)
  front/ gateway/  message bus + P2P (bcos-front, bcos-gateway)
  rpc/ sdk/   JSON-RPC access layer + client SDK (bcos-rpc, bcos-sdk)
  parallel/   device-mesh sharding of the crypto plane (ICI-scale batching)
  utils/      logging, workers, bytes (bcos-utilities)
  tool/ init/ node config + composition root (bcos-tool, libinitializer)
"""

__version__ = "0.1.0"

import os as _os


def _setup_compilation_cache() -> None:
    """Enable JAX's persistent compilation cache for every consumer.

    The EC kernels are large programs; without a disk cache every daemon
    start, test run and bench re-pays their compilation. Configured here
    (package import) so all entry points share one cache. Its place is
    decided OUTSIDE the code: where JAX_COMPILATION_CACHE_DIR is set, JAX
    reads it itself and no directory is set here; otherwise the cache
    lives at the fixed path <checkout>/.jax_cache (the path is part of the
    cache key — a directory that moves never hits).
    """
    import jax

    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(
                _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
                ".jax_cache"))
    # A Pallas kernel is serialized into its program WITH its MLIR
    # locations, and by default those carry the Python call stack: the
    # cache key of every program holding a kernel then depends on who
    # called it, and what one entry point compiled (a warm-up, the smoke's
    # kernel stage) never hits for another (a daemon). One frame per
    # location keeps the key a function of the program alone.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")


_setup_compilation_cache()
