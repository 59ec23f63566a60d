"""Batch-first CryptoSuite — the framework's central crypto seam.

Reference counterpart: `CryptoSuite` / `SignatureCrypto` / `Hash`
(/root/reference/bcos-crypto/bcos-crypto/interfaces/crypto/CryptoSuite.h:33-69,
 Signature.h:31-59, Hash.h), selected at node boot by chain config
(libinitializer/ProtocolInitializer.cpp:62-123: Keccak256+Secp256k1 vs
SM3+SM2). The reference exposes scalar virtuals and wraps them in tbb loops
(TransactionSync.cpp:516-537); here the interface is **batch-native**:

    verify_batch(hashes, sigs, pubs)  -> bool[N]
    recover_batch(hashes, sigs)       -> (pubs[N], ok[N])
    hash_batch(msgs)                  -> digest[N]
    merkle_root(leaves)               -> digest

with the single-item API as the degenerate case. Large batches run on the
TPU kernels (`ops.ec`, `ops.keccak`, `ops.sm3`, `ops.merkle`), padded to a
small set of bucket sizes so XLA compiles once per bucket; small batches, and
every batch where JAX reports no TPU, take the native host path (`nativeec`,
`nativehash`). Results are bit-identical across paths (SURVEY §4
golden-value requirement).

The seam says where each call ran: `status()` carries the backend as
configured, the platform JAX resolved, per-op device/host call and item
counts, the lanes the device calls were issued with (padding to the
bucket included), the host's seconds around each device call (packing the
arguments, the call until every output is numpy, unpacking them into the
values returned) and the process's compile count —
`Node.system_status()["crypto"]`.

Signing stays host-side and single-item: a node signs only its own messages
(one per PBFT phase — PBFTCodec.cpp:47), never in bulk.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Sequence

import numpy as np

from . import refimpl
from ..analysis import lockcheck as _lc
from ..ops import bigint, ec, keccak, merkle, platform, sm3
from ..utils.log import LOG, badge

DIGEST = 32

# batch buckets: pad N up to the next one; one compiled executable per bucket
BUCKETS = (8, 64, 512, 4096, 16384, 65536)
# batches above this run as a pipeline of CHUNK-sized kernel calls: jax's
# async dispatch overlaps chunk k+1's host->device staging with chunk k's
# compute (the double-buffered staging of SURVEY §5's 64k-block analogue),
# reuses one compiled executable instead of a giant bucket, and caps
# padding waste for sizes between buckets
CHUNK = 16384

# device hashing covers messages up to this many rate/compression blocks
# (1 KiB of Keccak input), bucketed to powers of two; longer messages in a
# batch take the host hasher. One contract deploy would otherwise inflate
# the block axis for the whole batch AND be a fresh compile mid-serving.
HASH_MAX_BLOCKS = 8

_OPS = ("recover", "verify", "hash", "merkle", "poseidon")


class DeviceUnavailable(RuntimeError):
    """backend = device was configured and JAX reports no TPU."""


class DeviceError(RuntimeError):
    """A device-path call failed to compile, lower or run. Inputs are
    validated and packed on the host before the call, so this is never a
    data error: bad signatures come back as ok=False, not as exceptions."""


class _CompileLog:
    """Process-wide XLA compile accounting read from jax.monitoring: every
    compile request (a new program shape, whether or not the persistent
    cache served it), seconds spent, and persistent-cache hits/misses."""

    def __init__(self):
        self._lock = threading.Lock()
        self._installed = False
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1
                self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.cache_misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiles": self.compiles,
                    "compileSeconds": round(self.seconds, 3),
                    "cacheHits": self.cache_hits,
                    "cacheMisses": self.cache_misses}


COMPILE_LOG = _CompileLog()

# substitute row for malformed (short) signatures on the rows fast path:
# r=s=0 is rejected by every verify/recover backend, same as _split_sigs
_ZERO32 = b"\x00" * 32


def _bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return ((n + BUCKETS[-1] - 1) // BUCKETS[-1]) * BUCKETS[-1]


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _chunks(n: int) -> list[tuple[int, int]]:
    """[(offset, length)] covering n in CHUNK-sized pieces."""
    return [(o, min(CHUNK, n - o)) for o in range(0, n, CHUNK)]


def _split_rows(buf: bytes, width: int) -> list[bytes]:
    """Rows of `width` bytes out of one buffer: one slice a row."""
    return [buf[o:o + width] for o in range(0, len(buf), width)]


def _pad_rows(a: np.ndarray, n: int) -> np.ndarray:
    if a.shape[0] == n:
        return a
    pad = np.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)
    return np.concatenate([a, pad], axis=0)


def _frame(rows: Sequence[bytes], width: int, fix) -> np.ndarray:
    """The rows as one uint8[N, width] array: one join, one view, no copy
    per row. A row of another length is malformed (served traffic has
    none) and goes through `fix` first, which pads or cuts it to `width`
    or raises."""
    lens = np.fromiter(map(len, rows), np.intp, len(rows))
    odd = np.flatnonzero(lens != width)
    if odd.size:
        rows = list(rows)
        for i in odd.tolist():
            rows[i] = fix(bytes(rows[i]))
    return np.frombuffer(b"".join(rows), np.uint8).reshape(len(rows), width)


def _fix_digest(d: bytes) -> bytes:
    """A digest of another length as the 32-byte row of the same integer:
    left-padded with zeros; one that does not fit 256 bits is an error of
    the call (`bigint.to_limbs` raises the same)."""
    if any(d[:-DIGEST]):
        raise ValueError(f"out of range for {bigint.NLIMBS} limbs: "
                         f"digest of {len(d)} bytes")
    return d[-DIGEST:].rjust(DIGEST, b"\x00")


def _fix_pub(p: bytes) -> bytes:
    """A key of another length as qx = p[:32], qy = p[32:64], each the
    32-byte row of the same integer."""
    return p[:32].rjust(32, b"\x00") + p[32:64].rjust(32, b"\x00")


@dataclasses.dataclass(frozen=True)
class KeyPair:
    """Node/account key pair. secret stays host-side (signing is host-only)."""

    secret: int
    pub: tuple[int, int]
    suite: "CryptoSuite"

    @property
    def pub_bytes(self) -> bytes:
        return self.pub[0].to_bytes(32, "big") + self.pub[1].to_bytes(32, "big")

    @property
    def address(self) -> bytes:
        return self.suite.address_of_pub(self.pub_bytes)


class CryptoSuite:
    """A hash + signature algorithm bundle with batch-native device paths.

    kind: "ecdsa" (secp256k1 + Keccak256, default chain) or
          "sm" (SM2 + SM3, 国密 chain) — mirrors chain.sm_crypto selection
          (ProtocolInitializer.cpp:102/:110).
    backend: "device" | "host" | "auto", as configured ([crypto] backend).
          The platform JAX reports decides what that means (asked once,
          in `_device_enabled`, and logged): "auto" takes the JAX kernels for
          batches at or above `device_min_batch` ONLY when the platform is
          "tpu" and the native host path otherwise — on a host with no
          chip no batch ever reaches XLA:CPU; "device" takes them for
          every batch and refuses to run without a TPU
          (`DeviceUnavailable`); "host" never touches JAX. 512 is a pick
          inside a band the chip has not measured yet (ROADMAP queue 1
          item 4).
    mesh_devices: shard device batches over this many local chips (a
          `jax.sharding.Mesh` "dp" axis — the ICI analogue of the
          reference's txpool.verify_worker_num tbb fan-out). 0/None =
          single-device; the mesh is built on first device use so
          constructing a suite never touches the accelerator backend.
    allow_cpu: code-only switch for tests that want the JAX kernels on
          XLA:CPU: with it, "device"/"auto" accept any platform. Not
          reachable from the ini or the environment.
    """

    def __init__(self, kind: str = "ecdsa", backend: str = "auto",
                 device_min_batch: int = 512,
                 mesh_devices: int | None = None, *,
                 allow_cpu: bool = False):
        if kind not in ("ecdsa", "sm"):
            raise ValueError(f"unknown crypto suite kind: {kind}")
        if backend not in ("device", "host", "auto"):
            raise ValueError(f"unknown crypto backend: {backend}")
        self.kind = kind
        self.backend = backend
        self.device_min_batch = device_min_batch
        self.mesh_devices = mesh_devices or 0
        self.allow_cpu = allow_cpu
        self._mesh_kernels = None
        self._mesh_tried = False
        self._device_ok: bool | None = None  # resolved by _device_enabled
        # observers of DeviceError (Node wires its health plane here): a
        # failed kernel must be loud wherever the call came from — a lane
        # that survives by rejecting the batch would otherwise read as
        # 1,000 invalid signatures and a chain that simply stops
        self.on_device_error: list[Callable[[str], None]] = []
        self._stats_lock = threading.Lock()
        # per op: device calls/items, host calls/items, the device
        # calls' cumulative pack/call/unpack seconds, the lanes they
        # were issued with (items plus the padding to their buckets),
        # the native calls the host batches were issued as and the items
        # of those issued as more than one (nativeec.parts_of)
        self._stats = {op: [0, 0, 0, 0, 0.0, 0.0, 0.0, 0, 0, 0]
                       for op in _OPS}
        self._ready: dict | None = None  # set by prepare()
        from . import nativehash

        if kind == "ecdsa":
            self.curve = ec.SECP256K1
            self.params = refimpl.SECP256K1
            self.hash_name = "keccak256"
            self._dev_hash = (keccak.keccak256_varlen, keccak.pad_tail,
                              keccak.nblocks_of, keccak.RATE_BYTES)
            self._host_hash = nativehash.host_hash("keccak256")
            self._host_hash_batch = nativehash.host_hash_batch("keccak256")
            self.signature_size = 65  # r(32) | s(32) | v(1)
        else:
            self.curve = ec.SM2P256V1
            self.params = refimpl.SM2P256V1
            self.hash_name = "sm3"
            self._dev_hash = (sm3.sm3_varlen, sm3.pad_tail,
                              sm3.nblocks_of, sm3.BLOCK_BYTES)
            self._host_hash = nativehash.host_hash("sm3")
            self._host_hash_batch = nativehash.host_hash_batch("sm3")
            self.signature_size = 128  # r(32) | s(32) | pub(64), SignatureDataWithPub.h

    # -- identity ----------------------------------------------------------
    def __repr__(self):
        return f"CryptoSuite({self.kind}, backend={self.backend})"

    # -- where calls run ---------------------------------------------------
    def _device_enabled(self) -> bool:
        """May this suite send a batch to the JAX kernels? Asks JAX for its
        platform the first time (initialising the backend; whatever that
        raises — chip held by another process — propagates)."""
        if self.backend == "host":
            return False
        if self._device_ok is None:
            plat = platform.resolve()
            ok = plat.platform == "tpu" or self.allow_cpu
            COMPILE_LOG.install()
            LOG.info(badge("CRYPTO", "platform", backend=self.backend,
                           platform=plat.platform, kind=plat.device_kind,
                           devices=plat.count,
                           route="jax-kernels" if ok else "host"))
            if not ok and self.backend == "device":
                raise DeviceUnavailable(
                    f"[crypto] backend = device, but JAX reports platform "
                    f"{plat.platform!r} ({plat.device_kind}): no TPU, or it "
                    f"is held by another process (a chip belongs to one "
                    f"process at a time)")
            self._device_ok = ok
        return self._device_ok

    def _use_device(self, n: int) -> bool:
        if not self._device_enabled():
            return False
        return self.backend == "device" or n >= self.device_min_batch

    def _count_host(self, op: str, n: int, parts: int = 1) -> None:
        with self._stats_lock:
            row = self._stats[op]
            row[2] += 1
            row[3] += n
            row[8] += parts
            if parts > 1:
                row[9] += n

    def _on_device(self, op: str, n: int, lanes: int, t_in: float, call,
                   unpack=None):
        """Run one device-path call of `n` items in `lanes` lanes, packed
        since `t_in` (for the EC ops that is `_stage`, where bytes become
        limbs, and `_pad_chunks`): `call()` issues the kernel and returns
        its outputs as numpy, `unpack(outputs)` makes the values the caller
        returns (for recover, where limbs become bytes).
        Inputs were validated and packed on the host, so whatever `call`
        raises is a compile, lowering or device failure: wrapped as
        DeviceError and reported to the observers. One clock read per
        boundary, nothing per item."""
        t_call = time.monotonic()
        try:
            out = call()
        except Exception as exc:
            err = DeviceError(f"{op} n={n}: {type(exc).__name__}: {exc}")
            LOG.critical(badge("CRYPTO", "device-call-failed", op=op, n=n,
                               error=repr(exc)[:500]))
            for cb in list(self.on_device_error):
                cb(str(err))
            raise err from exc
        t_out = time.monotonic()
        if unpack is not None:
            out = unpack(out)
        t_done = time.monotonic()
        with self._stats_lock:
            row = self._stats[op]
            row[0] += 1
            row[1] += n
            row[4] += t_call - t_in
            row[5] += t_out - t_call
            row[6] += t_done - t_out
            row[7] += lanes
        return out

    def status(self) -> dict:
        """The `crypto` block of Node.system_status(): a read of state the
        seam already holds. `platform` is None until something asked (a
        host suite never does)."""
        plat = platform.resolve() if self._device_ok is not None else None
        with self._stats_lock:
            ops_ = {op: {"deviceCalls": r[0], "deviceItems": r[1],
                         "deviceLanes": r[7],
                         "hostCalls": r[2], "hostItems": r[3],
                         "hostParts": r[8], "hostSplitItems": r[9],
                         "packSeconds": r[4], "callSeconds": r[5],
                         "unpackSeconds": r[6]}
                    for op, r in self._stats.items()}
        out = {
            "kind": self.kind,
            "backend": self.backend,
            "platform": plat.platform if plat else None,
            "deviceKind": plat.device_kind if plat else None,
            "deviceCount": plat.count if plat else None,
            "deviceMinBatch": self.device_min_batch,
            "meshDevices": self.mesh_devices,
            # the Pallas kernels compile (Mosaic) on TPU and are off
            # everywhere else; there is no interpret mode on this path
            "pallas": "compiled" if (plat and plat.platform == "tpu"
                                     and self._device_ok) else "off",
            "ops": ops_,
            **COMPILE_LOG.snapshot(),
        }
        if self._ready is not None:
            out["readySeconds"] = self._ready["seconds"]
            out["compilesAtReady"] = self._ready["compiles"]
            out["compilesAfterReady"] = (out["compiles"]
                                         - self._ready["compiles"])
        return out

    def prepare(self, max_batch: int = CHUNK) -> None:
        """Resolve the platform and, on the device path, compile every
        program shape this suite will ask for with batches up to
        `max_batch` — before the node opens RPC or seals, not in the
        middle of a view timeout. Raises DeviceUnavailable for a `device`
        suite without a TPU. A host (or auto-on-CPU) suite returns at
        once. Records ready time and the compile count at ready."""
        t0 = time.monotonic()
        if self._device_enabled():
            first = _bucket(1 if self.backend == "device"
                            else self.device_min_batch)
            zsig = bytes(self.signature_size)
            block = self._dev_hash[3]
            for b in (x for x in BUCKETS
                      if first <= x <= _bucket(min(max_batch, CHUNK))):
                if self.kind == "ecdsa":
                    self.recover_batch([_ZERO32] * b, [zsig] * b)
                else:
                    self.verify_batch([_ZERO32] * b, [zsig] * b,
                                      [bytes(64)] * b)
                nb = 1
                while nb <= HASH_MAX_BLOCKS:  # a message of exactly nb blocks
                    self.hash_batch([bytes(block * (nb - 1) + 1)] * b)
                    nb *= 2
            for b in (x for x in BUCKETS if x >= first):
                self.merkle_root([_ZERO32] * b)
        self._ready = {"seconds": round(time.monotonic() - t0, 3),
                       "compiles": COMPILE_LOG.snapshot()["compiles"]}
        LOG.info(badge("CRYPTO", "ready", backend=self.backend,
                       seconds=self._ready["seconds"],
                       compiles=self._ready["compiles"]))

    # -- hashing -----------------------------------------------------------
    def hash(self, data: bytes) -> bytes:
        return self._host_hash(data)

    def hash_batch(self, msgs: Sequence[bytes]) -> list[bytes]:
        """Batched hashing. The device path pads the batch axis to the
        suite's buckets and the block axis to a power of two up to
        HASH_MAX_BLOCKS — a closed set of compiled shapes; longer messages
        in the batch take the host hasher. The host path crosses the FFI
        once for the whole batch."""
        n = len(msgs)
        if n == 0:
            return []
        _lc.note_blocking("suite_batch", "hash_batch")
        if not self._use_device(n):
            self._count_host("hash", n)
            return self._host_hash_batch(msgs)
        t_in = time.monotonic()
        kernel, tail, nblocks_of, block = self._dev_hash
        nblk = nblocks_of(np.fromiter(map(len, msgs), np.int64, n))
        fits = nblk <= HASH_MAX_BLOCKS
        big = np.flatnonzero(~fits).tolist()
        if big:
            small = np.flatnonzero(fits).tolist()
            self._count_host("hash", len(big))
            host = self._host_hash_batch([msgs[i] for i in big])
            msgs = [msgs[i] for i in small]
            nblk = nblk[fits]
        digests: list[bytes] = []
        for o, ln in _chunks(len(msgs)):
            bucket = _bucket(ln)
            blocks, nvalid = keccak.pack_batch_np(
                msgs[o:o + ln], tail, nblocks_of, block, bucket,
                _pow2(int(nblk[o:o + ln].max())))
            digests += self._on_device(
                "hash", ln, bucket, t_in,
                lambda: np.asarray(kernel(blocks, nvalid)),
                lambda rows: _split_rows(rows[:ln].tobytes(), DIGEST))
            t_in = time.monotonic()
        if not big:
            return digests
        out: list = [None] * n
        for i, d in zip(small, digests):
            out[i] = d
        for i, d in zip(big, host):
            out[i] = d
        return out

    def poseidon_batch(self, lefts: Sequence[bytes],
                       rights: Sequence[bytes]) -> list[bytes]:
        """Batched Poseidon arity-2 compression over the BN254 scalar
        field (zk/poseidon.py reference; zk/poseidon_jax.py lane-major
        batch path) — the SNARK-friendly hash the ZK proof plane builds
        its Merkle trees from. Inputs are 32-byte big-endian values
        (arbitrary digests canonicalize via one mod-r reduction); outputs
        are canonical field elements. Device gating follows hash_batch:
        the JAX path at/above device_min_batch, the host oracle below."""
        n = len(lefts)
        assert len(rights) == n
        if n == 0:
            return []
        _lc.note_blocking("suite_batch", "poseidon_batch")
        if not self._use_device(n):
            from ..zk import poseidon

            self._count_host("poseidon", n)
            return poseidon.hash2_batch_host(lefts, rights)
        from ..zk import poseidon_jax

        return self._on_device(
            "poseidon", n, n, time.monotonic(),
            lambda: poseidon_jax.hash2_batch(lefts, rights))

    def merkle_root(self, leaves: Sequence[bytes]) -> bytes:
        """Deterministic width-16 Merkle root over 32-byte leaf digests
        (protocol definition in ops.merkle; replaces BlockImpl.h:111,156).
        Device trees are padded to the suite's buckets; a tree beyond the
        largest bucket takes the host path (same root either way)."""
        n = len(leaves)
        if n == 0:
            return b"\x00" * DIGEST
        if n > BUCKETS[-1] or not self._use_device(n):
            self._count_host("merkle", n)
            return merkle.merkle_levels_host(list(leaves), self.hash_name)[-1][0]
        t_in = time.monotonic()
        arr = np.frombuffer(b"".join(leaves), np.uint8).reshape(n, DIGEST)
        mk = self._mesh()
        if mk is not None:
            bucket = max(merkle.WIDTH, mk.n_devices, _pow2(n))
            root = mk.merkle_root
        else:
            bucket = max(merkle.WIDTH, _bucket(n))
            root = merkle.merkle_root_padded
        arr = _pad_rows(arr, bucket)
        return self._on_device(
            "merkle", n, bucket, t_in,
            lambda: np.asarray(root(arr, np.int32(n), self.hash_name)),
            bytes)

    # -- keys --------------------------------------------------------------
    def generate_keypair(self, seed: bytes | None = None) -> KeyPair:
        secret, pub = refimpl.keygen(self.params, seed)
        return KeyPair(secret, pub, self)

    def keypair_from_secret(self, secret: int) -> KeyPair:
        pub = refimpl.ec_mul(self.params, secret, (self.params.gx, self.params.gy))
        return KeyPair(secret, pub, self)

    def address_of_pub(self, pub_bytes: bytes) -> bytes:
        """Right-160 bits of H(pubkey) — the reference's calculateAddress."""
        return self._host_hash(pub_bytes)[12:]

    # -- signing (host, single) --------------------------------------------
    def sign(self, kp, digest: bytes) -> bytes:
        if hasattr(kp, "sign_digest"):  # HSM-backed: secret stays inside
            return kp.sign_digest(digest)
        from . import nativeec

        if self.kind == "ecdsa":
            # native EC, RFC 6979 nonce from the oracle — byte-exact with
            # refimpl.ecdsa_sign (consensus packets/seals sign per message;
            # the pure-Python ladder was ~17 ms per signature)
            sig = nativeec.ecdsa_sign(kp.secret, digest)
            r, s, v = sig if sig is not None else \
                refimpl.ecdsa_sign(self.params, kp.secret, digest)
            return r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])
        sig = nativeec.sm2_sign(kp.secret, digest)
        r, s = sig if sig is not None else refimpl.sm2_sign(kp.secret, digest)
        return r.to_bytes(32, "big") + s.to_bytes(32, "big") + kp.pub_bytes

    # -- verification / recovery (batch-native) ----------------------------
    def verify(self, pub_bytes: bytes, digest: bytes, sig: bytes) -> bool:
        return bool(self.verify_batch([digest], [sig], [pub_bytes])[0])

    def recover(self, digest: bytes, sig: bytes) -> bytes | None:
        pubs, ok = self.recover_batch([digest], [sig])
        return pubs[0] if ok[0] else None

    def _mesh(self):
        """Lazy mesh kernels (None unless mesh_devices >= 2)."""
        if not self._mesh_tried:
            self._mesh_tried = True
            if self.mesh_devices >= 2:
                from ..parallel import MeshKernels, local_mesh

                self._mesh_kernels = MeshKernels(
                    local_mesh(self.mesh_devices))
        return self._mesh_kernels

    def _bucket_for(self, n: int) -> int:
        b = _bucket(n)
        mk = self._mesh()  # lazy+cached: no call-order dependency
        return max(b, mk.n_devices) if mk is not None else b

    def _split_sigs(self, sigs: Sequence[bytes]):
        """r, s scalars per sig, for the host door's integer calls (the
        device path stages arrays: `_stage`); malformed (short) sigs become
        r=s=0, which every verify/recover path rejects as invalid."""
        rs = [int.from_bytes(g[:32], "big") if len(g) >= self.signature_size
              else 0 for g in sigs]
        ss = [int.from_bytes(g[32:64], "big") if len(g) >= self.signature_size
              else 0 for g in sigs]
        return rs, ss

    def _stage(self, digests: Sequence[bytes], sigs: Sequence[bytes],
               pubs: Sequence[bytes] | None = None) -> list[np.ndarray]:
        """The EC kernels' operands for a batch, where bytes become limbs:
        e, r, s as uint32[N, 16] (`bigint.rows_to_limbs` over the joined
        rows) and then v as uint32[N] (recover) or qx, qy from `pubs`
        (verify). Array for array what `bigint.batch_to_limbs` gives for
        the integers of `_split_sigs`: a signature shorter than
        `signature_size` stages as r = s = 0 (v = 255), a longer one is
        cut, a short digest or key is the same integer."""
        ssz = self.signature_size
        bad = bytes(64) + b"\xff" + bytes(ssz - 65)  # r = s = 0, v = 255
        e = _frame(digests, DIGEST, _fix_digest)
        g = _frame(sigs, ssz, lambda x: x[:ssz] if len(x) > ssz else bad)
        cols = [bigint.rows_to_limbs(c) for c in (e, g[:, :32], g[:, 32:64])]
        if pubs is None:
            return cols + [g[:, 64].astype(np.uint32)]
        q = _frame(pubs, 64, _fix_pub)
        return cols + [bigint.rows_to_limbs(q[:, :32]),
                       bigint.rows_to_limbs(q[:, 32:])]

    def _pad_chunks(self, n: int, cols: list) -> list:
        """The kernel's operands for n rows: one bucket-padded set up to
        CHUNK, CHUNK-sized sets above it. -> [(rows, padded columns)]."""
        if n <= CHUNK:
            b = self._bucket_for(n)
            return [(n, [_pad_rows(a, b) for a in cols])]
        return [(ln, [_pad_rows(a[o:o + ln], CHUNK) for a in cols])
                for o, ln in _chunks(n)]

    @staticmethod
    def _lanes(chunks: list) -> int:
        """Lanes `_pad_chunks`' sets issue: the sum of their buckets."""
        return sum(padded[0].shape[0] for _ln, padded in chunks)

    def _run_chunks(self, fn, chunks: list) -> list:
        """Run kernel `fn(curve, *columns)` over `_pad_chunks`' sets, all
        issued before the first output is fetched (jax's async dispatch
        overlaps the next chunk's staging with the current chunk's
        compute). -> per-output numpy arrays trimmed to the real rows."""
        outs = [fn(self.curve, *padded) for _ln, padded in chunks]
        outs = [o if isinstance(o, tuple) else (o,) for o in outs]
        return [np.concatenate([np.asarray(o[k])[:ln]
                                for o, (ln, _p) in zip(outs, chunks)])
                for k in range(len(outs[0]))]

    def verify_batch(self, digests: Sequence[bytes], sigs: Sequence[bytes],
                     pubs: Sequence[bytes]) -> np.ndarray:
        """-> bool[N]. For ecdsa, pubs are 64-byte uncompressed keys; sigs may
        carry a trailing v byte (ignored for verify). For sm, the pub embedded
        in the signature is ignored in favour of the explicit pubs arg.
        The host door takes integers (`_split_sigs`); the device door takes
        the rows as whole arrays (`_stage`), the same values limb for limb."""
        n = len(digests)
        assert len(sigs) == n and len(pubs) == n
        if n == 0:
            return np.zeros((0,), bool)
        _lc.note_blocking("suite_batch", "verify_batch")
        if not self._use_device(n):
            from . import nativeec

            self._count_host("verify", n, nativeec.parts_of(n))
            rs, ss = self._split_sigs(sigs)
            qx = [int.from_bytes(p[:32], "big") for p in pubs]
            qy = [int.from_bytes(p[32:64], "big") for p in pubs]
            es = [int.from_bytes(d, "big") for d in digests]
            if self.kind == "ecdsa":
                native = nativeec.ecdsa_verify_batch(es, rs, ss, qx, qy)
                if native is not None:
                    return np.array(native)
                return np.array([
                    refimpl.ecdsa_verify(self.params, (x, y), d, r, s)
                    for x, y, d, r, s in zip(qx, qy, digests, rs, ss)
                ])
            native = nativeec.sm2_verify_batch(es, rs, ss, qx, qy)
            if native is not None:
                return np.array(native)
            return np.array([
                refimpl.sm2_verify((x, y), d, r, s)
                for x, y, d, r, s in zip(qx, qy, digests, rs, ss)
            ])
        t_in = time.monotonic()
        mk = self._mesh()
        if mk is not None:
            fn = (mk.verify if self.kind == "ecdsa" else mk.sm2_verify)
        else:
            fn = (ec.ecdsa_verify_batch if self.kind == "ecdsa"
                  else ec.sm2_verify_batch)
        chunks = self._pad_chunks(n, self._stage(digests, sigs, pubs))
        return self._on_device(
            "verify", n, self._lanes(chunks), t_in,
            lambda: self._run_chunks(fn, chunks))[0]

    def recover_batch(self, digests: Sequence[bytes], sigs: Sequence[bytes]
                      ) -> tuple[list[bytes | None], np.ndarray]:
        """-> (pub_bytes[N] (None where invalid), ok[N]).

        The reference's tx hot path (Transaction.h:68-82): recover sender key
        from signature. For sm suites the signature carries the pubkey
        (SignatureDataWithPub.h) — recovery degenerates to verify + extract.

        Neither door makes an integer of a well-formed row: the host door
        hands the joined rows to the C side, the device door makes limbs of
        them in `_stage` and bytes of the recovered limbs in `pub_bytes`
        (`bigint.rows_to_limbs` / `limbs_to_rows`), a whole column a pass.
        """
        n = len(digests)
        assert len(sigs) == n
        _lc.note_blocking("suite_batch", "recover_batch")
        if n == 0:
            return [], np.zeros((0,), bool)
        if self.kind == "sm":
            pubs = [g[64:128] if len(g) >= 128 else b"\x00" * 64 for g in sigs]
            ok = self.verify_batch(digests, sigs, pubs)
            return [p if o else None for p, o in zip(pubs, ok)], ok
        if not self._use_device(n):
            from . import nativeec

            self._count_host("recover", n, nativeec.parts_of(n))
            if (nativeec.available()
                    and all(len(d) == 32 for d in digests)):
                # rows fast path: wire signature bytes and 32-byte tx
                # hashes ARE the count x 32 BE rows the C side reads, so
                # the r16 call-site residue (per-sig int round trips on
                # both sides of the FFI) disappears — slices of the
                # columnar arena feed the join directly. Malformed rows
                # degrade to r=s=0 / v=255, rejected by the C side the
                # same way _split_sigs' zeros are. The device door below
                # reads the same rows as arrays (`_stage`): no integer
                # per signature on either door.
                ssz = self.signature_size
                native = nativeec.ecdsa_recover_batch_rows(
                    b"".join(digests),
                    b"".join(g[:32] if len(g) >= ssz else _ZERO32
                             for g in sigs),
                    b"".join(g[32:64] if len(g) >= ssz else _ZERO32
                             for g in sigs),
                    bytes(g[64] if len(g) >= 65 else 255 for g in sigs))
                if native is not None:
                    return native[0], np.array(native[1])
            rs, ss = self._split_sigs(sigs)
            vs = [g[64] if len(g) >= 65 else 255 for g in sigs]
            es = [int.from_bytes(d, "big") for d in digests]
            native = nativeec.ecdsa_recover_batch(es, rs, ss, vs)
            if native is not None:
                return native[0], np.array(native[1])
            out, okl = [], []
            for d, r, s, v in zip(digests, rs, ss, vs):
                Q = refimpl.ecdsa_recover(self.params, d, r, s, v)
                good = Q is not None
                okl.append(good)
                out.append(Q[0].to_bytes(32, "big") + Q[1].to_bytes(32, "big")
                           if good else None)
            return out, np.array(okl)
        t_in = time.monotonic()
        mk = self._mesh()
        rec = mk.recover if mk is not None else ec.ecdsa_recover_batch
        chunks = self._pad_chunks(n, self._stage(digests, sigs))

        def pub_bytes(outs):
            # limbs become bytes here: one uint8[N, 64] array, cut by row
            qx, qy, ok = outs
            buf = np.concatenate([bigint.limbs_to_rows(qx),
                                  bigint.limbs_to_rows(qy)], axis=1).tobytes()
            return [buf[o:o + 64] if good else None
                    for o, good in zip(range(0, 64 * n, 64), ok.tolist())], ok

        return self._on_device(
            "recover", n, self._lanes(chunks), t_in,
            lambda: self._run_chunks(rec, chunks), pub_bytes)

    def recover_addresses(self, digests: Sequence[bytes], sigs: Sequence[bytes]
                          ) -> tuple[list[bytes | None], np.ndarray]:
        """Sender addresses for a tx batch (None where sig invalid)."""
        pubs, ok = self.recover_batch(digests, sigs)
        # one hash call for all valid pubs (address = right-160 of H(pub))
        valid = [i for i, p in enumerate(pubs) if p is not None]
        out: list[bytes | None] = [None] * len(pubs)
        if valid:
            for i, d in zip(valid, self._host_hash_batch(
                    [pubs[i] for i in valid])):
                out[i] = d[12:]
        return out, ok


def make_suite(sm_crypto: bool = False, **kw) -> CryptoSuite:
    """The ProtocolInitializer seam: chain.sm_crypto -> suite selection."""
    return CryptoSuite("sm" if sm_crypto else "ecdsa", **kw)
