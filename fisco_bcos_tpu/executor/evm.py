"""EVM bytecode interpreter — the framework's contract VM.

Reference counterpart: /root/reference/bcos-executor/src/vm/ — the reference
links **evmone** (VMFactory.h:46-64, VMInstance with code-analysis cache) and
exposes chain state through an EVMC host (HostContext.cpp: storage access,
calls, logs, balance). This module provides the same capability as an
independent from-spec interpreter:

  * full opcode set through Shanghai (PUSH0, arithmetic/bitwise/keccak,
    storage, memory, context, logs, CALL family, CREATE/CREATE2,
    RETURN/REVERT/SELFDESTRUCT) plus Cancun's TLOAD/TSTORE (EIP-1153,
    per-tx transient storage with frame-revert semantics) and MCOPY
    (EIP-5656, memmove);
  * gas metering (per-opcode base costs, quadratic memory expansion, word
    copy costs, EIP-2929 cold/warm access sets, EIP-2200 net SSTORE
    metering with EIP-3529 refunds capped at gas_used/5, EIP-3651 warm
    coinbase — see AccessSet; framework system contracts are pre-warmed
    like classic precompiles);

Intentional deviations from mainnet (consensus choices for THIS chain,
mirrored bit-for-bit by native/nevm — tests/test_nevm.py enforces):
  * PUSH reading past code end yields zero-padded immediates;
  * JUMP lands at dest+1, so JUMPDEST's 1 gas is skipped on jumps;
  * DMC cross-shard segments each open a fresh EIP-2929 context (warmth
    does not travel across executor shards; deterministic by message
    boundary);
  * memory is hard-capped at 2^34 bytes (beyond it: out-of-gas before
    any charge/allocation — mainnet relies on gas alone);
  * intrinsic tx gas / calldata gas are not charged (block gas economics
    are governed by the chain's own tx_count_limit / gas_limit configs);
  * SELFDESTRUCT follows EIP-6780 (Cancun): the balance moves at the
    opcode; same-transaction creations are deleted (code, storage,
    residual balance burned) at END of transaction;
  * bn128 PAIRING (address 8) IS implemented (precompile_classic.py +
    crypto/bn254), gated on compatibility_version >= 1.1.0; the pure-
    Python pairing is priced at ~1.35M gas/pair (its measured ~0.45 s
    cost) and capped per call AND per transaction so a pairing-heavy tx
    cannot stall the execution lane (pre-1.1 chains keep the legacy
    vacuous empty-input-true, loud-failure-otherwise behavior);
  * nested frames with per-frame state savepoints (revert unwinds exactly
    the frame's writes — same recoder discipline as the reference's
    executive stack, TransactionExecutive.cpp);
  * the classic precompiled contracts at addresses 1..9 (ecrecover routes
    back through the framework CryptoSuite — i.e. a TPU-batchable verify
    when the SDK bulk-calls it).

Contract state layout (tables on the framework's storage):
  s_code  address -> runtime bytecode        (shared with get_code RPC)
  s_abi   address -> ABI json (set by deploy tooling)
  s_store address||slot32 -> value32         (EVM storage)
  s_bal   address -> u256 balance            (value transfers)
  s_nonce address -> u64 create nonce
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Optional

from ..protocol import LogEntry
from ..storage.state import StateStorage
from ..utils import otrace

U256 = 1 << 256
M256 = U256 - 1
T_CODE = "s_code"
T_STORE = "s_store"
T_BAL = "s_bal"
T_NONCE = "s_nonce"

MAX_DEPTH = 1024
MAX_CODE_SIZE = 0x6000


class EVMError(Exception):
    """Exceptional halt: consumes all gas of the frame."""


class OutOfGas(EVMError):
    pass


@dataclasses.dataclass
class EVMResult:
    success: bool
    output: bytes = b""
    gas_left: int = 0
    logs: list = dataclasses.field(default_factory=list)
    create_address: bytes = b""
    error: str = ""


@dataclasses.dataclass
class TxEnv:
    origin: bytes
    gas_price: int
    block_number: int
    timestamp: int
    gas_limit: int
    chain_id: int = 1
    coinbase: bytes = b"\x00" * 20
    # active compatibility_version for this block, snapshotted from the
    # block-START state by the executor (next-block governance semantics:
    # a raise committed as tx i of block N must not flip gated behavior
    # for tx i+1 of the SAME block). None = read from live state.
    compat_version: Optional[tuple] = None


# ---------------------------------------------------------------------------
# gas schedule (public Ethereum yellow-paper / EIP values, simplified
# cold/warm handling: flat warm costs — deterministic and chain-local)
# ---------------------------------------------------------------------------

G_ZERO, G_BASE, G_VERYLOW, G_LOW, G_MID, G_HIGH = 0, 2, 3, 5, 8, 10
G_KECCAK = 30
G_KECCAK_WORD = 6
G_COPY_WORD = 3
G_SLOAD = 100  # warm access (EIP-2929 WARM_STORAGE_READ_COST)
G_COLD_SLOAD = 2100  # EIP-2929 COLD_SLOAD_COST
G_COLD_ACCOUNT = 2600  # EIP-2929 COLD_ACCOUNT_ACCESS_COST
G_SSTORE_SET = 20000
G_SSTORE_RESET = 2900  # 5000 - COLD_SLOAD (Berlin)
G_SSTORE_SENTRY = 2300  # EIP-2200: SSTORE needs gas > sentry
R_SSTORE_CLEARS = 4800  # EIP-3529 clearing refund
MAX_REFUND_QUOTIENT = 5  # EIP-3529: refund capped at gas_used/5
G_LOG = 375
G_LOG_TOPIC = 375
G_LOG_DATA = 8
G_CREATE = 32000
G_CALL = 100
G_CALLVALUE = 9000
G_CALLSTIPEND = 2300
G_NEWACCOUNT = 25000
G_EXP = 10
G_EXP_BYTE = 50
G_MEMORY = 3
G_BALANCE = 100
G_EXTCODE = 100
G_SELFDESTRUCT = 5000
G_INITCODE_WORD = 2  # EIP-3860


def _mem_cost(words: int) -> int:
    return G_MEMORY * words + (words * words) // 512


MEM_CAP = 1 << 34  # hard memory ceiling, lockstep with nevm.cpp Frame::extend


def _gas_size(n: int) -> int:
    """Validated attacker-chosen size for a gas multiply: anything beyond
    the memory cap can never be paid for or materialised — out-of-gas
    before any charge or slice allocation (lockstep with nevm.cpp
    checked_size; the native side additionally needs this to keep
    per*size products inside int64)."""
    if n > MEM_CAP:
        raise OutOfGas("out of gas")
    return n


class Memory:
    __slots__ = ("data", "_frame")

    def __init__(self, frame: "Frame"):
        self.data = bytearray()
        self._frame = frame

    def extend(self, off: int, size: int) -> None:
        if size == 0:
            return
        end = off + size
        if end > MEM_CAP:
            raise OutOfGas("out of gas")
        if end > len(self.data):
            old_words = (len(self.data) + 31) // 32
            new_words = (end + 31) // 32
            self._frame.use_gas(_mem_cost(new_words) - _mem_cost(old_words))
            self.data.extend(b"\x00" * (new_words * 32 - len(self.data)))

    def read(self, off: int, size: int) -> bytes:
        self.extend(off, size)
        return bytes(self.data[off:off + size])

    def write(self, off: int, blob: bytes) -> None:
        self.extend(off, len(blob))
        self.data[off:off + len(blob)] = blob


class AccessSet:
    """Per-transaction warm/cold tracking + net SSTORE metering
    (EIP-2929 access sets, EIP-2200 net metering with EIP-3529 refunds).

    One instance lives for the whole outer transaction, shared by every
    nested frame across BOTH interpreters — the native interpreter
    (native/nevm) charges through host callbacks that land here, so the
    metering logic exists exactly once. Reverted frames roll their
    warmth/refund additions back via the journal (EIP-2929: "when a
    context reverts, the access lists return to their previous state");
    `original` values (pre-transaction storage) survive rollbacks by
    definition and are kept.
    """

    __slots__ = ("addresses", "slots", "original", "refund", "transient",
                 "created", "destroyed", "_journal")

    def __init__(self):
        self.addresses: set[bytes] = set()
        self.slots: set[tuple[bytes, bytes]] = set()
        self.original: dict[tuple[bytes, bytes], int] = {}
        self.refund = 0
        # EIP-1153 transient storage: per-TRANSACTION, reverts with the
        # frame journal, discarded at tx end (never touches the trie)
        self.transient: dict[tuple[bytes, bytes], int] = {}
        # EIP-6780: contracts CREATEd in this tx (full SELFDESTRUCT
        # allowed); reverts with the frame journal like warmth
        self.created: set[bytes] = set()
        # destructions are DEFERRED to end of transaction (canonical
        # Cancun: later same-tx frames still see the code; the account —
        # including any residual balance — is deleted at tx end)
        self.destroyed: set[bytes] = set()
        self._journal: list = []  # ("a",addr)|("s",key)|("r",d)|("t",k,old)
        #                           |("c",addr)

    # -- journal (frame revert restores prior warmth + refund) -------------
    def snapshot(self) -> int:
        return len(self._journal)

    def rollback_to(self, mark: int) -> None:
        while len(self._journal) > mark:
            entry = self._journal.pop()
            kind = entry[0]
            if kind == "a":
                self.addresses.discard(entry[1])
            elif kind == "s":
                self.slots.discard(entry[1])
            elif kind == "t":
                _, key, old = entry
                if old == 0:
                    self.transient.pop(key, None)
                else:
                    self.transient[key] = old
            elif kind == "c":
                self.created.discard(entry[1])
            elif kind == "d":
                self.destroyed.discard(entry[1])
            else:
                self.refund -= entry[1]

    def _add_refund(self, delta: int) -> None:
        self.refund += delta
        self._journal.append(("r", delta))

    def mark_created(self, addr: bytes) -> None:
        """EIP-6780: record a same-transaction CREATE."""
        if addr not in self.created:
            self.created.add(addr)
            self._journal.append(("c", addr))

    def mark_destroyed(self, addr: bytes) -> None:
        """EIP-6780: schedule end-of-tx account deletion (journaled: a
        reverting frame cancels it)."""
        if addr not in self.destroyed:
            self.destroyed.add(addr)
            self._journal.append(("d", addr))

    # -- transient storage (EIP-1153) --------------------------------------
    def tload(self, addr: bytes, slot: bytes) -> int:
        return self.transient.get((addr, slot), 0)

    def tstore(self, addr: bytes, slot: bytes, value: int) -> None:
        key = (addr, slot)
        old = self.transient.get(key, 0)
        self._journal.append(("t", key, old))
        if value == 0:
            self.transient.pop(key, None)
        else:
            self.transient[key] = value

    # -- account access ----------------------------------------------------
    def warm_address(self, addr: bytes) -> None:
        if addr not in self.addresses:
            self.addresses.add(addr)
            self._journal.append(("a", addr))

    def account_cost(self, addr: bytes) -> int:
        """Full access cost: cold 2600 / warm 100 (BALANCE, EXTCODE*,
        CALL-family target)."""
        if addr in self.addresses:
            return G_SLOAD
        self.warm_address(addr)
        return G_COLD_ACCOUNT

    def account_surcharge(self, addr: bytes) -> int:
        """Cold surcharge only: 2600 / 0 (SELFDESTRUCT heir)."""
        if addr in self.addresses:
            return 0
        self.warm_address(addr)
        return G_COLD_ACCOUNT

    # -- storage access -----------------------------------------------------
    def slot_cost(self, addr: bytes, slot: bytes) -> int:
        """SLOAD: cold 2100 / warm 100."""
        key = (addr, slot)
        if key in self.slots:
            return G_SLOAD
        self.slots.add(key)
        self._journal.append(("s", key))
        return G_COLD_SLOAD

    def sstore_gas(self, current: int, slot_original: int, new: int,
                   addr: bytes, slot: bytes) -> int:
        """Net-metered SSTORE cost; refund deltas applied internally.

        `slot_original` is the value at transaction start (first-touch
        snapshot taken by the caller via :meth:`note_original`)."""
        key = (addr, slot)
        cost = 0
        if key not in self.slots:
            cost += G_COLD_SLOAD
            self.slots.add(key)
            self._journal.append(("s", key))
        if new == current:
            return cost + G_SLOAD
        if current == slot_original:
            if slot_original != 0 and new == 0:
                self._add_refund(R_SSTORE_CLEARS)
            return cost + (G_SSTORE_SET if slot_original == 0
                           else G_SSTORE_RESET)
        # dirty slot (already written this tx)
        if slot_original != 0:
            if current == 0:
                self._add_refund(-R_SSTORE_CLEARS)
            if new == 0:
                self._add_refund(R_SSTORE_CLEARS)
        if new == slot_original:
            if slot_original == 0:
                self._add_refund(G_SSTORE_SET - G_SLOAD)
            else:
                # Berlin: RESET is already the cold-adjusted 2900; the
                # restore credit is RESET - warm access = 2800
                self._add_refund(G_SSTORE_RESET - G_SLOAD)
        return cost + G_SLOAD

    def note_original(self, addr: bytes, slot: bytes, current: int) -> int:
        """Record (once) and return the slot's pre-transaction value."""
        return self.original.setdefault((addr, slot), current)


class Frame:
    """One call frame: stack, memory, gas, pc."""

    def __init__(self, gas: int):
        self.stack: list[int] = []
        self.gas = gas
        self.pc = 0
        self.ret: bytes = b""  # returndata of the last sub-call
        self.mem = Memory(self)

    def use_gas(self, n: int) -> None:
        if n < 0:
            raise EVMError("negative gas")
        self.gas -= n
        if self.gas < 0:
            raise OutOfGas("out of gas")

    def push(self, v: int) -> None:
        if len(self.stack) >= 1024:
            raise EVMError("stack overflow")
        self.stack.append(v & M256)

    def pop(self) -> int:
        if not self.stack:
            raise EVMError("stack underflow")
        return self.stack.pop()


def _sign(v: int) -> int:
    return v - U256 if v >> 255 else v


def _addr_bytes(v: int) -> bytes:
    return (v & ((1 << 160) - 1)).to_bytes(20, "big")


# evmone-style code-analysis LRU (VMFactory.h:46-64 keeps analyzed code
# cached so repeated calls to the same contract skip the O(len) scan)
_JD_CACHE_MAX = 256
_jd_cache: "dict[bytes, frozenset[int]]" = {}
_jd_lock = threading.Lock()


def _analyze_jumpdests(code: bytes) -> frozenset[int]:
    with _jd_lock:
        cached = _jd_cache.get(code)
        if cached is not None:
            return cached
    dests = set()
    i = 0
    n = len(code)
    while i < n:
        op = code[i]
        if op == 0x5B:
            dests.add(i)
        if 0x60 <= op <= 0x7F:
            i += op - 0x5F
        i += 1
    frozen = frozenset(dests)
    with _jd_lock:
        if len(_jd_cache) >= _JD_CACHE_MAX:
            _jd_cache.pop(next(iter(_jd_cache)))  # FIFO eviction
        _jd_cache[code] = frozen
    return frozen


class EVM:
    """Interpreter bound to a state overlay + crypto suite."""

    def __init__(self, suite, registry=None, native: Optional[bool] = None,
                 stages=None):
        self.suite = suite
        # the node's stage table: evm_frames / evm_native_frames, counted
        # once a frame where `_run` picks the interpreter
        self.stages = stages if stages is not None else otrace.stages()
        # per-transaction access set (EIP-2929 warm/cold + refunds),
        # thread-local: the executor runs concurrent txs on one EVM
        self._tls = threading.local()
        # framework precompiles (Table/Consensus/...) visible to EVM CALLs
        self.registry = registry or {}
        # DMC seam: when set, internal CALL/STATICCALL targets the hook may
        # claim (contracts owned by ANOTHER executor shard) are routed out
        # instead of executed locally. hook(caller, to, value, data, gas,
        # static, depth) -> EVMResult, or None to execute locally.
        self.external_call = None
        # native frame interpreter (native/nevm, the evmone analogue):
        # None = auto (use when the built library loads; FBTPU_EVM_NATIVE=0
        # forces the pure-Python interpreter, =1 requires native)
        if native is None:
            flag = os.environ.get("FBTPU_EVM_NATIVE", "auto")
            if flag == "0":
                self.native = False
            else:
                from . import nevm as _nevm
                self.native = _nevm.available()
                if flag == "1" and not self.native:
                    raise RuntimeError("FBTPU_EVM_NATIVE=1 but "
                                       "native/build/libnevm.so not loadable")
        else:
            self.native = native

    # a TRANSACTION may spend at most this many pairing pairs across all
    # its frames (~0.45 s/pair pure-Python: bounds worst-case execution-
    # lane stall per tx; the block-level bound follows deterministically as
    # tx_count_limit x this). Deliberately per-tx, NOT a shared per-block
    # counter: DAG waves execute non-conflicting txs on parallel threads,
    # so a cross-tx counter would make which tx hits the limit depend on
    # thread scheduling — honest nodes would produce different receipts
    # for the same block (consensus divergence). Per-tx state (reset in
    # begin_tx_access) is order-independent and identical on every node.
    MAX_PAIRING_PAIRS_PER_TX = 16

    def _charge_pairing_budget(self, pairs: int) -> bool:
        used = getattr(self._tls, "pairing_pairs", 0)
        if used + pairs > self.MAX_PAIRING_PAIRS_PER_TX:
            return False
        self._tls.pairing_pairs = used + pairs
        return True

    # -- account helpers ---------------------------------------------------
    @staticmethod
    def get_code(state: StateStorage, addr: bytes) -> bytes:
        return state.get(T_CODE, addr) or b""

    @staticmethod
    def balance_of(state: StateStorage, addr: bytes) -> int:
        raw = state.get(T_BAL, addr)
        return int.from_bytes(raw, "big") if raw else 0

    @staticmethod
    def set_balance(state: StateStorage, addr: bytes, v: int) -> None:
        state.set(T_BAL, addr, v.to_bytes(32, "big"))

    @staticmethod
    def nonce_of(state: StateStorage, addr: bytes) -> int:
        raw = state.get(T_NONCE, addr)
        return int.from_bytes(raw, "big") if raw else 0

    def transfer(self, state: StateStorage, frm: bytes, to: bytes,
                 value: int) -> bool:
        if value == 0:
            return True
        b = self.balance_of(state, frm)
        if b < value:
            return False
        self.set_balance(state, frm, b - value)
        self.set_balance(state, to, self.balance_of(state, to) + value)
        return True

    # -- entry points ------------------------------------------------------
    def do_selfdestruct(self, state, address: bytes, heir: bytes) -> None:
        """EIP-6780 SELFDESTRUCT: the balance moves to the heir now; the
        account (code, storage, residual balance) is deleted at END of
        transaction, and only when the contract was created in this same
        transaction — later frames in the tx still see the code, and a
        self-heir's balance ends up burned by the deferred deletion.
        Shared by both interpreters (the native side routes through the
        selfdestruct host callback)."""
        bal = self.balance_of(state, address)
        if bal:
            self.transfer(state, address, heir, bal)
        if address in self.access().created:
            self.access().mark_destroyed(address)

    def _finalize_destructions(self, state) -> None:
        """Apply deferred EIP-6780 deletions at top-frame success."""
        acc = getattr(self._tls, "access", None)
        if acc is None or not acc.destroyed:
            return
        for addr in acc.destroyed:
            state.remove(T_CODE, addr)
            for k in list(state.keys(T_STORE, addr)):
                state.remove(T_STORE, k)
            # full account deletion: balance (already routed to the heir or
            # burned), nonce, and any residual records must all vanish so a
            # later CREATE2 redeploy at this address starts from a truly
            # empty account (child CREATE addresses derive from nonce 0);
            # existence-guarded so no-op tombstones don't amplify KeyPage
            # writes at 2PC prepare
            if state.get(T_BAL, addr) is not None:
                state.remove(T_BAL, addr)
            if state.get(T_NONCE, addr) is not None:
                state.remove(T_NONCE, addr)

    # -- per-tx access context (EIP-2929) ----------------------------------
    def access(self) -> AccessSet:
        acc = getattr(self._tls, "access", None)
        if acc is None:
            acc = self._tls.access = AccessSet()
        return acc

    def begin_tx_access(self, origin: bytes, target: bytes,
                        coinbase: bytes = b"") -> AccessSet:
        """Fresh per-transaction access set, pre-warmed per EIP-2929
        (origin, target, classic precompiles 1..9, framework system
        contracts) + EIP-3651 (coinbase)."""
        acc = self._tls.access = AccessSet()
        self._tls.pairing_pairs = 0  # fresh per-tx pairing budget
        acc.warm_address(origin)
        if target:
            acc.warm_address(target)
        if len(coinbase) == 20:  # EIP-3651 (zero-addr default included)
            acc.warm_address(coinbase)
        for i in range(1, 10):
            acc.warm_address(b"\x00" * 19 + bytes([i]))
        for addr in self.registry:
            acc.warm_address(addr)
        return acc

    def take_refund(self, gas_used: int) -> int:
        """EIP-3529-capped refund for the finished tx; clears the
        context so the next tx on this thread starts cold."""
        acc = getattr(self._tls, "access", None)
        self._tls.access = None
        if acc is None or acc.refund <= 0:
            return 0
        return min(acc.refund, gas_used // MAX_REFUND_QUOTIENT)

    def execute_message(self, state: StateStorage, env: TxEnv, caller: bytes,
                        to: bytes, value: int, data: bytes, gas: int,
                        depth: int = 0, static: bool = False) -> EVMResult:
        """CALL semantics against `to` (code fetched from state)."""
        if depth > MAX_DEPTH:
            return EVMResult(False, gas_left=gas, error="call depth")
        if self.external_call is not None and depth > 0:
            ext = self.external_call(caller, to, value, data, gas, static,
                                     depth)
            if ext is not None:
                return ext
        if depth == 0:
            self.begin_tx_access(env.origin, to, env.coinbase)
        acc = self.access()
        sp = state.savepoint()
        sp_acc = acc.snapshot()
        if not static and not self.transfer(state, caller, to, value):
            state.rollback_to(sp)
            return EVMResult(False, gas_left=gas, error="insufficient balance")
        pre = self._precompile(state, env, to, data, gas)
        if pre is not None:
            if pre.success:
                state.release(sp)
            else:
                state.rollback_to(sp)
            return pre
        code = self.get_code(state, to)
        if not code:
            state.release(sp)
            return EVMResult(True, gas_left=gas)  # plain transfer
        res = self._run_in_message(state, env, code, caller, to, value, data,
                                   gas, depth, static)
        if res.success:
            if depth == 0:
                self._finalize_destructions(state)
            state.release(sp)
        else:
            state.rollback_to(sp)
            acc.rollback_to(sp_acc)  # EIP-2929: reverted frames cool again
        return res

    def create(self, state: StateStorage, env: TxEnv, caller: bytes,
               value: int, initcode: bytes, gas: int, depth: int = 0,
               salt: Optional[int] = None) -> EVMResult:
        """CREATE/CREATE2 semantics; returns create_address on success."""
        if depth > MAX_DEPTH:
            return EVMResult(False, gas_left=gas, error="call depth")
        if len(initcode) > 2 * MAX_CODE_SIZE:
            return EVMResult(False, gas_left=gas, error="initcode too large")
        nonce = self.nonce_of(state, caller)
        state.set(T_NONCE, caller, (nonce + 1).to_bytes(8, "big"))
        if salt is None:
            seed = caller + nonce.to_bytes(8, "big")
            new_addr = self.suite.hash(b"\xd6\x94" + seed)[12:]
        else:
            h = self.suite.hash(initcode)
            new_addr = self.suite.hash(
                b"\xff" + caller + salt.to_bytes(32, "big") + h)[12:]
        if self.get_code(state, new_addr):
            return EVMResult(False, gas_left=0, error="address collision")
        if depth == 0:
            self.begin_tx_access(env.origin, new_addr, env.coinbase)
        acc = self.access()
        sp = state.savepoint()
        sp_acc = acc.snapshot()
        acc.warm_address(new_addr)  # EIP-2929: created address is warm
        acc.mark_created(new_addr)  # EIP-6780: full selfdestruct allowed
        if not self.transfer(state, caller, new_addr, value):
            state.rollback_to(sp)
            acc.rollback_to(sp_acc)
            return EVMResult(False, gas_left=gas, error="insufficient balance")
        res = self._run_in_message(state, env, initcode, caller, new_addr,
                                   value, b"", gas, depth, False)
        if not res.success:
            state.rollback_to(sp)
            acc.rollback_to(sp_acc)
            return res
        deployed = res.output
        if len(deployed) > MAX_CODE_SIZE:
            state.rollback_to(sp)
            acc.rollback_to(sp_acc)
            return EVMResult(False, gas_left=0, error="code too large")
        code_gas = 200 * len(deployed)
        if res.gas_left < code_gas:
            state.rollback_to(sp)
            acc.rollback_to(sp_acc)
            return EVMResult(False, gas_left=0, error="code deposit gas")
        state.set(T_CODE, new_addr, deployed)
        if depth == 0:
            self._finalize_destructions(state)
        state.release(sp)
        return EVMResult(True, output=b"", gas_left=res.gas_left - code_gas,
                         logs=res.logs, create_address=new_addr)

    def _compat_version(self, state, env) -> tuple:
        """Active on-chain compatibility_version for the executing block.
        The block pipeline snapshots it from block-START state into
        env.compat_version (TransactionExecutor), giving exact next-block
        governance semantics; direct execute_message callers without a
        snapshot fall back to the live state read."""
        if env.compat_version is not None:
            return env.compat_version
        return self.read_compat_version(state)

    @staticmethod
    def read_compat_version(state) -> tuple:
        from ..codec.wire import Reader
        from ..ledger import ledger as ledger_mod

        raw = state.get(ledger_mod.SYS_CONFIG,
                        ledger_mod.SYSTEM_KEY_COMPATIBILITY_VERSION.encode())
        if not raw:
            return (0, 0, 0)  # pre-versioning chain: oldest semantics
        try:
            return ledger_mod.parse_version(Reader(raw).text())
        except Exception:
            return (0, 0, 0)

    # -- classic precompiles (addresses 1..9) + framework system contracts -
    def _precompile(self, state, env, to: bytes, data: bytes, gas: int
                    ) -> Optional[EVMResult]:
        if to in self.registry:  # framework system contracts (Table etc.)
            return self._system_contract(state, env, to, data, gas)
        if len(to) != 20 or to[:19] != b"\x00" * 19 or not 1 <= to[19] <= 9:
            return None
        which = to[19]
        try:
            if which == 1:  # ecrecover
                cost = 3000
                if gas < cost:
                    return EVMResult(False, gas_left=0, error="oog")
                try:
                    h = data[0:32].ljust(32, b"\x00")
                    v = int.from_bytes(data[32:64], "big")
                    r, s = data[64:96], data[96:128]
                    sig = r + s + bytes([v - 27 if 27 <= v <= 30 else v])
                    pub = self.suite.recover(h, sig)
                except Exception:
                    pub = None  # spec: malformed input -> empty success
                out = (b"\x00" * 12 + self.suite.address_of_pub(pub)
                       if pub else b"")
                return EVMResult(True, output=out, gas_left=gas - cost)
            if which == 2:  # sha256
                import hashlib
                words = (len(data) + 31) // 32
                cost = 60 + 12 * words
                if gas < cost:
                    return EVMResult(False, gas_left=0, error="oog")
                return EVMResult(True, output=hashlib.sha256(data).digest(),
                                 gas_left=gas - cost)
            if which == 3:  # ripemd160
                import hashlib
                words = (len(data) + 31) // 32
                cost = 600 + 120 * words
                if gas < cost:
                    return EVMResult(False, gas_left=0, error="oog")
                try:
                    d = hashlib.new("ripemd160", data).digest()
                except Exception:
                    d = hashlib.sha256(data).digest()[:20]  # gated fallback
                return EVMResult(True, output=d.rjust(32, b"\x00"),
                                 gas_left=gas - cost)
            if which == 4:  # identity
                words = (len(data) + 31) // 32
                cost = 15 + 3 * words
                if gas < cost:
                    return EVMResult(False, gas_left=0, error="oog")
                return EVMResult(True, output=data, gas_left=gas - cost)
            if which == 5:  # modexp (EIP-198, simplified gas)
                bl = int.from_bytes(data[0:32], "big")
                el = int.from_bytes(data[32:64], "big")
                ml = int.from_bytes(data[64:96], "big")
                if max(bl, el, ml) > 4096:
                    return EVMResult(False, gas_left=0, error="modexp size")
                body = data[96:]
                b_ = int.from_bytes(body[:bl].ljust(bl, b"\x00"), "big")
                e_ = int.from_bytes(body[bl:bl + el].ljust(el, b"\x00"), "big")
                m_ = int.from_bytes(
                    body[bl + el:bl + el + ml].ljust(ml, b"\x00"), "big")
                cost = max(200, (max(bl, ml) ** 2 // 8) * max(1, e_.bit_length()) // 20)
                if gas < cost:
                    return EVMResult(False, gas_left=0, error="oog")
                out = pow(b_, e_, m_) if m_ else 0
                return EVMResult(True, output=out.to_bytes(ml, "big") if ml else b"",
                                 gas_left=gas - cost)
            if which in (6, 7, 8, 9):
                from . import precompile_classic as pcc
            if which in (6, 7):  # alt_bn128 add / mul (EIP-196/1108)
                cost = pcc.G_BNADD if which == 6 else pcc.G_BNMUL
                if gas < cost:
                    return EVMResult(False, gas_left=0, error="oog")
                try:
                    out = (pcc.bn128_add(data) if which == 6
                           else pcc.bn128_mul(data))
                except pcc.PrecompileInputError as exc:
                    return EVMResult(False, gas_left=0,
                                     error=f"bn128: {exc}")
                return EVMResult(True, output=out, gas_left=gas - cost)
            if which == 8:  # bn128 pairing check (EIP-197, repriced gas),
                # gated on compatibility_version >= 1.1.0 — the chain
                # enables it fleet-wide at a governed height
                # (LedgerTypeDef.h:42 rolling-upgrade semantics)
                pairs = len(data) // 192
                if pairs > pcc.MAX_PAIRING_PAIRS:
                    # O(1) refusal BEFORE gas math or curve work: the
                    # ~0.45 s/pair pure-Python pairing must never be
                    # droveable past the cap (execution-lane DoS guard)
                    return EVMResult(
                        False, gas_left=0,
                        error=f"bn128 pairing: {pairs} pairs exceeds the "
                              f"{pcc.MAX_PAIRING_PAIRS}-pair per-call cap")
                if self._compat_version(state, env) < (1, 1, 0):
                    # the gate outranks the repriced gas: on a pre-1.1
                    # chain the pairing "does not exist" for real input
                    if len(data) == 0 and gas >= pcc.G_PAIRING_BASE:
                        return EVMResult(  # pre-1.1 behavior preserved
                            True, output=(1).to_bytes(32, "big"),
                            gas_left=gas - pcc.G_PAIRING_BASE)
                    if len(data) == 0:
                        return EVMResult(False, gas_left=0, error="oog")
                    return EVMResult(
                        False, gas_left=0,
                        error="bn128 pairing needs compatibility_version"
                              " >= 1.1.0")
                cost = (pcc.G_PAIRING_BASE
                        + pcc.G_PAIRING_PER_PAIR * pairs)
                if gas < cost:
                    return EVMResult(False, gas_left=0, error="oog")
                if pairs and not self._charge_pairing_budget(pairs):
                    return EVMResult(
                        False, gas_left=0,
                        error="bn128 pairing: per-transaction pair budget "
                              f"({self.MAX_PAIRING_PAIRS_PER_TX}) "
                              "exhausted")
                try:
                    out = pcc.bn128_pairing(data)
                except pcc.PrecompileInputError as exc:
                    return EVMResult(False, gas_left=0,
                                     error=f"bn128 pairing: {exc}")
                return EVMResult(True, output=out, gas_left=gas - cost)
            if which == 9:  # blake2f (EIP-152)
                try:  # gas gate BEFORE any compression work (DoS guard)
                    cost = pcc.blake2f_cost(data)
                except pcc.PrecompileInputError as exc:
                    return EVMResult(False, gas_left=0,
                                     error=f"blake2f: {exc}")
                if gas < cost:
                    return EVMResult(False, gas_left=0, error="oog")
                out, _ = pcc.blake2f(data)
                return EVMResult(True, output=out, gas_left=gas - cost)
        except Exception as exc:
            return EVMResult(False, gas_left=0, error=f"precompile: {exc}")
        return None  # unreachable for 1..9; kept for safety

    def _system_contract(self, state, env, to: bytes, data: bytes,
                         gas: int) -> EVMResult:
        """Dispatch an in-EVM CALL to a framework precompile (the reference
        routes these through TransactionExecutive's precompile path,
        executive/TransactionExecutive.cpp)."""
        from .precompiled import CallContext, PrecompileError
        cost = G_CALL * 10
        if gas < cost:
            return EVMResult(False, gas_left=0, error="oog")
        ctx = CallContext(state=state, block_number=env.block_number,
                          timestamp=env.timestamp, sender=env.origin, to=to,
                          input=data, gas_limit=gas, suite=self.suite)
        try:
            out = self.registry[to].call(ctx)
            return EVMResult(True, output=out, gas_left=gas - cost,
                             logs=ctx.logs)
        except PrecompileError as exc:
            return EVMResult(False, output=str(exc).encode(),
                             gas_left=gas - cost, error="revert")

    # -- the interpreter loop ----------------------------------------------
    def _run_in_message(self, *args) -> EVMResult:
        """_run for frames whose access context execute_message/create
        already manages (bypasses the direct-call reset below)."""
        self._tls.in_message = True
        try:
            return self._run(*args)
        finally:
            self._tls.in_message = False

    def _run(self, state: StateStorage, env: TxEnv, code: bytes,
             caller: bytes, address: bytes, value: int, calldata: bytes,
             gas: int, depth: int, static: bool) -> EVMResult:
        if depth == 0 and not getattr(self._tls, "in_message", False):
            # direct frame execution (tests, tools): independent tx context
            self.begin_tx_access(env.origin, address, env.coinbase)
        acc = self.access()
        jumpdests = _analyze_jumpdests(code)
        self.stages.count("evm_frames", 1)
        if self.native:
            self.stages.count("evm_native_frames", 1)
            from . import nevm
            return nevm.run_frame(self, state, env, code, caller, address,
                                  value, calldata, gas, depth, static,
                                  jumpdests)
        f = Frame(gas)
        logs: list[LogEntry] = []

        def store_key(slot: int) -> bytes:
            return address + slot.to_bytes(32, "big")

        try:
            while f.pc < len(code):
                op = code[f.pc]
                f.pc += 1
                # PUSH family
                if 0x5F <= op <= 0x7F:
                    n = op - 0x5F
                    f.use_gas(G_BASE if n == 0 else G_VERYLOW)
                    v = int.from_bytes(code[f.pc:f.pc + n], "big") if n else 0
                    f.pc += n
                    f.push(v)
                    continue
                # DUP / SWAP
                if 0x80 <= op <= 0x8F:
                    f.use_gas(G_VERYLOW)
                    n = op - 0x7F
                    if len(f.stack) < n:
                        raise EVMError("stack underflow")
                    f.push(f.stack[-n])
                    continue
                if 0x90 <= op <= 0x9F:
                    f.use_gas(G_VERYLOW)
                    n = op - 0x8F
                    if len(f.stack) < n + 1:
                        raise EVMError("stack underflow")
                    f.stack[-1], f.stack[-n - 1] = f.stack[-n - 1], f.stack[-1]
                    continue
                if op == 0x00:  # STOP
                    return EVMResult(True, b"", f.gas, logs)
                if op == 0x01:  # ADD
                    f.use_gas(G_VERYLOW)
                    f.push(f.pop() + f.pop())
                elif op == 0x02:  # MUL
                    f.use_gas(G_LOW)
                    f.push(f.pop() * f.pop())
                elif op == 0x03:  # SUB
                    f.use_gas(G_VERYLOW)
                    a, b = f.pop(), f.pop()
                    f.push(a - b)
                elif op == 0x04:  # DIV
                    f.use_gas(G_LOW)
                    a, b = f.pop(), f.pop()
                    f.push(a // b if b else 0)
                elif op == 0x05:  # SDIV
                    f.use_gas(G_LOW)
                    a, b = _sign(f.pop()), _sign(f.pop())
                    f.push(0 if b == 0 else abs(a) // abs(b) * (1 if a * b >= 0 else -1))
                elif op == 0x06:  # MOD
                    f.use_gas(G_LOW)
                    a, b = f.pop(), f.pop()
                    f.push(a % b if b else 0)
                elif op == 0x07:  # SMOD
                    f.use_gas(G_LOW)
                    a, b = _sign(f.pop()), _sign(f.pop())
                    f.push(0 if b == 0 else abs(a) % abs(b) * (1 if a >= 0 else -1))
                elif op == 0x08:  # ADDMOD
                    f.use_gas(G_MID)
                    a, b, n = f.pop(), f.pop(), f.pop()
                    f.push((a + b) % n if n else 0)
                elif op == 0x09:  # MULMOD
                    f.use_gas(G_MID)
                    a, b, n = f.pop(), f.pop(), f.pop()
                    f.push((a * b) % n if n else 0)
                elif op == 0x0A:  # EXP
                    a, e = f.pop(), f.pop()
                    f.use_gas(G_EXP + G_EXP_BYTE * ((e.bit_length() + 7) // 8))
                    f.push(pow(a, e, U256))
                elif op == 0x0B:  # SIGNEXTEND
                    f.use_gas(G_LOW)
                    b, x = f.pop(), f.pop()
                    if b < 31:
                        bit = 8 * b + 7
                        if x & (1 << bit):
                            x |= M256 ^ ((1 << (bit + 1)) - 1)
                        else:
                            x &= (1 << (bit + 1)) - 1
                    f.push(x)
                elif op == 0x10:  # LT
                    f.use_gas(G_VERYLOW)
                    f.push(1 if f.pop() < f.pop() else 0)
                elif op == 0x11:  # GT
                    f.use_gas(G_VERYLOW)
                    f.push(1 if f.pop() > f.pop() else 0)
                elif op == 0x12:  # SLT
                    f.use_gas(G_VERYLOW)
                    f.push(1 if _sign(f.pop()) < _sign(f.pop()) else 0)
                elif op == 0x13:  # SGT
                    f.use_gas(G_VERYLOW)
                    f.push(1 if _sign(f.pop()) > _sign(f.pop()) else 0)
                elif op == 0x14:  # EQ
                    f.use_gas(G_VERYLOW)
                    f.push(1 if f.pop() == f.pop() else 0)
                elif op == 0x15:  # ISZERO
                    f.use_gas(G_VERYLOW)
                    f.push(1 if f.pop() == 0 else 0)
                elif op == 0x16:  # AND
                    f.use_gas(G_VERYLOW)
                    f.push(f.pop() & f.pop())
                elif op == 0x17:  # OR
                    f.use_gas(G_VERYLOW)
                    f.push(f.pop() | f.pop())
                elif op == 0x18:  # XOR
                    f.use_gas(G_VERYLOW)
                    f.push(f.pop() ^ f.pop())
                elif op == 0x19:  # NOT
                    f.use_gas(G_VERYLOW)
                    f.push(~f.pop())
                elif op == 0x1A:  # BYTE
                    f.use_gas(G_VERYLOW)
                    i_, x = f.pop(), f.pop()
                    f.push((x >> (8 * (31 - i_))) & 0xFF if i_ < 32 else 0)
                elif op == 0x1B:  # SHL
                    f.use_gas(G_VERYLOW)
                    s, v = f.pop(), f.pop()
                    f.push(v << s if s < 256 else 0)
                elif op == 0x1C:  # SHR
                    f.use_gas(G_VERYLOW)
                    s, v = f.pop(), f.pop()
                    f.push(v >> s if s < 256 else 0)
                elif op == 0x1D:  # SAR
                    f.use_gas(G_VERYLOW)
                    s, v = f.pop(), _sign(f.pop())
                    f.push((v >> s) if s < 256 else (0 if v >= 0 else M256))
                elif op == 0x20:  # KECCAK256
                    off, size = f.pop(), f.pop()
                    f.use_gas(G_KECCAK + G_KECCAK_WORD * ((_gas_size(size) + 31) // 32))
                    f.push(int.from_bytes(
                        self.suite.hash(f.mem.read(off, size)), "big"))
                elif op == 0x30:  # ADDRESS
                    f.use_gas(G_BASE)
                    f.push(int.from_bytes(address, "big"))
                elif op == 0x31:  # BALANCE
                    a = _addr_bytes(f.pop())
                    f.use_gas(acc.account_cost(a))
                    f.push(self.balance_of(state, a))
                elif op == 0x32:  # ORIGIN
                    f.use_gas(G_BASE)
                    f.push(int.from_bytes(env.origin, "big"))
                elif op == 0x33:  # CALLER
                    f.use_gas(G_BASE)
                    f.push(int.from_bytes(caller, "big"))
                elif op == 0x34:  # CALLVALUE
                    f.use_gas(G_BASE)
                    f.push(value)
                elif op == 0x35:  # CALLDATALOAD
                    f.use_gas(G_VERYLOW)
                    off = f.pop()
                    f.push(int.from_bytes(
                        calldata[off:off + 32].ljust(32, b"\x00"), "big"))
                elif op == 0x36:  # CALLDATASIZE
                    f.use_gas(G_BASE)
                    f.push(len(calldata))
                elif op == 0x37:  # CALLDATACOPY
                    d, s, n = f.pop(), f.pop(), f.pop()
                    f.use_gas(G_VERYLOW
                              + G_COPY_WORD * ((_gas_size(n) + 31) // 32))
                    f.mem.write(d, calldata[s:s + n].ljust(n, b"\x00"))
                elif op == 0x38:  # CODESIZE
                    f.use_gas(G_BASE)
                    f.push(len(code))
                elif op == 0x39:  # CODECOPY
                    d, s, n = f.pop(), f.pop(), f.pop()
                    f.use_gas(G_VERYLOW
                              + G_COPY_WORD * ((_gas_size(n) + 31) // 32))
                    f.mem.write(d, code[s:s + n].ljust(n, b"\x00"))
                elif op == 0x3A:  # GASPRICE
                    f.use_gas(G_BASE)
                    f.push(env.gas_price)
                elif op == 0x3B:  # EXTCODESIZE
                    a = _addr_bytes(f.pop())
                    f.use_gas(acc.account_cost(a))
                    f.push(len(self.get_code(state, a)))
                elif op == 0x3C:  # EXTCODECOPY
                    a = _addr_bytes(f.pop())
                    d, s, n = f.pop(), f.pop(), f.pop()
                    f.use_gas(acc.account_cost(a)
                              + G_COPY_WORD * ((_gas_size(n) + 31) // 32))
                    c = self.get_code(state, a)
                    f.mem.write(d, c[s:s + n].ljust(n, b"\x00"))
                elif op == 0x3D:  # RETURNDATASIZE
                    f.use_gas(G_BASE)
                    f.push(len(f.ret))
                elif op == 0x3E:  # RETURNDATACOPY
                    d, s, n = f.pop(), f.pop(), f.pop()
                    f.use_gas(G_VERYLOW
                              + G_COPY_WORD * ((_gas_size(n) + 31) // 32))
                    if s + n > len(f.ret):
                        raise EVMError("returndata out of bounds")
                    f.mem.write(d, f.ret[s:s + n])
                elif op == 0x3F:  # EXTCODEHASH
                    a = _addr_bytes(f.pop())
                    f.use_gas(acc.account_cost(a))
                    c = self.get_code(state, a)
                    f.push(int.from_bytes(self.suite.hash(c), "big") if c else 0)
                elif op == 0x40:  # BLOCKHASH (not tracked: zero)
                    f.use_gas(20)
                    f.pop()
                    f.push(0)
                elif op == 0x41:  # COINBASE
                    f.use_gas(G_BASE)
                    f.push(int.from_bytes(env.coinbase, "big"))
                elif op == 0x42:  # TIMESTAMP
                    f.use_gas(G_BASE)
                    f.push(env.timestamp // 1000)
                elif op == 0x43:  # NUMBER
                    f.use_gas(G_BASE)
                    f.push(env.block_number)
                elif op == 0x44:  # PREVRANDAO (deterministic chain: 0)
                    f.use_gas(G_BASE)
                    f.push(0)
                elif op == 0x45:  # GASLIMIT
                    f.use_gas(G_BASE)
                    f.push(env.gas_limit)
                elif op == 0x46:  # CHAINID
                    f.use_gas(G_BASE)
                    f.push(env.chain_id)
                elif op == 0x47:  # SELFBALANCE
                    f.use_gas(G_LOW)
                    f.push(self.balance_of(state, address))
                elif op == 0x48:  # BASEFEE
                    f.use_gas(G_BASE)
                    f.push(0)
                elif op == 0x50:  # POP
                    f.use_gas(G_BASE)
                    f.pop()
                elif op == 0x51:  # MLOAD
                    f.use_gas(G_VERYLOW)
                    f.push(int.from_bytes(f.mem.read(f.pop(), 32), "big"))
                elif op == 0x52:  # MSTORE
                    f.use_gas(G_VERYLOW)
                    off, v = f.pop(), f.pop()
                    f.mem.write(off, v.to_bytes(32, "big"))
                elif op == 0x53:  # MSTORE8
                    f.use_gas(G_VERYLOW)
                    off, v = f.pop(), f.pop()
                    f.mem.write(off, bytes([v & 0xFF]))
                elif op == 0x54:  # SLOAD (EIP-2929 cold/warm)
                    slot_b = f.pop().to_bytes(32, "big")
                    f.use_gas(acc.slot_cost(address, slot_b))
                    raw = state.get(T_STORE, address + slot_b)
                    f.push(int.from_bytes(raw, "big") if raw else 0)
                elif op == 0x55:  # SSTORE (EIP-2200 net + EIP-3529)
                    if static:
                        raise EVMError("SSTORE in static call")
                    if f.gas <= G_SSTORE_SENTRY:
                        raise OutOfGas("sstore sentry")
                    slot, v = f.pop(), f.pop()
                    slot_b = slot.to_bytes(32, "big")
                    key = store_key(slot)
                    raw = state.get(T_STORE, key)
                    current = int.from_bytes(raw, "big") if raw else 0
                    orig = acc.note_original(address, slot_b, current)
                    f.use_gas(acc.sstore_gas(current, orig, v,
                                             address, slot_b))
                    if v != current:
                        if v == 0:
                            state.remove(T_STORE, key)
                        else:
                            state.set(T_STORE, key, v.to_bytes(32, "big"))
                elif op == 0x56:  # JUMP
                    f.use_gas(G_MID)
                    d = f.pop()
                    if d not in jumpdests:
                        raise EVMError("bad jump destination")
                    f.pc = d + 1
                elif op == 0x57:  # JUMPI
                    f.use_gas(G_HIGH)
                    d, c = f.pop(), f.pop()
                    if c:
                        if d not in jumpdests:
                            raise EVMError("bad jump destination")
                        f.pc = d + 1
                elif op == 0x58:  # PC
                    f.use_gas(G_BASE)
                    f.push(f.pc - 1)
                elif op == 0x59:  # MSIZE
                    f.use_gas(G_BASE)
                    f.push(len(f.mem.data))
                elif op == 0x5A:  # GAS
                    f.use_gas(G_BASE)
                    f.push(f.gas)
                elif op == 0x5B:  # JUMPDEST
                    f.use_gas(1)
                elif op == 0x5C:  # TLOAD (EIP-1153)
                    f.use_gas(G_SLOAD)
                    slot_b = f.pop().to_bytes(32, "big")
                    f.push(acc.tload(address, slot_b))
                elif op == 0x5D:  # TSTORE (EIP-1153)
                    if static:
                        raise EVMError("TSTORE in static call")
                    f.use_gas(G_SLOAD)
                    slot, v = f.pop(), f.pop()
                    acc.tstore(address, slot.to_bytes(32, "big"), v)
                elif op == 0x5E:  # MCOPY (EIP-5656), memmove semantics
                    d, s, n = f.pop(), f.pop(), f.pop()
                    f.use_gas(G_VERYLOW
                              + G_COPY_WORD * ((_gas_size(n) + 31) // 32))
                    if n:
                        blob = f.mem.read(s, n)  # charges src expansion
                        f.mem.write(d, blob)     # charges dst expansion
                elif 0xA0 <= op <= 0xA4:  # LOG0..LOG4
                    if static:
                        raise EVMError("LOG in static call")
                    ntopics = op - 0xA0
                    off, size = f.pop(), f.pop()
                    topics = [f.pop().to_bytes(32, "big")
                              for _ in range(ntopics)]
                    f.use_gas(G_LOG + G_LOG_TOPIC * ntopics
                              + G_LOG_DATA * _gas_size(size))
                    logs.append(LogEntry(address=address, topics=topics,
                                         data=f.mem.read(off, size)))
                elif op == 0xF0 or op == 0xF5:  # CREATE / CREATE2
                    if static:
                        raise EVMError("CREATE in static call")
                    v = f.pop()
                    off, size = f.pop(), f.pop()
                    salt = f.pop() if op == 0xF5 else None
                    f.use_gas(G_CREATE
                              + G_INITCODE_WORD * ((_gas_size(size) + 31) // 32))
                    init = f.mem.read(off, size)
                    gas_child = f.gas - f.gas // 64
                    f.use_gas(gas_child)
                    res = self.create(state, env, address, v, init,
                                      gas_child, depth + 1, salt)
                    f.gas += res.gas_left
                    f.ret = res.output if not res.success else b""
                    logs.extend(res.logs)
                    f.push(int.from_bytes(res.create_address, "big")
                           if res.success else 0)
                elif op in (0xF1, 0xF2, 0xF4, 0xFA):  # CALL family
                    gas_req = f.pop()
                    to_i = f.pop()
                    if op in (0xF1, 0xF2):
                        v = f.pop()
                    else:
                        v = 0
                    in_off, in_size = f.pop(), f.pop()
                    out_off, out_size = f.pop(), f.pop()
                    if static and v and op == 0xF1:
                        raise EVMError("value call in static context")
                    to_b = _addr_bytes(to_i)
                    f.use_gas(acc.account_cost(to_b)
                              + (G_CALLVALUE if v else 0))
                    args = f.mem.read(in_off, in_size)
                    f.mem.extend(out_off, out_size)
                    avail = f.gas - f.gas // 64
                    gas_child = min(gas_req, avail)
                    f.use_gas(gas_child)
                    if v:
                        gas_child += G_CALLSTIPEND
                    if op == 0xF1:  # CALL
                        res = self.execute_message(
                            state, env, address, to_b, v, args, gas_child,
                            depth + 1, static)
                    elif op == 0xF2:  # CALLCODE: run their code as us
                        res = self._call_with_code(
                            state, env, address, address, v, args, gas_child,
                            depth + 1, static, self.get_code(state, to_b))
                    elif op == 0xF4:  # DELEGATECALL
                        res = self._call_with_code(
                            state, env, caller, address, value, args,
                            gas_child, depth + 1, static,
                            self.get_code(state, to_b))
                    else:  # STATICCALL
                        res = self.execute_message(
                            state, env, address, to_b, 0, args, gas_child,
                            depth + 1, True)
                    f.gas += res.gas_left
                    f.ret = res.output
                    logs.extend(res.logs)
                    out = res.output[:out_size]
                    if out:
                        f.mem.write(out_off, out)
                    f.push(1 if res.success else 0)
                elif op == 0xF3:  # RETURN
                    off, size = f.pop(), f.pop()
                    return EVMResult(True, f.mem.read(off, size), f.gas, logs)
                elif op == 0xFD:  # REVERT
                    off, size = f.pop(), f.pop()
                    return EVMResult(False, f.mem.read(off, size), f.gas,
                                     [], error="revert")
                elif op == 0xFE:  # INVALID
                    raise EVMError("invalid opcode 0xfe")
                elif op == 0xFF:  # SELFDESTRUCT
                    if static:
                        raise EVMError("SELFDESTRUCT in static call")
                    heir = _addr_bytes(f.pop())
                    f.use_gas(G_SELFDESTRUCT
                              + acc.account_surcharge(heir))
                    self.do_selfdestruct(state, address, heir)
                    return EVMResult(True, b"", f.gas, logs)
                else:
                    raise EVMError(f"unknown opcode 0x{op:02x}")
            return EVMResult(True, b"", f.gas, logs)
        except OutOfGas:
            return EVMResult(False, b"", 0, [], error="out of gas")
        except EVMError as exc:
            return EVMResult(False, b"", 0, [], error=str(exc))

    def _call_with_code(self, state, env, caller, address, value, data, gas,
                        depth, static, code) -> EVMResult:
        """DELEGATECALL/CALLCODE: run foreign code in our storage context."""
        if depth > MAX_DEPTH:
            return EVMResult(False, gas_left=gas, error="call depth")
        if not code:
            return EVMResult(True, gas_left=gas)
        acc = self.access()
        sp = state.savepoint()
        sp_acc = acc.snapshot()
        res = self._run_in_message(state, env, code, caller, address, value,
                                   data, gas, depth, static)
        if res.success:
            state.release(sp)
        else:
            state.rollback_to(sp)
            acc.rollback_to(sp_acc)
        return res
