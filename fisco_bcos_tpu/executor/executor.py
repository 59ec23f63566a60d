"""TransactionExecutor — block execution over the state overlay.

Reference counterpart: /root/reference/bcos-executor/src/executor/
TransactionExecutor.cpp (:120 executeTransactions serial path, :143
dagExecuteTransactions) + executive/TransactionExecutive.cpp (per-tx call
dispatch, revert on error). Round-1 scope: precompile dispatch with
per-transaction savepoint revert, serial and DAG-parallel scheduling (the
DAG plans conflict-free groups from declared critical fields like
dag/CriticalFields.h:45; groups execute in topological waves).

State root: the reference derives it from storage hashes at commit. Here the
root is H over the block's sorted changeset entry digests — computed as a
width-16 device Merkle over per-entry hashes, so a 64k-entry block is one
TPU call (ops.merkle), bit-identical on the host fallback.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

from ..codec import abi as abi_mod
from ..protocol import Receipt, Transaction, TransactionStatus
from ..storage.interface import ChangeSet
from ..storage.state import StateStorage
from ..utils import otrace
from ..utils.log import metric
from .precompiled import (
    PRECOMPILED_REGISTRY,
    CallContext,
    Precompile,
    PrecompileError,
    SMALLBANK_ADDRESS,
    account_status,
    ACCOUNT_NORMAL,
    check_deploy_auth,
    check_method_auth,
    contract_available,
    record_contract_admin,
)
from .wasm import WasmEngine, is_wasm

TX_GAS = 21_000  # flat per-tx gas for precompile calls (EVM meters its own)
WASM_GAS_LIMIT = 2_000_000  # per-call interpreter budget (instruction units)


def state_leaf_payload(table: str, key: bytes, value: bytes,
                       deleted: bool = False) -> bytes:
    """The canonical preimage of one state-root leaf: a changeset entry
    serialized as table \\0 key \\0 tag value. ONE definition shared by
    the root computation below and every state-proof verifier
    (zk/proof.py, the light client, sanitize_ci --zk) — a verifier
    recomputes H(payload) from the claimed value and checks the digest's
    inclusion under header.state_root."""
    tag = b"\x01" if deleted else b"\x00"
    return table.encode() + b"\x00" + key + b"\x00" + tag + value


class WasmHostContext:
    """Contract I/O bridge the interpreter's env imports resolve against
    (the reference's BCOS host interface for liquid contracts: input,
    output, storage, caller, revert, events)."""

    TABLE = "s_wasm"

    def __init__(self, state, suite, address: bytes, sender: bytes,
                 input_data: bytes):
        self.state = state
        self.suite = suite
        self.address = address
        self.sender = sender
        self.input = input_data
        self.output = b""
        self.logs: list[bytes] = []
        self.inst = None

    def bind(self, inst, args: bytes) -> None:
        self.inst = inst
        self.input = args

    def _key(self, k: bytes) -> bytes:
        return self.address + b"/" + k

    def funcs(self) -> dict:
        from .wasm_interp import WasmRevertError

        def revert(inst, ptr, ln):
            raise WasmRevertError(inst.mem_read(ptr, ln))

        def storage_read(inst, kptr, klen, vptr, vcap):
            v = self.state.get(self.TABLE,
                               self._key(inst.mem_read(kptr, klen)))
            if v is None:
                return -1
            inst.mem_write(vptr, v[:vcap])
            return len(v)

        def storage_write(inst, kptr, klen, vptr, vlen):
            self.state.set(self.TABLE, self._key(inst.mem_read(kptr, klen)),
                           inst.mem_read(vptr, vlen))

        return {
            "input_size": lambda inst: len(self.input),
            "input_copy": lambda inst, ptr: inst.mem_write(ptr, self.input),
            "caller_copy": lambda inst, ptr: inst.mem_write(
                ptr, self.sender[:20].ljust(20, b"\x00")),
            "set_output": lambda inst, ptr, ln: self._set_output(
                inst.mem_read(ptr, ln)),
            "storage_read": storage_read,
            "storage_write": storage_write,
            "revert": revert,
            "log_event": lambda inst, ptr, ln: self.logs.append(
                inst.mem_read(ptr, ln)),
        }

    def _set_output(self, data: bytes) -> None:
        self.output = data


class TransactionExecutor:
    def __init__(self, suite,
                 registry: Optional[dict[bytes, Precompile]] = None,
                 trace_label: str = ""):
        self.suite = suite
        self.registry = dict(PRECOMPILED_REGISTRY if registry is None else registry)
        # the node's stage table (utils/otrace.py): `dag_plan` and the
        # dag_* counters here, the evm_* frame counters in the EVM
        self.stages = otrace.stages(trace_label)
        from .evm import EVM
        self.evm = EVM(suite, registry=self.registry, stages=self.stages)
        # parallel-annotation cache: address -> (abi bytes, {sel: heads})
        self._parallel_cache: dict[bytes, tuple[bytes, dict]] = {}
        # block-start compatibility_version snapshot (block_number, version):
        # taken BEFORE any tx of the block executes so a same-block
        # governance raise activates next block, not mid-block
        self._compat_snapshot: Optional[tuple[int, tuple]] = None

    # -- single transaction ------------------------------------------------
    def execute_transaction(self, tx: Transaction, state: StateStorage,
                            block_number: int, timestamp: int,
                            gas_limit: int = 3_000_000_000) -> Receipt:
        if self._compat_snapshot is None or \
                self._compat_snapshot[0] != block_number:
            # first touch of this block outside the DAG path (serial /
            # read-only call): the state is still block-start clean here
            from .evm import EVM as _EVM
            self._compat_snapshot = (block_number,
                                     _EVM.read_compat_version(state))
        sender = tx.sender(self.suite) or b""
        sp = state.savepoint()
        try:
            code = (b"" if tx.to == b"" or tx.to in self.registry
                    else self.evm.get_code(state, tx.to))
            rc = self._auth_gate(tx, state, sender, block_number, code)
            if rc is not None:
                state.release(sp)
                return rc
            if tx.to == b"":
                if is_wasm(tx.input):
                    rc = self._execute_wasm_create(tx, state, sender,
                                                   block_number)
                else:
                    rc = self._execute_create(tx, state, sender, block_number,
                                              timestamp, gas_limit)
            elif code and is_wasm(code):
                rc = self._execute_wasm_call(tx, state, sender, block_number,
                                             code)
            elif code:
                rc = self._execute_evm(tx, state, sender, block_number,
                                       timestamp, gas_limit)
            else:
                rc = self._execute_precompile(tx, state, sender, block_number,
                                              timestamp, gas_limit)
            state.release(sp)
            return rc
        except Exception as exc:  # defensive: executor must not kill the node
            state.rollback_to(sp)
            rc = Receipt(block_number=block_number, gas_used=TX_GAS)
            rc.status = int(TransactionStatus.EXECUTION_ABORTED)
            rc.message = f"internal: {exc}"
            return rc

    def _auth_gate(self, tx, state, sender: bytes,
                   block_number: int, code: bytes) -> Optional[Receipt]:
        """Deterministic, state-driven auth checks before any execution
        (the reference's auth-check path in TransactionExecutive): frozen/
        abolished sender accounts, the chain deploy ACL, per-contract
        freeze, and per-method ACLs. Returns a denial receipt or None."""
        def deny(status, msg):
            rc = Receipt(block_number=block_number, gas_used=TX_GAS)
            rc.status = int(status)
            rc.message = msg
            return rc

        if account_status(state, sender) != ACCOUNT_NORMAL:
            return deny(TransactionStatus.ACCOUNT_FROZEN,
                        "sender account frozen/abolished")
        if tx.to == b"":
            if not check_deploy_auth(state, sender):
                return deny(TransactionStatus.PERMISSION_DENIED,
                            "deploy denied by chain ACL")
            return None
        if tx.to in self.registry:
            return None  # system precompiles gate themselves
        if not contract_available(state, tx.to):
            return deny(TransactionStatus.CONTRACT_FROZEN, "contract frozen")
        # method selector: EVM = first 4 input bytes; WASM = H(method)[:4]
        # (wasm call data is SCALE method-name + args, so a raw input prefix
        # would never match an ACL keyed by method hash)
        if code and is_wasm(code):
            from ..codec import scale
            try:
                selector = self.suite.hash(
                    scale.Decoder(tx.input).string().encode())[:4]
            except Exception:
                selector = b""  # malformed call data traps in execution
        else:
            selector = tx.input[:4]
        if not check_method_auth(state, tx.to, selector, sender):
            return deny(TransactionStatus.PERMISSION_DENIED,
                        "method call denied by contract ACL")
        return None

    def _env(self, sender: bytes, block_number: int, timestamp: int,
             gas_limit: int):
        from .evm import TxEnv
        snap = self._compat_snapshot
        return TxEnv(origin=sender, gas_price=0, block_number=block_number,
                     timestamp=timestamp, gas_limit=gas_limit,
                     compat_version=(snap[1] if snap and snap[0] == block_number
                                     else None))

    def _execute_create(self, tx, state, sender, block_number, timestamp,
                        gas_limit) -> Receipt:
        """Contract deployment (empty `to`, input = EVM initcode)."""
        env = self._env(sender, block_number, timestamp, gas_limit)
        res = self.evm.create(state, env, sender, 0, tx.input, gas_limit)
        gas_used = gas_limit - res.gas_left
        gas_used -= self.evm.take_refund(gas_used)  # EIP-3529 cap inside
        rc = Receipt(block_number=block_number, gas_used=gas_used)
        if res.success:
            rc.contract_address = res.create_address
            rc.logs = res.logs
            record_contract_admin(state, res.create_address, sender)
            if tx.abi:
                state.set(self.T_ABI, res.create_address, tx.abi.encode())
        else:
            rc.status = int(TransactionStatus.REVERT if res.error == "revert"
                            else TransactionStatus.EXECUTION_ABORTED)
            rc.output = res.output
            rc.message = res.error
        return rc

    def _execute_evm(self, tx, state, sender, block_number, timestamp,
                     gas_limit) -> Receipt:
        env = self._env(sender, block_number, timestamp, gas_limit)
        res = self.evm.execute_message(state, env, sender, tx.to, 0,
                                       tx.input, gas_limit)
        gas_used = gas_limit - res.gas_left
        gas_used -= self.evm.take_refund(gas_used)  # EIP-3529 cap inside
        rc = Receipt(block_number=block_number, gas_used=gas_used,
                     output=res.output)
        if res.success:
            rc.logs = res.logs
        else:
            if res.error == "revert":
                rc.status = int(TransactionStatus.REVERT)
            elif res.error == "out of gas":
                rc.status = int(TransactionStatus.OUT_OF_GAS)
            else:
                rc.status = int(TransactionStatus.EXECUTION_ABORTED)
            rc.message = res.error
        return rc

    # -- WASM ("liquid") contracts -----------------------------------------
    def _execute_wasm_create(self, tx, state, sender, block_number
                             ) -> Receipt:
        """Deploy: tx.input is the module bytes; run exported `deploy` if
        present (the liquid constructor)."""
        from .wasm_interp import (
            Instance,
            Module,
            WasmOutOfGas,
            WasmRevertError,
            WasmTrap,
        )

        addr = self.suite.hash(sender + tx.nonce.encode() + b"\x00wasm")[12:]
        rc = Receipt(block_number=block_number, gas_used=TX_GAS)
        sp = state.savepoint()
        try:
            if not WasmEngine.available():
                raise PrecompileError(
                    "wasm execution disabled (WITH_WASM=OFF analogue)",
                    TransactionStatus.EXECUTION_ABORTED)
            m = Module(tx.input)  # one parse: validates structure
            state.set(self.T_CODE, addr, tx.input)
            record_contract_admin(state, addr, sender)
            host = WasmHostContext(state, self.suite, addr, sender, b"")
            inst = Instance(m, host.funcs(), WASM_GAS_LIMIT)
            host.bind(inst, b"")
            if "deploy" in m.exports:  # the liquid constructor
                inst.invoke("deploy", [])
            rc.gas_used += WASM_GAS_LIMIT - inst.gas
            rc.contract_address = addr
            rc.logs = [(addr, [], blob) for blob in host.logs]
            if tx.abi:
                state.set(self.T_ABI, addr, tx.abi.encode())
            state.release(sp)
        except PrecompileError as exc:
            state.rollback_to(sp)
            rc.status = int(exc.status)
            rc.message = str(exc)
        except WasmOutOfGas:
            state.rollback_to(sp)
            rc.status = int(TransactionStatus.OUT_OF_GAS)
            rc.gas_used += WASM_GAS_LIMIT
            rc.message = "wasm deploy out of gas"
        except WasmRevertError as exc:
            state.rollback_to(sp)
            rc.status = int(TransactionStatus.REVERT)
            rc.output = exc.data
            rc.gas_used += WASM_GAS_LIMIT - getattr(exc, "gas_left", 0)
            rc.message = "wasm deploy reverted"
        except (WasmTrap, ValueError) as exc:
            state.rollback_to(sp)
            rc.status = int(TransactionStatus.EXECUTION_ABORTED)
            rc.gas_used += WASM_GAS_LIMIT - getattr(exc, "gas_left", 0)
            rc.message = str(exc)
        return rc

    def _execute_wasm_call(self, tx, state, sender, block_number, code
                           ) -> Receipt:
        """Call: tx.input = SCALE(method-name string) ++ raw arg bytes."""
        from ..codec import scale
        from .wasm_interp import WasmOutOfGas, WasmRevertError, WasmTrap

        rc = Receipt(block_number=block_number, gas_used=TX_GAS)
        sp = state.savepoint()
        try:
            d = scale.Decoder(tx.input)
            func = d.string()
            args = d._take(d.remaining())
            host = WasmHostContext(state, self.suite, tx.to, sender, args)
            out, gas_left = WasmEngine().execute(code, func, args,
                                                 WASM_GAS_LIMIT, host=host)
            rc.output = out
            rc.gas_used += WASM_GAS_LIMIT - gas_left
            rc.logs = [(tx.to, [], blob) for blob in host.logs]
            state.release(sp)
        except WasmOutOfGas:
            state.rollback_to(sp)
            rc.status = int(TransactionStatus.OUT_OF_GAS)
            rc.gas_used += WASM_GAS_LIMIT
            rc.message = "wasm out of gas"
        except WasmRevertError as exc:
            state.rollback_to(sp)
            rc.status = int(TransactionStatus.REVERT)
            rc.output = exc.data
            rc.gas_used += WASM_GAS_LIMIT - getattr(exc, "gas_left", 0)
            rc.message = "wasm revert"
        except (WasmTrap, ValueError, scale.ScaleError) as exc:
            state.rollback_to(sp)
            rc.status = int(TransactionStatus.EXECUTION_ABORTED)
            rc.gas_used += WASM_GAS_LIMIT - getattr(exc, "gas_left", 0)
            rc.message = f"wasm trap: {exc}"
        return rc

    def _execute_precompile(self, tx, state, sender, block_number, timestamp,
                            gas_limit) -> Receipt:
        sp = state.savepoint()
        ctx = CallContext(state=state, block_number=block_number,
                          timestamp=timestamp, sender=sender, to=tx.to,
                          input=tx.input, gas_limit=gas_limit,
                          suite=self.suite)
        rc = Receipt(block_number=block_number, gas_used=TX_GAS)
        try:
            handler = self.registry.get(tx.to)
            if handler is None:
                raise PrecompileError("no contract at address",
                                      TransactionStatus.CALL_ADDRESS_ERROR)
            rc.output = handler.call(ctx)
            rc.logs = ctx.logs
            state.release(sp)
        except PrecompileError as exc:
            state.rollback_to(sp)
            rc.status = int(exc.status)
            rc.message = str(exc)
        except Exception as exc:  # defensive: executor must not kill the node
            state.rollback_to(sp)
            rc.status = int(TransactionStatus.EXECUTION_ABORTED)
            rc.message = f"internal: {exc}"
        return rc

    # -- serial block ------------------------------------------------------
    def execute_block_serial(self, txs: Sequence[Transaction],
                             state: StateStorage, block_number: int,
                             timestamp: int) -> list[Receipt]:
        return [self.execute_transaction(tx, state, block_number, timestamp)
                for tx in txs]

    # -- DAG block (conflict-free waves) -----------------------------------
    def plan_dag(self, txs: Sequence[Transaction],
                 state: Optional[StateStorage] = None) -> list[list[int]]:
        """Group tx indices into topological waves by critical-field overlap.

        The reference derives critical fields from parallel-contract
        annotations (CriticalFields.h:45, TxDAG2.h:34). Here EVERY
        precompile can declare its own via ``Precompile.conflict_keys``
        (a dry parse of call data, no state mutation), and EVM contracts
        opt in through the parallel-ABI annotation (see
        ``_evm_parallel_keys`` — the reference's ParallelConfig scheme).
        Unknown/opaque txs fall into singleton waves in order."""
        last_wave_of_key: dict[bytes, int] = {}
        waves: list[list[int]] = []
        for i, tx in enumerate(txs):
            keys = self._conflict_keys(tx, state)
            if keys is None:
                # opaque: serialize against everything before and after it
                w = len(waves)
                waves.append([i])
                last_wave_of_key.clear()
                last_wave_of_key[b"*"] = w
                continue
            w = last_wave_of_key.get(b"*", -1)
            for k in keys:
                w = max(w, last_wave_of_key.get(k, -1))
            w += 1
            if w == len(waves):
                waves.append([])
            waves[w].append(i)
            for k in keys:
                last_wave_of_key[k] = w
        return waves

    def _conflict_keys(self, tx: Transaction,
                       state: Optional[StateStorage] = None
                       ) -> Optional[list[bytes]]:
        """Static conflict analysis; None = opaque (serialize)."""
        handler = self.registry.get(tx.to)
        if handler is not None:
            try:
                return handler.conflict_keys(tx.input)
            except Exception:
                return None
        if state is not None and tx.to:
            return self._evm_parallel_keys(tx, state)
        return None

    def _evm_parallel_keys(self, tx: Transaction, state: StateStorage
                           ) -> Optional[list[bytes]]:
        """Parallel-contract annotation for EVM txs: an ABI function entry
        carrying ``"parallel": N`` declares that two calls conflict iff
        they share the value of any of their first N parameters — the
        reference's ParallelConfigPrecompiled registration scheme
        (bcos-executor/src/dag/CriticalFields.h:45-60, critical fields =
        leading params of registered methods). A static parameter's value
        is its head word(s); a ``string``/``bytes`` one's is its contents,
        read at the offset its head word gives (its head word is an offset
        that says nothing of the value). Keys are address||value so
        different annotated methods touching the same account still
        conflict with each other. Calldata shorter than the declared
        parameters, or an offset or length pointing outside it, is
        opaque (None)."""
        try:
            raw = state.get(self.T_ABI, tx.to)
            if not raw:
                return None
            data = tx.input
            sel = data[:4]
            if len(sel) != 4:
                return None
            heads = self._parallel_selectors(tx.to, raw).get(sel)
            if not heads:
                return None
            keys = []
            for at, size, contents in heads:
                value = data[at:at + size]
                if len(value) != size:
                    return None
                if contents:
                    start = 4 + int.from_bytes(value, "big")
                    if start + 32 > len(data):
                        return None
                    end = start + 32 + int.from_bytes(
                        data[start:start + 32], "big")
                    if end > len(data):
                        return None
                    value = data[start + 32:end]
                keys.append(tx.to + value)
            return keys
        except Exception:
            return None

    def _parallel_selectors(self, address: bytes, raw_abi: bytes
                            ) -> dict[bytes, Optional[tuple]]:
        """{selector: the critical parameters' (calldata offset of the
        head, head bytes, contents?)} for a contract's annotated
        functions (None: an annotation the planner cannot read, so the
        call is opaque), cached per (address, abi bytes) so block planning
        does one JSON parse + selector-hash pass per contract, not per
        tx."""
        cached = self._parallel_cache.get(address)
        if cached is not None and cached[0] == raw_abi:
            return cached[1]
        import json

        sel_map: dict[bytes, Optional[tuple]] = {}
        for e in json.loads(raw_abi):
            if e.get("type") != "function" or not e.get("parallel"):
                continue
            inputs = [i["type"] for i in e.get("inputs", [])]
            sig = e["name"] + "(" + ",".join(inputs) + ")"
            sel_map[abi_mod.selector(sig, self.suite.hash)] = \
                self._critical_heads(inputs, int(e["parallel"]))
        if len(self._parallel_cache) >= 256:
            self._parallel_cache.pop(next(iter(self._parallel_cache)))
        self._parallel_cache[address] = (raw_abi, sel_map)
        return sel_map

    @staticmethod
    def _critical_heads(inputs: list[str], n: int) -> Optional[tuple]:
        """Where the first `n` parameters' values sit: a static type's
        head words, or (``string``/``bytes``) the contents its head word
        points at. None where one is an array or tuple of dynamic size,
        or `n` passes the parameters."""
        heads = []
        at = 4
        for typ in inputs[:n]:
            t = abi_mod.parse_type(typ)
            if t.kind in ("string", "bytes"):
                heads.append((at, 32, True))
            elif t.dynamic:
                return None
            else:
                heads.append((at, 32 * t.head_words(), False))
            at += 32 * t.head_words()
        return tuple(heads) if len(heads) == n else None

    def execute_block_dag(self, txs: Sequence[Transaction],
                          state: StateStorage, block_number: int,
                          timestamp: int) -> list[Receipt]:
        """Execute in conflict-free waves. Within a wave order is irrelevant
        by construction, so results equal the serial schedule.

        Every wave runs serially on the block state. A wave thread pool
        (the reference's tbb wave execution, TransactionExecutor.cpp:143)
        does not pay on this interpreter: a ParallelOk transfer spends its
        frame mostly in the SLOAD / SSTORE / gas host callbacks, which take
        the GIL back, and a pooled wave adds a per-transaction overlay,
        merge and dispatch. On a TPU v5e host (13 cores, four nodes) pooled
        waves cost the `air4-parallelok` cell 637-687 ms of `execute` a
        block against 164 ms serial. A precompile holds the GIL for its
        whole body. Counted once a block into the node's stage table:
        `dag_plan` (the planner, conflict keys included), dag_blocks /
        dag_waves / dag_txs, and the block's SmallBank calls and those of
        them refused (smallbank_calls / smallbank_refused)."""
        t0 = time.monotonic()
        # snapshot the feature-gate version from block-START state, before
        # any tx (possibly a governance raise) dirties the overlay
        from .evm import EVM as _EVM
        self._compat_snapshot = (block_number,
                                 _EVM.read_compat_version(state))
        with self.stages.stage("dag_plan"):
            waves = self.plan_dag(txs, state)
        receipts: list[Optional[Receipt]] = [None] * len(txs)
        for wave in waves:
            for i in wave:
                receipts[i] = self.execute_transaction(
                    txs[i], state, block_number, timestamp)
        said = [rc.status for tx, rc in zip(txs, receipts)
                if tx.to == SMALLBANK_ADDRESS]
        for name, n in (("dag_blocks", 1), ("dag_waves", len(waves)),
                        ("dag_txs", len(txs)),
                        ("smallbank_calls", len(said)),
                        ("smallbank_refused", sum(map(bool, said)))):
            self.stages.count(name, n)
        metric("executor.dag", n=len(txs), waves=len(waves),
               ms=int((time.monotonic() - t0) * 1000))
        return [r for r in receipts]

    # -- contract metadata (getCode/getABI RPC; EVM deploy writes these;
    # table layout owned by evm.py — single definition) --------------------
    from .evm import T_CODE
    T_ABI = "s_abi"

    def get_code(self, address: bytes, storage) -> bytes:
        from .evm import EVM
        return EVM.get_code(storage, address)

    def get_abi(self, address: bytes, storage) -> str:
        raw = storage.get(self.T_ABI, address)
        return raw.decode() if raw else ""

    # -- state root (device Merkle over changeset digests) -----------------
    def state_root(self, changes: ChangeSet) -> bytes:
        return self.state_root_with_leaves(changes)[0]

    def state_root_with_leaves(self, changes: ChangeSet
                               ) -> tuple[bytes, list]:
        """-> (root, [(table, key, leaf_digest)]) over the sorted
        changeset. The leaf list is the block's state-proof index
        (zk/proof.py + Ledger.state_proof): persisting it alongside the
        block lets `getProof` serve changeset-inclusion proofs anchored
        at this root without re-reading (or retaining) the values — the
        digests here are a free by-product of the root computation.
        Counts the leaves into the stage table (`state_leaves`), once a
        block."""
        self.stages.count("state_leaves", len(changes))
        if not changes:
            return b"\x00" * 32, []
        items = sorted(changes.items(), key=lambda kv: (kv[0][0], kv[0][1]))
        payloads = [state_leaf_payload(table, key, entry.value,
                                       entry.deleted)
                    for (table, key), entry in items]
        leaves = self.suite.hash_batch(payloads)
        return (self.suite.merkle_root(leaves),
                [(tk[0], tk[1], leaf)
                 for (tk, _e), leaf in zip(items, leaves)])
