"""ParallelOk — java-sdk-demo's Solidity transfer contract, as chains run it.

Upstream's perf tool (`ParallelOkPerf`, `parallelok` mode) deploys
`ParallelOk.sol`, calls `set(name, balance)` once per user and then sends
`transfer(from, to, num)`. The contract::

    mapping(string => uint256) _balance;                      // slot 0
    function transfer(string from, string to, uint256 num)   // "overflow
        { _balance[from] -= num; _balance[to] += num; }       //  is ok"
    function set(string name, uint256 num) { _balance[name] = num; }
    function balanceOf(string name) view returns (uint256)
    function enableParallel()  // transfer: 2 criticals, set: 1

There is no `solc` here, so the runtime code below is written by hand in
EVM assembly, in the compiler's shape: free-memory pointer, non-payable
check, selector dispatch, ABI decoding of each `string` into memory (offset
and length bounds-checked, REVERT on short or malformed calldata), and
`_balance[name]` at `keccak256(bytes(name) ++ uint256(0))` as Solidity lays
a `mapping(string => ...)` at slot 0. The arithmetic is unchecked (pre-0.8
Solidity). The selectors and ABI are the source's; the bytes are not the
compiler's. `ABI` carries the `"parallel"` counts that `enableParallel()`
registers upstream; the executor plans the DAG from it (executor.py
`_evm_parallel_keys`).

`chipbench/workloads/parallelok.py` and `tests/test_parallelok.py` share
this one copy.
"""

from __future__ import annotations

import itertools
import json
from typing import Iterable

from ..codec import abi as abi_mod
from ..executor.evm import T_CODE, T_STORE
from ..executor.executor import TransactionExecutor
from .scenario import PREFUND_BATCH

# where the contract sits: a constant standing in for the address its
# deployment would derive from the deployer and its nonce
ADDRESS = bytes.fromhex("0000000000000000000000000000000000c0ffee")
BALANCE_SLOT = 0  # `_balance` is the contract's first state variable

SIGNATURES = {"transfer": "transfer(string,string,uint256)",
              "set": "set(string,uint256)",
              "balanceOf": "balanceOf(string)"}

ABI = json.dumps([
    {"type": "function", "name": "transfer", "stateMutability": "nonpayable",
     "inputs": [{"name": "from", "type": "string"},
                {"name": "to", "type": "string"},
                {"name": "num", "type": "uint256"}],
     "outputs": [], "parallel": 2},
    {"type": "function", "name": "set", "stateMutability": "nonpayable",
     "inputs": [{"name": "name", "type": "string"},
                {"name": "num", "type": "uint256"}],
     "outputs": [], "parallel": 1},
    {"type": "function", "name": "balanceOf", "stateMutability": "view",
     "inputs": [{"name": "name", "type": "string"}],
     "outputs": [{"name": "", "type": "uint256"}]},
], separators=(",", ":"))


# -- a two-pass assembler ----------------------------------------------------
# An item is an opcode (int), ("push", value), ("label", name) (a JUMPDEST)
# or ("ref", name) (PUSH2 of the label's offset).

(STOP, ADD, SUB, LT, EQ, ISZERO, AND, NOT, SHR, KECCAK256, CALLVALUE,
 CALLDATALOAD, CALLDATASIZE, CALLDATACOPY, POP, MLOAD, MSTORE, SLOAD, SSTORE,
 JUMP, JUMPI, JUMPDEST, DUP1, DUP2, DUP3, DUP4, DUP5, SWAP1, SWAP2, RETURN,
 REVERT) = (0x00, 0x01, 0x03, 0x10, 0x14, 0x15, 0x16, 0x19, 0x1C, 0x20, 0x34,
            0x35, 0x36, 0x37, 0x50, 0x51, 0x52, 0x54, 0x55, 0x56, 0x57, 0x5B,
            0x80, 0x81, 0x82, 0x83, 0x84, 0x90, 0x91, 0xF3, 0xFD)


def _push(v: int) -> bytes:
    n = max(1, (v.bit_length() + 7) // 8)
    return bytes([0x5F + n]) + v.to_bytes(n, "big")


def assemble(items: Iterable) -> bytes:
    """Bytecode of `items`: labels placed in a first pass, refs filled in a
    second."""
    items = list(items)
    where: dict = {}
    pc = 0
    for it in items:
        if isinstance(it, int):
            pc += 1
        elif it[0] == "push":
            pc += len(_push(it[1]))
        elif it[0] == "label":
            where[it[1]] = pc
            pc += 1
        else:  # ref
            pc += 3
    out = bytearray()
    for it in items:
        if isinstance(it, int):
            out.append(it)
        elif it[0] == "push":
            out += _push(it[1])
        elif it[0] == "label":
            out.append(JUMPDEST)
        else:
            out += bytes([0x61]) + where[it[1]].to_bytes(2, "big")
    return bytes(out)


def _p(v: int):
    return ("push", v)


def _l(name: str):
    return ("label", name)


def _r(name: str):
    return ("ref", name)


def _revert_if(cond: list) -> list:
    """`cond` leaves a flag on the stack: REVERT(0, 0) where it is set."""
    return [*cond, _r("revert"), JUMPI]


def _call(sub: str, back: str, *arg) -> list:
    """Jump to a subroutine with a return label under its argument."""
    return [_r(back), *arg, _r(sub), JUMP, _l(back)]


def _short(*n) -> list:
    """calldatasize < n, with n pushed here or (no argument) on the stack."""
    return [*(_p(v) for v in n), CALLDATASIZE, LT]


_U64 = (1 << 64) - 1


def runtime_code(hash_fn) -> bytes:
    """The contract's runtime bytecode. `hash_fn` is the chain's hash (the
    selectors are its first four bytes of the signature, as the ABI's)."""
    sel = {k: int.from_bytes(abi_mod.selector(s, hash_fn), "big")
           for k, s in SIGNATURES.items()}
    return assemble([
        _p(0x80), _p(0x40), MSTORE,                 # free-memory pointer
        CALLVALUE, ISZERO, _r("paid"), JUMPI,       # every function is
        _p(0), DUP1, REVERT,                        # non-payable
        _l("paid"),
        *_revert_if(_short(4)),
        _p(0), CALLDATALOAD, _p(0xE0), SHR,
        DUP1, _p(sel["transfer"]), EQ, _r("transfer"), JUMPI,
        DUP1, _p(sel["set"]), EQ, _r("set"), JUMPI,
        DUP1, _p(sel["balanceOf"]), EQ, _r("balanceOf"), JUMPI,
        _l("revert"), _p(0), DUP1, REVERT,

        # transfer(string from, string to, uint256 num)
        _l("transfer"), POP,
        *_revert_if(_short(4 + 96)),
        *_call("decode_string", "t_from", _p(4)),     # [from]
        *_call("decode_string", "t_to", _p(36)),      # [from, to]
        _p(68), CALLDATALOAD,                         # [from, to, num]
        *_call("slot_of", "t_src", DUP4),             # [.., num, s]
        DUP2, DUP2, SLOAD, SUB, SWAP1, SSTORE,        # _balance[from] -= num
        *_call("slot_of", "t_dst", DUP3),             # [.., num, s]
        DUP1, SLOAD, DUP3, ADD, SWAP1, SSTORE,        # _balance[to] += num
        STOP,

        # set(string name, uint256 num)
        _l("set"), POP,
        *_revert_if(_short(4 + 64)),
        *_call("decode_string", "s_name", _p(4)),     # [name]
        _p(36), CALLDATALOAD,                         # [name, num]
        *_call("slot_of", "s_slot", DUP3),            # [name, num, s]
        SSTORE, STOP,

        # balanceOf(string name) view returns (uint256)
        _l("balanceOf"), POP,
        *_revert_if(_short(4 + 32)),
        *_call("decode_string", "b_name", _p(4)),     # [name]
        *_call("slot_of", "b_slot", DUP2),            # [name, s]
        SLOAD, _p(0x40), MLOAD,                       # [name, bal, p]
        SWAP1, DUP2, MSTORE, _p(32), SWAP1, RETURN,

        # [ret, head] -> [ptr]: the string whose offset is the calldata
        # word at `head`, copied to fresh memory as (length, bytes)
        _l("decode_string"),
        *_revert_if([DUP1, _p(32), ADD, *_short()]),
        CALLDATALOAD,                                 # [ret, off]
        *_revert_if([DUP1, _p(_U64), LT]),            # off > 2**64-1
        _p(4), ADD,                                   # [ret, at]
        *_revert_if([DUP1, _p(32), ADD, *_short()]),
        DUP1, CALLDATALOAD,                           # [ret, at, len]
        *_revert_if([DUP1, _p(_U64), LT]),            # len > 2**64-1
        *_revert_if([DUP1, DUP3, ADD, _p(32), ADD, *_short()]),
        _p(0x40), MLOAD,                              # [ret, at, len, ptr]
        DUP2, DUP2, MSTORE,                           # mem[ptr] = len
        DUP2, DUP4, _p(32), ADD, DUP3, _p(32), ADD,   # copy the bytes
        CALLDATACOPY,
        DUP2, _p(31), ADD, _p(31), NOT, AND, DUP2, ADD, _p(32), ADD,
        _p(0x40), MSTORE,                             # bump the pointer
        SWAP2, POP, POP, SWAP1, JUMP,

        # [ret, ptr] -> [keccak256(bytes(name) ++ uint256(0))], packed
        # word by word at the free-memory pointer
        _l("slot_of"),
        _p(0x40), MLOAD, DUP2, MLOAD, _p(0),          # [ret, ptr, q, len, i]
        _l("copy"),
        DUP2, DUP2, LT, ISZERO, _r("copied"), JUMPI,
        DUP1, DUP5, ADD, _p(32), ADD, MLOAD,          # mload(ptr + 32 + i)
        DUP2, DUP5, ADD, MSTORE,                      # -> mem[q + i]
        _p(32), ADD, _r("copy"), JUMP,
        _l("copied"), POP,                            # [ret, ptr, q, len]
        _p(BALANCE_SLOT), DUP3, DUP3, ADD, MSTORE,    # mem[q + len] = slot
        _p(32), ADD, SWAP1, KECCAK256,                # [ret, ptr, h]
        SWAP1, POP, SWAP1, JUMP,
    ])


# -- calls and state ---------------------------------------------------------

def encode(method: str, *args, hash_fn) -> bytes:
    """The ABI-encoded call of one of the contract's functions."""
    return abi_mod.encode_call(SIGNATURES[method], list(args), hash_fn)


def slot_key(name: bytes, hash_fn) -> bytes:
    """`s_store`'s key of `_balance[name]`: address ++ keccak256(name ++
    uint256(0)), as the contract's SLOAD/SSTORE address it."""
    return ADDRESS + hash_fn(name + BALANCE_SLOT.to_bytes(32, "big"))


def deploy(storage, names: Iterable[bytes], balance: int, hash_fn) -> int:
    """What deployment, `enableParallel()` and one `set(name, balance)` a
    name leave in a storage: the code, the annotated ABI and the
    `s_store` rows, streamed in batches. -> rows of `_balance` written."""
    storage.set(T_CODE, ADDRESS, runtime_code(hash_fn))
    storage.set(TransactionExecutor.T_ABI, ADDRESS, ABI.encode())
    value = balance.to_bytes(32, "big")
    rows = ((slot_key(name, hash_fn), value) for name in names)
    n = 0
    while chunk := list(itertools.islice(rows, PREFUND_BATCH)):
        storage.set_batch(T_STORE, chunk)
        n += len(chunk)
    return n
