"""Verifiable proof serving — render once at commit, verify N as one call.

Three jobs:

  * `render_block_proofs` — at commit time (riding the PR-5 QueryCache
    prime path, off the consensus thread) build the block's tx and
    receipt Merkle levels ONCE, from the leaf hashes the commit holds,
    and render every transaction's full `getProof` response, so
    steady-state proof hits cost zero tree walks and zero hashing.
  * `verify_inclusion_batch` — check N width-16 ledger proofs (tx,
    receipt, state-changeset) with ONE batched hash call: every level's
    node group is known up front, so the hashes are independent and the
    chain linkage (sibling-slot equality level to level, last digest ==
    root) is pure host comparison. This is the `verifyProofs` RPC body
    and the light client's per-span verification.
  * `ZkPlane` — the node-attached counter surface behind `bcos_zk_*`
    metrics and the `getSystemStatus` "zk" section.

Trust model (README "ZK proof plane"): txsRoot/receiptsRoot proofs bind
to quorum-sealed headers — full light-client strength. State proofs bind
to `state_root`, which is the root of the block's OWN changeset (PR-4
caveat: deliberately not cumulative), so a state proof shows "this block
wrote key K := V", not "K = V now".
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from ..analysis import lockcheck as lc
from ..ops import merkle as m
from ..utils.log import LOG, badge

# width-16 proof level: (siblings[WIDTH], position) — ops.merkle shape


def _hex(b: bytes) -> str:
    return "0x" + b.hex()


def _unhex(s: str) -> bytes:
    return bytes.fromhex(s[2:] if s.startswith("0x") else s)


def w16_proof_json(proof) -> list[dict]:
    return [{"siblings": [_hex(s) for s in sibs], "index": pos}
            for sibs, pos in proof]


def w16_proof_from_json(doc: Sequence[dict]) -> list:
    return [([_unhex(s) for s in lvl["siblings"]], int(lvl["index"]))
            for lvl in doc]


def verify_inclusion_batch(suite, items: Sequence[tuple]) -> np.ndarray:
    """-> bool[N] for items of (leaf, w16_proof, root).

    One `suite.hash_batch` over every item's every level node (the call
    that rides the crypto lane), then host-side linkage: leaf sits in its
    claimed sibling slot, each level's digest fills the next level's
    slot, the final digest equals the root. An empty proof asserts
    leaf == root (single-leaf tree)."""
    nodes: list[bytes] = []
    for _leaf, proof, _root in items:
        for sibs, _pos in proof:
            nodes.append(b"".join(sibs))
    digests = list(suite.hash_batch(nodes)) if nodes else []
    ok = np.zeros(len(items), bool)
    off = 0
    for i, (leaf, proof, root) in enumerate(items):
        cur = leaf
        good = True
        for sibs, pos in proof:
            if not (0 <= pos < len(sibs)) or sibs[pos] != cur:
                good = False
            cur = digests[off]
            off += 1
        ok[i] = good and cur == root
    return ok


# -- commit-time rendering ---------------------------------------------------

def _hexed_rows(levels: list[list[bytes]]) -> list[list[list[str]]]:
    """Every proof row of a tree, hexed once: level -> group -> the
    WIDTH sibling digests as JSON strings. The leaves under one parent
    share its row (the list itself: proof documents are frozen)."""
    return [[[_hex(s) for s in m.sibling_group(level, g)]
             for g in range(-(-len(level) // m.WIDTH))]
            for level in levels[:-1]]


def _proof_from_rows(rows: list[list[list[str]]], index: int) -> list[dict]:
    proof = []
    for groups in rows:
        group = index // m.WIDTH
        proof.append({"siblings": groups[group], "index": index % m.WIDTH})
        index = group
    return proof


def proof_doc_size(doc: dict) -> int:
    """Cache footprint of a proof document, reckoned rather than dumped:
    a row is WIDTH quoted 0x-digests, the envelope four more and change."""
    rows = len(doc["txProof"]) + len(doc["receiptProof"])
    return 320 + rows * (m.WIDTH * (2 * m.DIGEST + 5) + 28)


def render_block_proofs(number: int, header, tx_hashes: Sequence[bytes],
                        receipt_hashes: Sequence[bytes], alg: str) -> list:
    """Every tx's `getProof` document for a committed block, out of the
    leaf hashes the commit already holds: each tree's levels built once,
    each sibling row hexed once. -> [(cache key, document, size)], for the
    caller's one cache transaction. Same documents as `render_proof_doc`."""
    if not tx_hashes:
        return []
    tx_rows = _hexed_rows(m.merkle_levels_host(list(tx_hashes), alg))
    rc_rows = _hexed_rows(m.merkle_levels_host(list(receipt_hashes), alg))
    txs_root, receipts_root = _hex(header.txs_root), \
        _hex(header.receipts_root)
    out = []
    for i, h in enumerate(tx_hashes):
        doc = {
            "blockNumber": number,
            "txHash": _hex(h),
            "txsRoot": txs_root,
            "txProof": _proof_from_rows(tx_rows, i),
            "receiptsRoot": receipts_root,
            "receiptProof": _proof_from_rows(rc_rows, i),
        }
        out.append((("proof", h), doc, proof_doc_size(doc)))
    return out


def render_proof_doc(ledger, tx_hash: bytes) -> Optional[dict]:
    """Cold-path (cache miss) render of one tx's proof document — the
    per-request tree walk the commit-time prime exists to avoid."""
    rc = ledger.receipt(tx_hash)
    if rc is None:
        return None
    tp = ledger.tx_proof(tx_hash)
    rp = ledger.receipt_proof(tx_hash)
    if tp is None or rp is None:
        return None  # body rows raced a prune sweep mid-request
    return {
        "blockNumber": rc.block_number,
        "txHash": _hex(tx_hash),
        "txsRoot": _hex(tp[1]),
        "txProof": w16_proof_json(tp[0]),
        "receiptsRoot": _hex(rp[1]),
        "receiptProof": w16_proof_json(rp[0]),
    }


# -- node-attached counters (bcos_zk_* / getSystemStatus) --------------------

class ZkPlane:
    """Per-node ZK proof-plane bookkeeping: commit-time render counts,
    proof cache hit rate, batched-verify volume. Group-labeled via the
    node's metrics view."""

    def __init__(self, node):
        self.node = node
        self._reg = node.metrics_view
        self._lock = lc.make_lock("zk.plane")
        self._rendered = 0
        self._hits = 0
        self._misses = 0
        self._verified = 0
        self._verify_calls = 0

    def block_proofs(self, block) -> list:
        """The committed `block`'s proof documents as cache entries
        (`render_block_proofs`); [] where rendering them failed."""
        suite = self.node.suite
        try:
            # one batch where the block came from the ledger; a commit's
            # own receipts carry their hashes and this hashes nothing
            from ..protocol import prefill_hashes
            prefill_hashes(block.receipts, lambda rc: rc.encode(), suite)
            entries = render_block_proofs(
                block.header.number, block.header, block.tx_hashes,
                [rc.hash(suite) for rc in block.receipts], suite.hash_name)
        except Exception:  # noqa: BLE001 — priming is best-effort
            LOG.exception(badge("ZK", "proof-prime-failed",
                                number=block.header.number))
            return []
        if entries:
            with self._lock:
                self._rendered += len(entries)
            self._reg.inc("bcos_zk_proofs_rendered_total", len(entries))
        return entries

    def note_proof(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self._hits += 1
            else:
                self._misses += 1
        self._reg.inc("bcos_zk_proof_cache_hits_total" if hit
                      else "bcos_zk_proof_cache_misses_total")

    def note_verified(self, n: int, ok: int) -> None:
        with self._lock:
            self._verified += n
            self._verify_calls += 1
        self._reg.inc("bcos_zk_proofs_verified_total", n)
        self._reg.inc("bcos_zk_verify_calls_total")
        self._reg.observe("bcos_zk_verify_batch_size", n,
                          buckets=(1, 8, 64, 512, 4096, 16384, 65536))

    def stats(self) -> dict:
        with self._lock:
            total = self._hits + self._misses
            return {
                "proofsRendered": self._rendered,
                "proofHits": self._hits,
                "proofMisses": self._misses,
                "proofHitRate": round(self._hits / total, 4)
                if total else 0.0,
                "proofsVerified": self._verified,
                "verifyCalls": self._verify_calls,
            }
