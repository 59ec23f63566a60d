"""Production-shaped scenario workloads (game-day + bench plane).

A synthetic `register` storm exercises the append-only happy path and
nothing else; production traffic is shaped — a large pre-funded account
space, skewed hot keys, one-to-many fanouts, cross-group legs, wide
rows. This module is the ONE definition of those shapes, shared by

  * `benchmark/chain_bench.py --scenario <name>` — open-loop Poisson
    arrivals against an in-process 4-node chain, intensity calibrated
    as a multiple of measured capacity (the overload plane's PR-12
    calibration discipline), and
  * `fisco_bcos_tpu/testing/gameday.py` — the same load against a REAL
    multi-node daemon cluster over JSON-RPC while faults fire.

Scenarios (single-group unless noted):

  mint-storm     register a fresh account per tx — pure key-append write
                 storm; state grows monotonically (flush/compaction
                 pressure at GB scale).
  airdrop-sweep  a handful of rich funders transfer to a fresh
                 destination per tx — one-to-many fanout; the funder
                 rows are write hot spots every block touches.
  hot-key        transfers from a LARGE pre-funded account space into a
                 tiny hot destination set (`hot_share` of arrivals) —
                 conflict-key contention, the DAG scheduler's worst
                 production shape.
  wide-table     KV-table writes with `value_bytes`-wide values over a
                 bounded re-written key space — update-heavy pages, the
                 key_page_size read/write-amplification shape.
  xshard-heavy   `cross_share` of arrivals are cross-group transferOut
                 legs (needs a multi-group runner; the rest are local
                 transfers from the account space).

Pre-funding: state roots cover each block's CHANGESET, not the whole
state, so identical `prefund_rows()` injected into every node's storage
before the first block is consensus-safe — that is how a bench run gets
a 100k+-account space without signing 100k txs. Against a live cluster
(game day) the space is funded through the chain with `prefund_fields()`
register txs instead, at a smaller `accounts` setting.
"""

from __future__ import annotations

import dataclasses
import itertools
import random
import time
from typing import Callable, Optional

from fisco_bcos_tpu.executor import precompiled as pc

ACCOUNT_BALANCE = 1_000_000
FUNDER_BALANCE = 1 << 56
PREFUND_BATCH = 1 << 16  # rows a storage.set_batch of the prefund

SCENARIOS = {
    "mint-storm": "fresh-account register storm (append-only state growth)",
    "airdrop-sweep": "few funders -> fresh destination per tx (fanout)",
    "hot-key": "large account space -> tiny hot destination set",
    "wide-table": "wide KV rows over a re-written key space (key pages)",
    "xshard-heavy": "cross-group transferOut share + local transfers",
}


@dataclasses.dataclass
class ScenarioSpec:
    name: str
    accounts: int = 100_000   # pre-funded uniform account space
    funders: int = 16         # rich sources (airdrop-sweep)
    hot_keys: int = 8         # hot destination set (hot-key)
    hot_share: float = 0.9    # arrivals hitting the hot set (hot-key)
    cross_share: float = 0.5  # cross-group arrivals (xshard-heavy)
    cross_dest: str = ""      # destination group of cross legs
    value_bytes: int = 2048   # row width (wide-table)
    wide_rows: int = 4096     # re-written key space (wide-table)
    seed: int = 17

    def __post_init__(self) -> None:
        if self.name not in SCENARIOS:
            raise ValueError(
                f"unknown scenario {self.name!r}; "
                f"choose from {sorted(SCENARIOS)}")


def _acct(spec: ScenarioSpec, i: int) -> bytes:
    return b"acct-%07d" % i


def _prefund_tables(spec: ScenarioSpec):
    """(table, rows) of `prefund_rows`, each table's rows an iterator in
    key order: 2,000,000 accounts are never one list."""
    bal = ACCOUNT_BALANCE.to_bytes(16, "big")
    fb = FUNDER_BALANCE.to_bytes(16, "big")
    accounts = spec.accounts if spec.name in ("hot-key", "xshard-heavy") \
        else 0
    funders = spec.funders if spec.name == "airdrop-sweep" else 0
    if accounts or funders:
        yield pc.T_BALANCE, itertools.chain(
            ((_acct(spec, i), bal) for i in range(accounts)),
            ((b"funder-%d" % i, fb) for i in range(funders)))
    if spec.name == "wide-table":
        yield pc.T_USER_PREFIX + "gd", iter([(b"\x00__meta__", b"kv")])


def prefund_rows(spec: ScenarioSpec) -> dict[str, list[tuple[bytes, bytes]]]:
    """table -> [(key, value)] rows that make the scenario's sources
    spendable, for DIRECT injection into every node's storage before the
    first block (bench path). Deterministic for a given spec."""
    return {table: list(rows) for table, rows in _prefund_tables(spec)}


def prefund_storage(storage, spec: ScenarioSpec) -> int:
    """Inject `prefund_rows` into one node's storage (call on EVERY node
    of an in-process chain, before load), streamed in batches: a page
    layer translates each batch once. Returns rows written."""
    n = 0
    for table, rows in _prefund_tables(spec):
        while chunk := list(itertools.islice(rows, PREFUND_BATCH)):
            storage.set_batch(table, chunk)
            n += len(chunk)
    return n


def prefund_fields(spec: ScenarioSpec) -> list[tuple[bytes, bytes, str]]:
    """(to, input, nonce) for funding THROUGH the chain (game-day path:
    a live cluster only takes state via committed blocks). Size
    `spec.accounts` for the cluster you have — these are real txs."""
    fields: list[tuple[bytes, bytes, str]] = []
    if spec.name == "airdrop-sweep":
        for i in range(spec.funders):
            data = pc.encode_call(
                "register", lambda w, i=i: w.blob(b"funder-%d" % i)
                .u64(FUNDER_BALANCE))
            fields.append((pc.BALANCE_ADDRESS, data, f"gdf-{i}"))
    if spec.name in ("hot-key", "xshard-heavy"):
        for i in range(spec.accounts):
            data = pc.encode_call(
                "register", lambda w, i=i: w.blob(_acct(spec, i))
                .u64(ACCOUNT_BALANCE))
            fields.append((pc.BALANCE_ADDRESS, data, f"gda-{i}"))
    if spec.name == "wide-table":
        data = pc.encode_call("createTable", lambda w: w.text("gd"))
        fields.append((pc.KV_TABLE_ADDRESS, data, "gdt-0"))
    return fields


def tx_fields(spec: ScenarioSpec, i: int) -> tuple[bytes, bytes, str]:
    """(to, input, nonce) of the scenario's i-th arrival. Deterministic:
    per-tx rng seeded on (spec.seed, i), so chunked parallel signing and
    re-generation agree."""
    rng = random.Random((spec.seed << 32) | i)
    name = spec.name
    if name == "mint-storm":
        data = pc.encode_call(
            "register", lambda w: w.blob(b"mint-%d-%d" % (spec.seed, i))
            .u64(1))
        return pc.BALANCE_ADDRESS, data, f"gdm-{i}"
    if name == "airdrop-sweep":
        src = b"funder-%d" % (i % spec.funders)
        dst = b"drop-%d-%d" % (spec.seed, i)
        data = pc.encode_call(
            "transfer", lambda w: w.blob(src).blob(dst).u64(1))
        return pc.BALANCE_ADDRESS, data, f"gds-{i}"
    if name == "hot-key":
        src = _acct(spec, rng.randrange(spec.accounts))
        if rng.random() < spec.hot_share:
            dst = b"hot-%d" % rng.randrange(spec.hot_keys)
        else:
            dst = _acct(spec, rng.randrange(spec.accounts))
        data = pc.encode_call(
            "transfer", lambda w: w.blob(src).blob(dst).u64(1))
        return pc.BALANCE_ADDRESS, data, f"gdh-{i}"
    if name == "wide-table":
        key = b"row-%06d" % rng.randrange(spec.wide_rows)
        val = rng.getrandbits(8 * spec.value_bytes).to_bytes(
            spec.value_bytes, "big")
        data = pc.encode_call(
            "set", lambda w: w.text("gd").blob(key).blob(val))
        return pc.KV_TABLE_ADDRESS, data, f"gdw-{i}"
    # xshard-heavy
    if rng.random() < spec.cross_share and spec.cross_dest:
        data = pc.encode_call(
            "transferOut",
            lambda w: w.blob(b"gdx-%d-%d" % (spec.seed, i))
            .text(spec.cross_dest).blob(_acct(spec, 0))
            .blob(b"xacct-%d" % i).u64(1))
        return pc.XSHARD_ADDRESS, data, f"gdx-{i}"
    src = _acct(spec, rng.randrange(1, spec.accounts))
    data = pc.encode_call(
        "transfer", lambda w: w.blob(src).blob(b"xl-%d" % i).u64(1))
    return pc.BALANCE_ADDRESS, data, f"gdl-{i}"


# -- signing (parallel across cores, picklable worker) -----------------------

_SIGN_CHUNK = 250


def _sign_chunk(args) -> list[bytes]:
    (spec_kw, sm, start, count, block_limit, group_id, prefund) = args
    from fisco_bcos_tpu.crypto.suite import make_suite
    from fisco_bcos_tpu.protocol import Transaction

    spec = ScenarioSpec(**spec_kw)
    suite = make_suite(sm, backend="host")
    kp = suite.generate_keypair(b"gameday-client")
    fields = prefund_fields(spec)[start:start + count] if prefund else \
        [tx_fields(spec, i) for i in range(start, start + count)]
    return [Transaction(to=to, input=data, group_id=group_id, nonce=nonce,
                        block_limit=block_limit).sign(suite, kp).encode()
            for to, data, nonce in fields]


def sign_workload(spec: ScenarioSpec, sm: bool, n: int, block_limit: int,
                  group_id: str = "group0", start: int = 0,
                  prefund: bool = False) -> list[bytes]:
    """n pre-signed wire txs of the scenario (or its prefund set when
    `prefund`), chunk-parallel across cores like chain_bench's builder."""
    import multiprocessing
    import os
    from concurrent.futures import ProcessPoolExecutor

    spec_kw = dataclasses.asdict(spec)
    chunks = [(spec_kw, sm, s, min(_SIGN_CHUNK, start + n - s),
               block_limit, group_id, prefund)
              for s in range(start, start + n, _SIGN_CHUNK)]
    workers = os.cpu_count() or 1
    if workers == 1 or len(chunks) == 1:
        return [tx for ch in map(_sign_chunk, chunks) for tx in ch]
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(workers, mp_context=ctx) as ex:
        return [tx for ch in ex.map(_sign_chunk, chunks) for tx in ch]


# -- open-loop Poisson driver ------------------------------------------------

def open_loop_poisson(submit: Callable[[list], int], txs: list,
                      rate: float, window_s: float, seed: int = 17,
                      batch_cap: int = 256,
                      on_sample: Optional[Callable[[int, float], None]]
                      = None, sample_every: int = 16,
                      stop: Optional[Callable[[], bool]] = None) -> dict:
    """Open-loop arrivals: exponential inter-arrival gaps at mean `rate`
    per second; arrivals due NOW are submitted in one batch (capped) and
    are never withheld because earlier ones were slow — that is what
    open-loop means, and it is exactly the shape that exposes a node
    that cannot shed. `submit(batch)` returns how many were ADMITTED;
    it may be an in-process submit_batch or an RPC fanout, and may raise
    on transport faults (counted, not fatal — game days kill nodes
    mid-window). `on_sample(index, t_submit)` fires for every
    `sample_every`-th ADMITTED tx so the caller can track commit
    latency without polling every receipt."""
    rng = random.Random(seed)
    counts = {"offered": 0, "admitted": 0, "shed": 0,
              "submit_errors": 0}
    t0 = time.perf_counter()
    deadline = t0 + window_s
    next_due = t0 + rng.expovariate(rate)
    i = 0
    while time.perf_counter() < deadline and i < len(txs):
        if stop is not None and stop():
            break
        now = time.perf_counter()
        due = 0
        while next_due <= now and due < batch_cap:
            due += 1
            next_due += rng.expovariate(rate)
        if due == 0:
            time.sleep(min(0.002, max(0.0, next_due - now)))
            continue
        batch = txs[i:i + due]
        t_sub = time.perf_counter()
        try:
            admitted = submit(batch)
        except Exception:  # noqa: BLE001 — the cluster is under fault
            counts["submit_errors"] += 1
            admitted = 0
        counts["offered"] += len(batch)
        counts["admitted"] += admitted
        counts["shed"] += len(batch) - admitted
        if on_sample is not None and admitted:
            for k in range(i, i + admitted, sample_every):
                on_sample(k, t_sub)
        i += len(batch)
    wall = time.perf_counter() - t0
    counts["wall_seconds"] = round(wall, 3)
    counts["offered_tps"] = round(counts["offered"] / wall, 1)
    counts["shed_rate"] = round(
        counts["shed"] / max(1, counts["offered"]), 4)
    return counts
