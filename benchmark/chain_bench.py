#!/usr/bin/env python3
"""End-to-end chain TPS benchmark — BASELINE.json configs 4-5.

Drives a LIVE 4-node PBFT chain (in-process transport, real engines/
txpool/scheduler/ledger — the reference's 4-node Air chain shape,
tools/BcosAirBuilder/build_chain.sh + docs/README_EN.md:11 "20k TPS") and
reports:

  * end-to-end TPS (committed txs / wall time from first submit),
  * mean block interval and blocks committed,
  * block-verify p50/p95 — the txpool verify_proposal latency per proposal
    (BASELINE config 4's "block-verify p50" for large mixed blocks).

Suites: --suite ecdsa | sm | both (config 4's "mixed secp256k1+SM2" is two
chains, one per suite — a FISCO chain is single-suite by genesis).

Host-side signing of the workload is NOT the benchmark; it is parallelised
across processes and excluded from the timed window.

Concurrent-ingest mode (--rpc-clients N): the same 4-node chain serving N
independent HTTP JSON-RPC clients through the continuous-batching ingest
lane (txpool/ingest.py). Reports `rpc_ingest_tps`, the lane's mean batch
size, and verify (recover) calls per submitted tx on the ingress node —
the amortization the lane exists to buy. --rpc-compare additionally runs
the per-request baseline (lane disabled) and a single-client run, so the
coalescing win is measured against both anchors in one invocation.

Sync-bench mode (--sync-bench): a joining node's catch-up time, measured
both ways against the same source chain — full block-by-block replay vs
snap-sync (snapshot/ subsystem: one manifest + chunked state install, tail
replay only). Reports `replay_blocks_per_sec` and `snap_sync_seconds`
rows picked up by bench.py; the speedup is the O(chain length) ->
O(state size) win the checkpoint subsystem exists to buy.

Usage: python benchmark/chain_bench.py [-n 2000] [--backend auto|host]
       [--suite ecdsa|sm|both] [--tx-count-limit 1000]
       python benchmark/chain_bench.py --rpc-clients 8 [--rpc-compare]
       python benchmark/chain_bench.py --sync-bench [--sync-blocks 40]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_SIGN_CHUNK = 250

# host-weather stamping (analysis/hostweather.py): every emitted bench row
# carries the PSI/steal/spin-score stamp it was measured under, so the
# perf gate (tools/perf_gate.py) can widen its bands on a noisy host and
# the documented 1.45-1.6x run-to-run swings become explainable. Sampled
# once per emission wave (a ~50 ms spin probe must not run between timed
# windows more than it has to) and refreshed if older than 60 s.
_WEATHER: dict | None = None
_WEATHER_AT = 0.0


def _weather() -> dict:
    global _WEATHER, _WEATHER_AT
    now = time.monotonic()
    if _WEATHER is None or now - _WEATHER_AT > 60.0:
        from fisco_bcos_tpu.analysis import hostweather
        _WEATHER = hostweather.sample()
        _WEATHER_AT = now
    return _WEATHER


def _dumps(row) -> str:
    """json.dumps for bench rows, stamping host weather on each."""
    if isinstance(row, dict) and "metric" in row:
        row.setdefault("host_weather", _weather())
    return json.dumps(row)


def _sign_chunk(args) -> list[bytes]:
    """Worker: sign a chunk of register txs (picklable, re-imports)."""
    sm, seed, start, count, block_limit, group_id, cross = args[:7]
    prefix = args[7] if len(args) > 7 else "cb"
    from fisco_bcos_tpu.crypto.suite import make_suite
    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.protocol import Transaction

    suite = make_suite(sm, backend="host")
    kp = suite.generate_keypair(seed)
    out = []
    for i in range(start, start + count):
        if cross:
            # cross-shard leg: move 1 unit from this group's pre-funded
            # escrow account to an account on the destination group
            # (cross = destination group id)
            data = pc.encode_call(
                "transferOut",
                lambda w, i=i: w.blob(b"xs-%s-%d" % (group_id.encode(), i))
                .text(cross).blob(b"funder").blob(b"xacct%d" % i).u64(1))
            to = pc.XSHARD_ADDRESS
        else:
            data = pc.encode_call(
                "register",
                lambda w, i=i: w.blob(b"acct%d" % i).u64(1))
            to = pc.BALANCE_ADDRESS
        tx = Transaction(
            to=to, input=data, group_id=group_id,
            nonce=f"{prefix}-{'x' if cross else ''}{i}",
            block_limit=block_limit,
        ).sign(suite, kp)
        out.append(tx.encode())
    return out


def _build_workload(sm: bool, n: int, block_limit: int,
                    group_id: str = "group0",
                    cross: str = "", start: int = 0,
                    prefix: str = "cb") -> list[bytes]:
    from concurrent.futures import ProcessPoolExecutor
    import multiprocessing

    chunks = [(sm, b"chain-bench", s, min(_SIGN_CHUNK, start + n - s),
               block_limit, group_id, cross, prefix)
              for s in range(start, start + n, _SIGN_CHUNK)]
    workers = os.cpu_count() or 1
    if workers == 1 or len(chunks) == 1:
        return [tx for ch in map(_sign_chunk, chunks) for tx in ch]
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(workers, mp_context=ctx) as ex:
        return [tx for ch in ex.map(_sign_chunk, chunks) for tx in ch]


def _build_chain(sm: bool, backend: str, tx_count_limit: int,
                 transport: str = "fake", tls: bool = False,
                 rpc_on_first: bool = False, ingest_lane: bool = True,
                 min_seal_time: float = 0.0, max_wait_ms: float = 15.0,
                 pipeline: bool = True, cfg_overrides: dict | None = None):
    """4-node PBFT chain -> (nodes, gateways, tls_effective)."""
    from fisco_bcos_tpu.crypto.suite import make_suite
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.ledger.ledger import ConsensusNode
    from fisco_bcos_tpu.net.gateway import FakeGateway

    suite = make_suite(sm, backend="host")  # node identity keys
    keypairs = [suite.generate_keypair(bytes([i + 1]) * 16)
                for i in range(4)]
    if transport == "p2p":
        # real TCP sessions on localhost (net/p2p.py: framed wire protocol,
        # compression negotiation, router) — the BASELINE deployment shape.
        # --tls adds the dual-cert SM-TLS channel (the build_chain --sm-tls
        # deployment shape), so its overhead is quantified against plain TCP
        ctxs = [None] * 4
        if tls:
            from fisco_bcos_tpu.net.smtls import (CertificateAuthority,
                                                  SMTLSContext)
            ca = CertificateAuthority(name="bench-ca")
            ctxs = [SMTLSContext(ca.pub, ca.issue(f"bench-node{i}"))
                    for i in range(4)]
        from fisco_bcos_tpu.net.p2p import P2PGateway

        gateways = [P2PGateway(kp.pub_bytes, server_ssl=ctx, client_ssl=ctx)
                    for kp, ctx in zip(keypairs, ctxs)]
        for i, gw in enumerate(gateways):
            for j, other in enumerate(gateways):
                if i != j:
                    gw.add_peer(other.host, other.port)
    else:
        tls = False  # in-process bus: no transport to encrypt
        shared = FakeGateway()
        gateways = [shared] * 4
    sealers = [ConsensusNode(kp.pub_bytes) for kp in keypairs]
    nodes = []
    for i, (kp, gw) in enumerate(zip(keypairs, gateways)):
        kw = dict(consensus="pbft", sm_crypto=sm,
                  crypto_backend=backend,
                  min_seal_time=min_seal_time,
                  view_timeout=30.0,
                  tx_count_limit=tx_count_limit,
                  ingest_lane=ingest_lane,
                  ingest_max_wait_ms=max_wait_ms,
                  pipeline_commit=pipeline,
                  # benches measure the untraced hot path;
                  # --trace-profile reconfigures explicitly
                  trace_sample_rate=0.0, trace_slow_ms=0.0,
                  rpc_port=0 if rpc_on_first and i == 0 else None)
        kw.update(cfg_overrides or {})
        if kw.get("storage_path"):
            # a shared override names the chain's base dir; each node
            # gets its own subdirectory (real deployments never share)
            kw["storage_path"] = os.path.join(kw["storage_path"],
                                              f"node{i}")
        node = Node(NodeConfig(**kw), keypair=kp, gateway=gw)
        node.build_genesis(sealers)
        nodes.append(node)
    return nodes, gateways, tls


def run_chain(sm: bool, n: int, backend: str, tx_count_limit: int,
              transport: str = "fake", tls: bool = False,
              pipeline: bool = True, profile: bool = False,
              workers: int = 0) -> dict:
    from fisco_bcos_tpu.protocol import Transaction

    nodes, gateways, tls = _build_chain(
        sm, backend, tx_count_limit, transport, tls, pipeline=pipeline,
        cfg_overrides={"scheduler_workers": workers} if workers else None)
    gateway = gateways[0]

    # instrument proposal verification latency on every node
    verify_times: list[float] = []
    for node in nodes:
        orig = node.txpool.verify_proposal

        def timed(block, _orig=orig):
            t0 = time.perf_counter()
            ok = _orig(block)
            verify_times.append(time.perf_counter() - t0)
            return ok

        node.txpool.verify_proposal = timed

    print(f"signing {n} txs (excluded from the timed window)...",
          file=sys.stderr, flush=True)
    # block_limit must satisfy current < limit <= current + range (default
    # range 600, chain starts at 0) AND outlive every block this run needs,
    # or txs expire at seal time and the bench stalls to its deadline
    blocks_needed = -(-n // max(1, tx_count_limit))
    block_limit = min(600, max(100, 2 * blocks_needed + 20))
    if blocks_needed > 550:
        raise SystemExit(
            f"n/tx_count_limit needs ~{blocks_needed} blocks, beyond the "
            f"600-block tx lifetime; raise --tx-count-limit")
    wire_txs = _build_workload(sm, n, block_limit=block_limit)

    commit_times: dict[int, float] = {}
    orig_commit = nodes[0].scheduler.commit_block

    def commit_hook(header, _orig=orig_commit):
        ok = _orig(header)
        if ok:
            commit_times[header.number] = time.perf_counter()
        return ok

    nodes[0].scheduler.commit_block = commit_hook

    for node in nodes:
        node.start()
    try:
        # submit in wire-realistic gossip batches round-robin across nodes
        # (TransactionSync.cpp:516 imports downloaded txs in batches); the
        # batch path is what the TPU batch-recover accelerates
        t0 = time.perf_counter()
        chunk = 512
        for i, s in enumerate(range(0, len(wire_txs), chunk)):
            txs = [Transaction.decode(raw) for raw in wire_txs[s:s + chunk]]
            results = nodes[i % 4].txpool.submit_batch(txs)
            if i == 0 and int(results[0].status) != 0:
                raise RuntimeError(
                    f"first submit rejected: {results[0].status}")
        t_submitted = time.perf_counter()
        deadline = time.monotonic() + max(120.0, n / 50)
        want = nodes[0].ledger  # all nodes advance in lockstep
        while time.monotonic() < deadline:
            total = want.total_tx_count()
            if total >= n:
                break
            time.sleep(0.05)
        t_end = time.perf_counter()
        committed = want.total_tx_count()
        height = want.current_number()
        # the ingress node's per-stage occupancy (fill/execute/roots/
        # consensus_wait/commit seconds) — collected before stop so the
        # numbers cover exactly the timed window's blocks
        pstats = nodes[0].scheduler.pipeline_stats() if profile else None
        # out-of-process execution pools: per-node stats collected before
        # stop so occupancy covers exactly the timed window
        wstats = ([nd.exec_pool.stats() for nd in nodes]
                  if workers and nodes[0].exec_pool is not None else None)
    finally:
        for node in nodes:
            node.stop()
        for gw in set(gateways):
            gw.stop()

    intervals = []
    ordered = [commit_times[k] for k in sorted(commit_times)]
    intervals = [b - a for a, b in zip(ordered, ordered[1:])]
    vt = sorted(verify_times)

    def pct(p):
        return vt[min(len(vt) - 1, int(p * len(vt)))] if vt else 0.0

    row = {
        "suite": "sm" if sm else "ecdsa",
        "transport": transport,
        "tls": bool(tls),
        "pipeline": bool(pipeline),
        "txs_committed": int(committed),
        "blocks": int(height),
        "tps": round(committed / (t_end - t0), 1) if t_end > t0 else 0.0,
        "submit_seconds": round(t_submitted - t0, 3),
        "wall_seconds": round(t_end - t0, 3),
        "block_interval_mean_ms": round(
            statistics.mean(intervals) * 1000, 1) if intervals else None,
        "block_verify_p50_ms": round(pct(0.50) * 1000, 2),
        "block_verify_p95_ms": round(pct(0.95) * 1000, 2),
    }
    if pstats is not None:
        row["pipeline_stats"] = pstats
    if wstats is not None:
        row["exec_worker_stats"] = wstats
    return row


def run_rpc_ingest(sm: bool, n: int, backend: str, tx_count_limit: int,
                   clients: int, ingest_lane: bool = True,
                   max_wait_ms: float = 100.0,
                   pipeline: bool = True) -> dict:
    """N independent HTTP JSON-RPC clients against a live 4-node chain.

    Measures the serving-stack amortization the ingest lane buys: each
    client posts its share of pre-signed txs one request at a time (the
    millions-of-independent-clients shape, not batch submission), and the
    ingress node's suite is instrumented to count recover calls — with
    the lane ON, concurrent requests coalesce into shared verify batches;
    with it OFF (--rpc-compare baseline) every request pays a batch of 1.
    """
    import threading

    from fisco_bcos_tpu.sdk.client import SdkClient

    # min_seal_time 0.2 s: the serving shape must not seal a (costly on a
    # 2-core host) PBFT round per trickling tx — the reference's default
    # is 500 ms for the same reason. max_wait_ms 100 (vs the 15 ms node
    # default): on a host where the request round trip is itself >100 ms,
    # a wider coalescing ceiling is the documented latency/throughput
    # knob — admission latency stays far below commit latency either way.
    nodes, gateways, _ = _build_chain(sm, backend, tx_count_limit,
                                      rpc_on_first=True,
                                      ingest_lane=ingest_lane,
                                      min_seal_time=0.2,
                                      max_wait_ms=max_wait_ms,
                                      pipeline=pipeline)
    ingress = nodes[0]
    # instrument the ingress node's recover entry point (instance-attr
    # shadow): every signature verification on node 0 crosses it
    recover_stats = {"calls": 0, "sigs": 0}
    orig_recover = ingress.suite.recover_addresses

    def counted(hashes, sigs, _orig=orig_recover):
        recover_stats["calls"] += 1
        recover_stats["sigs"] += len(hashes)
        return _orig(hashes, sigs)

    ingress.suite.recover_addresses = counted

    print(f"signing {n} txs (excluded from the timed window)...",
          file=sys.stderr, flush=True)
    # full 600-block tx lifetime: serving-mode blocks are TIME-sealed
    # (min_seal 0.2 s), so a trickling client can commit far more blocks
    # than n/tx_count_limit — a tighter limit expires the tail of the
    # workload mid-run (BLOCK_LIMIT_CHECK_FAIL)
    wire_txs = ["0x" + raw.hex()
                for raw in _build_workload(sm, n, block_limit=600)]
    shares = [wire_txs[c::clients] for c in range(clients)]

    for node in nodes:
        node.start()
    try:
        url = f"http://{ingress.rpc.host}:{ingress.rpc.port}"
        errors: list[str] = []
        barrier = threading.Barrier(clients + 1)

        def client(share):
            sdk = SdkClient(url)
            barrier.wait()
            for tx_hex in share:
                try:
                    # wait=False: admission result only — throughput mode;
                    # the request still blocks until ITS batch dispatched
                    sdk.request("sendTransaction",
                                ["group0", "", tx_hex, False, False])
                except Exception as exc:  # noqa: BLE001 — report, don't die
                    errors.append(str(exc))
                    return

        threads = [threading.Thread(target=client, args=(s,), daemon=True)
                   for s in shares]
        for th in threads:
            th.start()
        barrier.wait()
        t0 = time.perf_counter()
        for th in threads:
            th.join()
        t_submitted = time.perf_counter()
        if errors:
            raise RuntimeError(f"rpc client failed: {errors[0]}")
        ledger = nodes[0].ledger
        deadline = time.monotonic() + max(120.0, n / 25)
        while time.monotonic() < deadline:
            if ledger.total_tx_count() >= n:
                break
            time.sleep(0.05)
        t_end = time.perf_counter()
        committed = ledger.total_tx_count()
        lane_stats = ingress.ingest.stats() if ingress.ingest else {}
    finally:
        for node in nodes:
            node.stop()
        for gw in set(gateways):
            gw.stop()

    return {
        "suite": "sm" if sm else "ecdsa",
        "clients": clients,
        "ingest_lane": bool(ingest_lane),
        "pipeline": bool(pipeline),
        "max_wait_ms": max_wait_ms,
        # a wedged chain must not masquerade as a slow one: consumers
        # (bench.py, sanitize_ci) check this before trusting tps
        "timed_out": int(committed) < n,
        "txs_committed": int(committed),
        "tps": round(committed / (t_end - t0), 1) if t_end > t0 else 0.0,
        "submit_tps": round(n / (t_submitted - t0), 1)
        if t_submitted > t0 else 0.0,
        "wall_seconds": round(t_end - t0, 3),
        "mean_batch": lane_stats.get("mean_batch", 1.0),
        "recover_calls": recover_stats["calls"],
        "recover_calls_per_tx": round(recover_stats["calls"] / n, 4),
    }


def run_rpc_read(sm: bool, backend: str, clients: int, n_requests: int,
                 blocks: int = 8, txs_per_block: int = 100,
                 cache: bool = True, keepalive: bool = True) -> dict:
    """Read-plane throughput: N keep-alive HTTP clients, mixed workload.

    A solo chain commits `blocks` full blocks, then `clients` independent
    JSON-RPC clients hammer a serving-shaped read mix — getBlockByNumber
    with txs (the sender-recovery-heavy call), getTransactionReceipt,
    `call` (balance read), and header-only getBlockByNumber — over
    persistent connections. Reports `rpc_read_qps`, request p50/p99, the
    query-cache hit rate, and recover calls during the read window (the
    per-request tax the commit-coherent cache exists to delete).
    `cache=False, keepalive=False` is the per-request baseline
    (--read-compare): fresh TCP connection + full re-render + a recover
    batch per getBlock, the shape of the old ThreadingHTTPServer edge.
    """
    import threading

    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.sdk.client import SdkClient

    node = Node(NodeConfig(consensus="solo", sm_crypto=sm,
                           crypto_backend=backend, min_seal_time=0.0,
                           tx_count_limit=txs_per_block, rpc_port=0,
                           rpc_cache_entries=4096 if cache else 0))
    node.build_genesis()
    n_txs = blocks * txs_per_block
    print(f"read-bench: building a {blocks}-block chain ({n_txs} txs)...",
          file=sys.stderr, flush=True)
    wire_txs = _build_workload(sm, n_txs, block_limit=min(
        600, 2 * blocks + 50))
    node.start()
    try:
        for s in range(0, n_txs, 256):
            node.txpool.submit_batch(
                [Transaction.decode(raw) for raw in wire_txs[s:s + 256]])
        deadline = time.monotonic() + max(120.0, n_txs / 20)
        while time.monotonic() < deadline:
            if node.ledger.total_tx_count() >= n_txs:
                break
            time.sleep(0.05)
        if node.ledger.total_tx_count() < n_txs:
            raise RuntimeError(
                f"read-bench chain wedged at {node.ledger.total_tx_count()}"
                f"/{n_txs} txs")
        head = node.ledger.current_number()
        # hot set: the last 8 committed blocks and their txs (polling-
        # client shape — receipts/blocks near the head dominate)
        hot_blocks = list(range(max(1, head - 7), head + 1))
        hot_txs = ["0x" + h.hex() for n in hot_blocks
                   for h in node.ledger.tx_hashes_by_number(n)]
        from fisco_bcos_tpu.executor import precompiled as pc
        call_to = "0x" + pc.BALANCE_ADDRESS.hex()
        call_data = "0x" + pc.encode_call(
            "balanceOf", lambda w: w.blob(b"acct0")).hex()

        # instrument the recover entry point for the READ window only
        recover_stats = {"calls": 0}
        orig_recover = node.suite.recover_addresses

        def counted(hashes, sigs, _orig=orig_recover):
            recover_stats["calls"] += 1
            return _orig(hashes, sigs)

        url = f"http://{node.rpc.host}:{node.rpc.port}"
        per_client = n_requests // clients
        latencies: list[list[float]] = [[] for _ in range(clients)]
        errors: list[str] = []
        barrier = threading.Barrier(clients + 1)

        def client(c):
            sdk = SdkClient(url, keepalive=keepalive)
            lat = latencies[c]
            barrier.wait()
            for i in range(per_client):
                j = c * per_client + i
                try:
                    t0 = time.perf_counter()
                    # 4:2:1:1 getBlock-with-txs : receipt : call : header —
                    # explorer/SDK read traffic is block-fetch dominated,
                    # and getBlock-with-txs is where the per-request
                    # recover tax lived
                    op = j % 8
                    if op < 4:
                        sdk.get_block_by_number(hot_blocks[j % len(hot_blocks)])
                    elif op < 6:
                        sdk.get_transaction_receipt(hot_txs[j % len(hot_txs)])
                    elif op == 6:
                        sdk.request("call", ["group0", "", call_to,
                                             call_data])
                    else:
                        sdk.get_block_by_number(
                            hot_blocks[j % len(hot_blocks)],
                            only_header=True)
                    lat.append(time.perf_counter() - t0)
                except Exception as exc:  # noqa: BLE001 — report, don't die
                    errors.append(f"{type(exc).__name__}: {exc}")
                    return

        node.suite.recover_addresses = counted
        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(clients)]
        for th in threads:
            th.start()
        barrier.wait()
        t0 = time.perf_counter()
        for th in threads:
            th.join(600)
        wall = time.perf_counter() - t0
        if any(th.is_alive() for th in threads):
            # a wedged client would otherwise yield a plausible-looking
            # but wrong QPS row (and race the instrumented suite restore)
            raise RuntimeError("read client wedged past the join timeout")
        node.suite.recover_addresses = orig_recover
        if errors:
            raise RuntimeError(f"read client failed: {errors[0]}")
        flat = sorted(x for ls in latencies for x in ls)
        done = len(flat)

        def pct(p):
            return flat[min(done - 1, int(p * done))] if flat else 0.0

        cache_stats = node.query_cache.stats() if node.query_cache else {}
    finally:
        node.stop()

    return {
        "suite": "sm" if sm else "ecdsa",
        "clients": clients,
        "requests": done,
        "cache": bool(cache),
        "keepalive": bool(keepalive),
        "qps": round(done / wall, 1) if wall > 0 else 0.0,
        "wall_seconds": round(wall, 3),
        "p50_ms": round(pct(0.50) * 1000, 2),
        "p99_ms": round(pct(0.99) * 1000, 2),
        "cache_hit_rate": cache_stats.get("hit_rate", 0.0),
        "cache_entries": cache_stats.get("entries", 0),
        "recover_calls": recover_stats["calls"],
        "blocks": head,
        "txs": n_txs,
    }


class _WsFrameReader:
    """Bench-local incremental parser for SERVER WebSocket frames (the
    server never masks): feed raw socket bytes, yields text payloads.
    The selector-driven subscriber harness needs this because the real
    WsConnection reader is blocking — 10k blocking readers would need
    10k client threads just to count notifications."""

    def __init__(self):
        self.buf = b""

    def feed(self, data: bytes):
        self.buf += data
        out = []
        while True:
            b = self.buf
            if len(b) < 2:
                break
            ln = b[1] & 0x7F
            off = 2
            if ln == 126:
                if len(b) < 4:
                    break
                ln = int.from_bytes(b[2:4], "big")
                off = 4
            elif ln == 127:
                if len(b) < 10:
                    break
                ln = int.from_bytes(b[2:10], "big")
                off = 10
            if len(b) < off + ln:
                break
            if b[0] & 0x0F == 0x1:  # text frame
                out.append(b[off:off + ln])
            self.buf = b[off + ln:]
        return out


def run_sub_bench(sm: bool, backend: str, subscribers: int,
                  blocks: int = 12, txs_per_block: int = 50,
                  compare: bool = False) -> list:
    """Push-plane fan-out at subscriber scale: N WS subscribers on
    `newBlockHeaders` (through the admission plane), then `blocks`
    committed blocks. Measures commit->client-receipt notify latency
    (server stamps each commit; a single selector reader stamps every
    arriving frame), fan-out events/s, and the per-notification CPU
    cost. With `compare`, adds the poll-vs-push A/B at equal information
    freshness: what read QPS N pollers would need to learn each head
    within the push plane's p99, against the node's measured polling
    capacity."""
    import selectors as _selectors
    import threading

    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.net.websocket import ws_connect
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.sdk.client import SdkClient

    node = Node(NodeConfig(consensus="solo", sm_crypto=sm,
                           crypto_backend=backend, min_seal_time=0.05,
                           tx_count_limit=txs_per_block, rpc_port=0,
                           ws_port=0, sub_max_sessions=subscribers + 64))
    node.build_genesis()
    n_txs = blocks * txs_per_block
    wire_txs = _build_workload(sm, n_txs, block_limit=min(
        600, 2 * blocks + 50))
    node.start()
    conns = []
    try:
        print(f"sub-bench: connecting {subscribers} WS subscribers...",
              file=sys.stderr, flush=True)
        sel = _selectors.DefaultSelector()
        for i in range(subscribers):
            conn = ws_connect(node.ws.host, node.ws.port, timeout=30)
            conn.send_text(json.dumps({
                "jsonrpc": "2.0", "id": 1, "method": "subscribe",
                "params": ["newBlockHeaders"]}))
            conns.append(conn)
        # every subscribe answered (admission + hub registration done)
        for conn in conns:
            msg = conn.recv()
            assert msg is not None, "subscribe dropped"
            resp = json.loads(msg[1])
            assert "result" in resp, f"subscribe rejected: {resp}"
        for conn in conns:
            conn.sock.setblocking(False)
            rdr = _WsFrameReader()
            rdr.buf = conn._rbuf  # bytes that rode in with the response
            conn._rbuf = b""
            sel.register(conn.sock, _selectors.EVENT_READ, rdr)

        # stamp FIRST in the observer list: commit->client latency then
        # honestly includes the cache prime and the hub fan-out cost
        t_commit: dict = {}
        node.scheduler.on_commit.insert(
            0, lambda n: t_commit.setdefault(n, time.perf_counter()))

        lats: list = []
        received = [0]
        done = threading.Event()

        def reader():
            while True:
                events = sel.select(timeout=0.2)
                now = time.perf_counter()
                for key, _m in events:
                    try:
                        data = key.fileobj.recv(1 << 16)
                    # spurious readiness — poll again
                    except (BlockingIOError, InterruptedError):  # bcoslint: disable=swallowed-worker-exception
                        continue
                    except OSError:
                        sel.unregister(key.fileobj)
                        continue
                    if not data:
                        sel.unregister(key.fileobj)
                        continue
                    for payload in key.data.feed(data):
                        try:
                            num = json.loads(payload)["params"][
                                "result"]["number"]
                        except Exception:  # non-push frame  # bcoslint: disable=swallowed-worker-exception
                            continue
                        received[0] += 1
                        t0 = t_commit.get(num)
                        if t0 is not None:
                            lats.append(now - t0)
                if done.is_set() and not events:
                    return

        rt = threading.Thread(target=reader, daemon=True)
        cpu0 = time.process_time()
        wall0 = time.perf_counter()
        rt.start()
        for s in range(0, n_txs, 256):
            node.txpool.submit_batch(
                [Transaction.decode(raw) for raw in wire_txs[s:s + 256]])
        deadline = time.monotonic() + max(120.0, n_txs / 10)
        while time.monotonic() < deadline:
            if node.ledger.total_tx_count() >= n_txs:
                break
            time.sleep(0.05)
        head = node.ledger.current_number()
        # every block 1..head fans out to every subscriber (the commit
        # notifier is async — t_commit may still be filling here)
        expect = subscribers * head
        settle = time.monotonic() + 60
        while time.monotonic() < settle and received[0] < expect:
            time.sleep(0.05)
        done.set()
        rt.join(timeout=5)
        wall = time.perf_counter() - wall0
        cpu = time.process_time() - cpu0
        lats.sort()

        def pct(p):
            return lats[min(len(lats) - 1, int(p * len(lats)))] \
                if lats else 0.0

        hub = node.subhub.stats()
        drops = node.ws.push_drop_stats()
        rows = [{
            "metric": f"sub_notify_p99_ms{'_sm' if sm else ''}",
            "unit": "ms", "value": round(pct(0.99) * 1000, 2),
            "suite": "sm" if sm else "ecdsa",
            "subscribers": subscribers,
            "blocks": head, "events": received[0],
            "events_expected": expect,
            "events_per_sec": round(received[0] / wall, 1) if wall else 0.0,
            "notify_p50_ms": round(pct(0.50) * 1000, 2),
            "cpu_us_per_notify": round(cpu / max(received[0], 1) * 1e6, 2),
            "outbox_drops": drops,
            "hub_p99_ms": hub["notifyP99Ms"],  # commit-dequeue -> wire
        }]
        if compare:
            # poll capacity: 8 keep-alive pollers, header-only getBlock,
            # closed loop for a short window on the SAME primed node
            url = f"http://{node.rpc.host}:{node.rpc.port}"
            stop = time.monotonic() + 3.0
            counts = [0] * 8

            def poller(c):
                sdk = SdkClient(url, keepalive=True)
                while time.monotonic() < stop:
                    sdk.get_block_by_number(head, only_header=True)
                    counts[c] += 1

            ths = [threading.Thread(target=poller, args=(c,), daemon=True)
                   for c in range(8)]
            p0 = time.perf_counter()
            for th in ths:
                th.start()
            for th in ths:
                th.join(30)
            poll_qps = sum(counts) / (time.perf_counter() - p0)
            p99s = max(pct(0.99), 1e-4)
            needed = subscribers / p99s  # each poller must poll ~1/p99
            rows.append({
                "metric": f"sub_poll_vs_push{'_sm' if sm else ''}",
                "unit": "x",
                "value": round(needed / max(poll_qps, 0.001), 1),
                "suite": "sm" if sm else "ecdsa",
                "subscribers": subscribers,
                "poll_qps_capacity": round(poll_qps, 1),
                "poll_qps_needed_for_p99_freshness": round(needed, 1),
                "push_p99_ms": round(p99s * 1000, 2),
            })
        return rows
    finally:
        for conn in conns:
            try:
                conn.sock.close()
            except OSError:
                pass
        node.stop()


def run_sync_bench(sm: bool, n_blocks: int, txs_per_block: int = 10) -> list:
    """Join-time comparison on one source chain: replay vs snap-sync.

    A single-sealer PBFT chain commits `n_blocks` full blocks; then two
    fresh joiners catch up from it over the in-process gateway — one forced
    through block replay (snap_sync_threshold=0), one through snap-sync
    (source checkpoints first). Same chain, same transport, same suite.
    """
    import time as _t

    from fisco_bcos_tpu.crypto.suite import make_suite
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.ledger.ledger import ConsensusNode
    from fisco_bcos_tpu.net.gateway import FakeGateway
    from fisco_bcos_tpu.protocol import Transaction

    n_txs = n_blocks * txs_per_block
    print(f"sync-bench: building a {n_blocks}-block source chain "
          f"({n_txs} txs)...", file=sys.stderr, flush=True)
    wire_txs = _build_workload(sm, n_txs, block_limit=min(
        600, 2 * n_blocks + 50))

    suite = make_suite(sm, backend="host")
    gw = FakeGateway()
    kp = suite.generate_keypair(b"\x01" * 16)
    sealers = [ConsensusNode(kp.pub_bytes)]
    src = Node(NodeConfig(consensus="pbft", sm_crypto=sm,
                          crypto_backend="host", min_seal_time=0.0,
                          view_timeout=30.0,
                          tx_count_limit=txs_per_block),
               keypair=kp, gateway=gw)
    src.build_genesis(sealers)
    src.start()
    rows = []
    joiners = []
    try:
        for s in range(0, n_txs, 256):
            txs = [Transaction.decode(raw) for raw in wire_txs[s:s + 256]]
            src.txpool.submit_batch(txs)
        deadline = _t.monotonic() + max(120.0, n_txs / 20)
        while _t.monotonic() < deadline:
            if src.ledger.total_tx_count() >= n_txs:
                break
            _t.sleep(0.05)
        head = src.ledger.current_number()
        if src.ledger.total_tx_count() < n_txs:
            raise RuntimeError(
                f"source chain wedged at {src.ledger.total_tx_count()}/"
                f"{n_txs} txs")

        def join(threshold: int) -> tuple[float, "Node"]:
            node = Node(NodeConfig(consensus="pbft", sm_crypto=sm,
                                   crypto_backend="host",
                                   snap_sync_threshold=threshold),
                        suite=suite, gateway=gw)
            node.build_genesis(sealers)
            t0 = _t.perf_counter()
            node.start()
            deadline = _t.monotonic() + max(120.0, n_blocks)
            while _t.monotonic() < deadline:
                if node.ledger.current_number() >= head:
                    break
                _t.sleep(0.02)
            secs = _t.perf_counter() - t0
            joiners.append(node)
            if node.ledger.current_number() < head:
                raise RuntimeError(
                    f"joiner wedged at {node.ledger.current_number()}/"
                    f"{head}")
            return secs, node

        replay_secs, replay_node = join(threshold=0)
        assert replay_node.blocksync.sync_mode == "replay"
        # stop the replay joiner BEFORE the snap join: at the same height
        # as src it would tie the peer selection, and its empty snapshot
        # store would make the snap joiner fall back to replay
        replay_node.stop()
        joiners.remove(replay_node)
        rows.append({
            "metric": "replay_blocks_per_sec",
            "value": round(head / replay_secs, 2), "unit": "blocks/sec",
            "suite": "sm" if sm else "ecdsa", "blocks": head,
            "txs": n_txs, "join_seconds": round(replay_secs, 3),
        })

        manifest = src.snapshot.checkpoint()
        snap_secs, snap_node = join(threshold=max(1, n_blocks // 10))
        assert snap_node.blocksync.sync_mode == "snap", \
            "snap joiner fell back to replay"
        rows.append({
            "metric": "snap_sync_seconds",
            "value": round(snap_secs, 3), "unit": "sec",
            "suite": "sm" if sm else "ecdsa", "blocks": head,
            "txs": n_txs, "chunks": manifest.chunk_count,
            "state_bytes": manifest.total_bytes,
            "replay_join_seconds": round(replay_secs, 3),
            "speedup_vs_replay": round(replay_secs / snap_secs, 1)
            if snap_secs > 0 else None,
        })
        return rows
    finally:
        for node in joiners:
            try:
                node.stop()
            except Exception:
                pass
        src.stop()
        gw.stop()


def run_groups(sm: bool, n: int, backend: str, tx_count_limit: int,
               groups: int, cross_pct: float = 0.0,
               lane: bool = True) -> dict:
    """Multi-group sharding throughput: G independent groups inside ONE
    node process (init/group.py GroupManager — the deployment shape: this
    process is one member of each group), storage namespaced per group
    over one shared store, every group's crypto riding ONE shared lane
    (crypto/lane.py), the cross-shard coordinator attached. Each group's
    feeder thread drives `n` pre-signed txs over the direct host-ingest
    path; groups run solo consensus so the measured work is THIS
    process's pipeline, not an in-process simulation of the whole
    committee. Reports aggregate and per-group TPS plus the lane's merge
    profile — the lane-filling claim is the measured
    `lane_mean_device_batch` vs each group's solo request mean.

    `cross_pct` makes that share of each group's workload cross-shard
    `transferOut` legs to the next group (ring order); the run then also
    waits for the coordinator to settle every transfer (credit committed
    on the destination + escrow finished at the source) and reports the
    settlement lag — the measured cross-shard tax."""
    import gc
    import threading

    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.init.group import GroupManager
    from fisco_bcos_tpu.init.node import NodeConfig
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.storage.memory import MemoryStorage

    gids = [f"group{g}" for g in range(groups)]
    if groups < 2:
        cross_pct = 0.0  # cross-shard needs a second shard
    n_cross = int(n * max(0.0, min(100.0, cross_pct)) / 100.0)
    n_local = n - n_cross
    blocks_needed = -(-n // max(1, tx_count_limit))
    block_limit = min(600, max(100, 2 * blocks_needed + 40))
    if blocks_needed > 500:
        raise SystemExit(
            f"n/tx_count_limit needs ~{blocks_needed} blocks, beyond the "
            f"600-block tx lifetime; raise --tx-count-limit")
    print(f"signing {groups}x{n} txs (excluded from the timed window)...",
          file=sys.stderr, flush=True)
    workload: dict[str, list[bytes]] = {}
    for g, gid in enumerate(gids):
        txs = _build_workload(sm, n_local, block_limit, group_id=gid)
        if n_cross:
            txs += _build_workload(sm, n_cross, block_limit, group_id=gid,
                                   cross=gids[(g + 1) % groups],
                                   start=n_local)
        # decode OUTSIDE the timed window: wire decode is workload-prep,
        # and doing it inside would add G threads of pure-GIL work that
        # masks the pipeline under measurement
        workload[gid] = [Transaction.decode(raw) for raw in txs]

    mgr = GroupManager(storage=MemoryStorage())
    nodes = {}
    for gid in gids:
        nodes[gid] = mgr.add_group(NodeConfig(
            group_id=gid, consensus="solo", sm_crypto=sm,
            crypto_backend=backend, min_seal_time=0.0,
            tx_count_limit=tx_count_limit, ingest_lane=False,
            crypto_lane=lane))
    mgr.start()
    gc_was_enabled = gc.isenabled()
    try:
        # setup (untimed): pre-fund each group's cross-shard escrow account
        if n_cross:
            for gid, node in nodes.items():
                tx = Transaction(
                    to=pc.BALANCE_ADDRESS,
                    input=pc.encode_call(
                        "register",
                        lambda w: w.blob(b"funder").u64(n_cross)),
                    nonce="fund", group_id=gid,
                    block_limit=block_limit).sign(
                        node.suite, node.suite.generate_keypair(b"fund"))
                res = node.send_transaction(tx)
                rc = node.txpool.wait_for_receipt(res.tx_hash, 30)
                if rc is None or rc.status != 0:
                    raise RuntimeError(f"funding {gid} failed: {rc}")

        from collections import deque

        from fisco_bcos_tpu.protocol import batch_hash

        # client tx hashes per group, computed OUTSIDE the timed window:
        # completion must count CLIENT txs by receipt — total_tx_count
        # also counts the coordinator's credit/finish legs, which would
        # let a cross-shard run claim completion early
        client_hashes = {gid: deque(batch_hash(workload[gid],
                                               nodes[gid].suite))
                         for gid in gids}
        t_done: dict[str, float] = {}
        errors: list[str] = []
        barrier = threading.Barrier(groups + 1)

        def feeder(gid: str) -> None:
            node, txs = nodes[gid], workload[gid]
            pending = client_hashes[gid]
            try:
                barrier.wait()
                for s in range(0, len(txs), 512):
                    results = node.txpool.submit_batch(txs[s:s + 512])
                    if s == 0 and int(results[0].status) != 0:
                        raise RuntimeError(
                            f"{gid} first submit: {results[0].status}")
                # done when every client tx has a committed receipt
                # (commits are block-ordered, so polling the FIFO front
                # costs O(n) total, not O(n^2))
                deadline = time.monotonic() + max(120.0, n / 25)
                while pending and time.monotonic() < deadline:
                    if node.ledger.receipt(pending[0]) is not None:
                        pending.popleft()
                    else:
                        time.sleep(0.005)
                if not pending:
                    t_done[gid] = time.perf_counter()
            except Exception as exc:  # noqa: BLE001 — surface, don't hang
                errors.append(f"{gid}: {type(exc).__name__}: {exc}")

        threads = [threading.Thread(target=feeder, args=(gid,), daemon=True)
                   for gid in gids]
        for th in threads:
            th.start()
        # bench hygiene for the 2-core host: collect BEFORE the window and
        # keep the collector from injecting GIL pauses inside it (1.6x
        # run-to-run swings traced to allocator/GC weather, not code)
        gc.collect()
        gc.disable()
        barrier.wait()
        t0 = time.perf_counter()
        for th in threads:
            th.join(max(240.0, n / 10))
        if errors:
            raise RuntimeError(f"group feeder failed: {errors[0]}")
        timed_out = any(th.is_alive() for th in threads) or \
            len(t_done) < groups
        t_clients = time.perf_counter()
        # cross-shard settlement drain: every escrow finished everywhere
        settled = groups * n_cross
        if n_cross and not timed_out:
            deadline = time.monotonic() + max(120.0, settled / 5)
            while time.monotonic() < deadline:
                pending = sum(
                    len(list(node.storage.keys(pc.T_XSHARD_PEND)))
                    for node in nodes.values())
                if pending == 0:
                    break
                time.sleep(0.05)
            else:
                timed_out = True
        t_end = time.perf_counter()
        committed = sum(node.ledger.total_tx_count()
                        for node in nodes.values())
        coord = mgr.coordinator.stats() if mgr.coordinator else {}
        lane_stats = mgr.crypto_lane_stats().get(
            "sm" if sm else "ecdsa", {})
    finally:
        if gc_was_enabled:
            gc.enable()
        mgr.stop()

    wall = t_end - t0
    per_group = {gid: round(n / (t_done[gid] - t0), 1)
                 for gid in gids if gid in t_done and t_done[gid] > t0}
    return {
        "suite": "sm" if sm else "ecdsa",
        "groups": groups,
        "cross_shard_pct": cross_pct,
        "crypto_lane": bool(lane),
        "timed_out": bool(timed_out),
        "txs_committed": int(committed),
        # aggregate DIRECT throughput: the G*n client txs over the wall
        # from first submit to the last group's completion (settlement
        # drain excluded — it's reported as the cross-shard tax below)
        "tps": round(groups * n / (t_clients - t0), 1)
        if t_clients > t0 else 0.0,
        "wall_seconds": round(t_clients - t0, 3),
        "per_group_tps": per_group,
        "lane_mean_device_batch": lane_stats.get("mean_device_batch", 0.0),
        "lane_per_group_mean": lane_stats.get("per_tag_mean_batch", {}),
        "lane_merged_calls": lane_stats.get("merged_calls", 0),
        "lane_device_calls": lane_stats.get("device_calls", 0),
        "cross_shard_txs": settled if n_cross else 0,
        "cross_shard_settled": coord.get("completed_total", 0),
        "cross_shard_aborted": coord.get("aborted_total", 0),
        # settlement lag past client completion: the measured tax of
        # making the shards NOT disjoint
        "cross_shard_drain_seconds": round(t_end - t_clients, 3)
        if n_cross else 0.0,
        "cross_shard_settle_tps": round(settled / wall, 1)
        if n_cross and wall > 0 else 0.0,
    }


def _emit_groups_mode(args, sm: bool) -> None:
    suffix = "_sm" if sm else ""
    reps = max(1, args.groups_runs)
    configs = []
    if args.groups_compare and args.groups != 1:
        configs.append(("groups_baseline", 1, 0.0))
    configs.append(("groups", args.groups, args.cross_shard_pct))
    # INTERLEAVED repetitions (PERF.md discipline: the 2-core CI host
    # swings 3-5x run-to-run with co-tenant load — back-to-back A then B
    # would attribute host weather to the config; A/B/A/B with medians
    # does not)
    rows: dict[str, list[dict]] = {name: [] for name, _g, _p in configs}
    # discarded warm-up: the first run in a fresh process measures
    # allocator/import warm-up alongside the chain (observed ~1.6x below
    # steady state) — without it the FIRST config measured eats the cold
    # start and the A/B comparison is biased
    run_groups(sm, max(512, args.n // 4), args.backend,
               args.tx_count_limit, args.groups,
               lane=not args.no_crypto_lane)
    for rep in range(reps):
        for name, g, pct in configs:
            res = run_groups(sm, args.n, args.backend, args.tx_count_limit,
                             g, cross_pct=pct,
                             lane=not args.no_crypto_lane)
            res.update({"metric": f"{name}_tps{suffix}",
                        "value": res["tps"], "unit": "tx/sec", "run": rep})
            rows[name].append(res)
            print(_dumps(res), flush=True)

    def median_tps(name: str) -> float:
        vals = sorted(r["tps"] for r in rows[name])
        return vals[len(vals) // 2] if vals else 0.0

    if args.groups_compare and rows.get("groups_baseline"):
        base_med = median_tps("groups_baseline")
        multi_med = median_tps("groups")
        multi = rows["groups"][-1]
        solo_means = [m for r in rows["groups"]
                      for m in r["lane_per_group_mean"].values()]
        lane_means = [r["lane_mean_device_batch"] for r in rows["groups"]
                      if r["lane_mean_device_batch"]]
        lane_mean = (sorted(lane_means)[len(lane_means) // 2]
                     if lane_means else 0.0)
        print(_dumps({
            "metric": f"groups_scaling{suffix}", "unit": "x",
            "value": round(multi_med / max(base_med, 0.001), 2),
            "groups": multi["groups"], "runs": reps,
            "tps_1group_median": base_med, "tps_median": multi_med,
            "tps_1group_runs": [r["tps"] for r in rows["groups_baseline"]],
            "tps_runs": [r["tps"] for r in rows["groups"]],
            "timed_out": any(r["timed_out"]
                             for rs in rows.values() for r in rs),
            "lane_mean_device_batch": lane_mean,
            "lane_max_group_solo_mean": max(solo_means) if solo_means
            else 0.0,
            # the lane-merging claim, measured: merged device batches must
            # exceed what any single group submits on its own
            "lane_merge_wins": lane_mean >
            (max(solo_means) if solo_means else 0.0),
        }), flush=True)


def _emit_rpc_mode(args, sm: bool) -> None:
    runs = []
    if args.rpc_compare:
        # anchors first: per-request baseline (lane off), then 1 client
        runs.append(("rpc_ingest_baseline", args.rpc_clients, False))
        runs.append(("rpc_ingest_1client", 1, True))
    runs.append(("rpc_ingest", args.rpc_clients, True))
    rows = {}
    for name, clients, lane in runs:
        res = run_rpc_ingest(sm, args.n, args.backend, args.tx_count_limit,
                             clients, ingest_lane=lane,
                             pipeline=not args.no_pipeline)
        suffix = "_sm" if sm else ""
        res.update({"metric": f"{name}_tps{suffix}", "value": res["tps"],
                    "unit": "tx/sec"})
        rows[name] = res
        print(_dumps(res), flush=True)
    if args.rpc_compare:
        base, lane_row = rows["rpc_ingest_baseline"], rows["rpc_ingest"]
        amort = (base["recover_calls_per_tx"] /
                 lane_row["recover_calls_per_tx"]) \
            if lane_row["recover_calls_per_tx"] else float("inf")
        print(_dumps({
            "metric": "rpc_ingest_amortization", "unit": "x",
            "value": round(amort, 1),
            "verify_calls_per_tx_baseline": base["recover_calls_per_tx"],
            "verify_calls_per_tx_lane": lane_row["recover_calls_per_tx"],
            "tps_vs_1client": round(
                lane_row["tps"] / max(rows["rpc_ingest_1client"]["tps"],
                                      0.001), 2),
        }), flush=True)


def _emit_read_mode(args, sm: bool) -> None:
    suffix = "_sm" if sm else ""
    rows = {}
    if args.read_compare:
        # per-request/no-cache anchor: fresh connection per request, no
        # query cache — the old ThreadingHTTPServer serving shape
        base = run_rpc_read(sm, args.backend, args.read_clients,
                            args.read_requests, cache=False,
                            keepalive=False)
        base.update({"metric": f"rpc_read_baseline_qps{suffix}",
                     "value": base["qps"], "unit": "req/sec"})
        rows["base"] = base
        print(_dumps(base), flush=True)
    res = run_rpc_read(sm, args.backend, args.read_clients,
                       args.read_requests)
    res.update({"metric": f"rpc_read_qps{suffix}", "value": res["qps"],
                "unit": "req/sec"})
    rows["read"] = res
    print(_dumps(res), flush=True)
    if args.read_compare:
        base = rows["base"]
        print(_dumps({
            "metric": f"rpc_read_speedup{suffix}", "unit": "x",
            "value": round(res["qps"] / max(base["qps"], 0.001), 2),
            "qps_baseline": base["qps"], "qps": res["qps"],
            "p99_ms_baseline": base["p99_ms"], "p99_ms": res["p99_ms"],
            "recover_calls_baseline": base["recover_calls"],
            "recover_calls": res["recover_calls"],
            "cache_hit_rate": res["cache_hit_rate"],
        }), flush=True)


def _emit_sub_mode(args, sm: bool) -> None:
    for row in run_sub_bench(sm, args.backend, args.subscribers,
                             blocks=args.sub_blocks,
                             compare=args.sub_compare):
        print(_dumps(row), flush=True)


def run_trace_profile(sm: bool, backend: str, n_txs: int = 24,
                      seal_mode: str = "multi") -> list:
    """End-to-end latency decomposition from the tracing plane
    (utils/otrace.py): a 4-node chain at sample_rate=1, `n_txs` closed-loop
    transactions each carrying its own trace root, stages aggregated from
    the INGRESS node's spans. Emits one row per stage plus a summary whose
    `coverage` reconciles the stage sum against the independently measured
    submit->receipt p50 — the check that the stages account for the
    transaction's wall-clock rather than a subset of it. `seal_mode`
    selects the commit-seal carriage (consensus/qc.py) so multi-vs-cert
    consensus stages can be A/B'd in one session; the summary row carries
    the consensus stage means and the measured per-block seal bytes as
    named fields for perf_gate banding."""
    import statistics as _stats

    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.utils import otrace

    nodes, gateways, _tls = _build_chain(
        sm, backend, 1000, min_seal_time=0.0,
        cfg_overrides={"seal_mode": seal_mode})
    otrace.TRACER.configure(sample_rate=1.0, ring_size=16384, slow_ms=0.0)
    otrace.TRACER.reset()
    ingress = nodes[0]
    suite = ingress.suite
    kp = suite.generate_keypair(b"trace-profile-client")
    for node in nodes:
        node.start()
    e2e_ms: list[float] = []
    roots = []
    try:
        for i in range(n_txs):
            tx = Transaction(
                to=pc.BALANCE_ADDRESS,
                input=pc.encode_call(
                    "register", lambda w, _i=i: w.blob(
                        b"tp%d" % _i).u64(10 + _i)),
                nonce=f"tp{i}", block_limit=500).sign(suite, kp)
            root = otrace.TRACER.new_root()
            tx._otrace = root
            roots.append(root)
            t0 = time.perf_counter()
            res = ingress.send_transaction(tx)
            rc = ingress.txpool.wait_for_receipt(res.tx_hash, 30)
            if rc is None:
                raise RuntimeError(f"tx {i} never committed")
            e2e_ms.append((time.perf_counter() - t0) * 1000.0)
        time.sleep(0.3)  # let follower stage spans drain into the ring
    finally:
        for node in nodes:
            node.stop()
        for gw in set(gateways):
            gw.stop()

    label = ingress.trace_label
    # ONE span per (trace, stage), chosen to follow the transaction's
    # actual PATH across the cluster (every node records its own copy of
    # the block stages; mixing them would count each stage four times):
    # admission on the INGRESS node, the gossiped copy's re-admission on
    # the block's LEADER (its lane coalesce is real path latency — the
    # tx cannot seal before it), `stage.seal_wait` on the leader, and the
    # block stages on the ingress node, whose commit+notify is what
    # resolves the client's receipt wait.
    per_stage: dict[str, list[float]] = {}
    stitched_nodes: set = set()
    for root in roots:
        spans = otrace.TRACER.get_trace(root.trace_id.hex())
        leader = next((s["attrs"].get("node") for s in spans
                       if s["name"] == "stage.seal_wait"), label)
        chosen: dict[str, dict] = {}
        for s in spans:
            node = s["attrs"].get("node")
            stitched_nodes.add(node or s["attrs"].get("node_idx"))
            name = s["name"]
            if name == "ingest.admit":
                if node == leader and leader != label:
                    chosen.setdefault("gossip.admit", s)
                    continue
                want = label
            elif name == "stage.seal_wait":
                want = leader
            elif name.startswith("stage."):
                want = label
            else:
                continue
            cur = chosen.get(name)
            if cur is None or (node == want
                               and cur["attrs"].get("node") != want):
                chosen[name] = s
        for name, s in chosen.items():
            per_stage.setdefault(name, []).append(s["duration_ms"])
    rows = []
    stage_sum = 0.0
    for name in sorted(per_stage):
        # inside another span of the path, or the same interval seen from
        # the ingress node (`round_wait` = the leader's admit + seal_wait)
        if name in ("txpool.admit", "stage.crypto", "stage.gossip",
                    "stage.round_wait"):
            continue
        mean = _stats.mean(per_stage[name])
        stage_sum += mean
        rows.append({"metric": "trace_profile", "unit": "ms",
                     "suite": "sm" if sm else "ecdsa", "stage": name,
                     "mean_ms": round(mean, 3),
                     "count": len(per_stage[name])})
    p50 = _stats.median(e2e_ms) if e2e_ms else 0.0
    # per-block commit-seal wire bytes actually committed in this run
    # (consensus/qc.py seal_wire_bytes: encode() minus encode_core())
    from fisco_bcos_tpu.consensus import qc as _qc
    head = ingress.ledger.current_number()
    seal_bytes = [_qc.seal_wire_bytes(ingress.ledger.header_by_number(nn))
                  for nn in range(1, head + 1)]
    rows.append({
        "metric": "trace_profile_summary", "unit": "ms",
        "suite": "sm" if sm else "ecdsa",
        "txs": len(e2e_ms),
        "seal_mode": seal_mode,
        "seal_bytes_per_block": round(_stats.mean(seal_bytes), 1)
        if seal_bytes else 0,
        # the two consensus stages as named fields (the generic per-stage
        # rows pool under one `mean_ms` name, which would gate ALL stages
        # as one population; perf_gate's `_ms` suffix bands these)
        "consensus_pre_ms": round(_stats.mean(
            per_stage.get("stage.consensus_pre", [0.0])), 3),
        "consensus_wait_ms": round(_stats.mean(
            per_stage.get("stage.consensus_wait", [0.0])), 3),
        "stage_sum_ms": round(stage_sum, 3),
        "e2e_p50_ms": round(p50, 3),
        "e2e_mean_ms": round(_stats.mean(e2e_ms), 3) if e2e_ms else 0.0,
        # stage-sum / measured p50: ~1.0 means the decomposition accounts
        # for the transaction's wall-clock end to end
        "coverage": round(stage_sum / p50, 3) if p50 else None,
        "nodes_stitched": len({n for n in stitched_nodes
                               if n not in (None, "")}),
    })
    return rows


def run_seal_bench(sm: bool, backend: str, rosters=(4, 16, 64)) -> list:
    """Commit-seal carriage bytes + verify cost per `seal_mode`
    (consensus/qc.py), deterministic and offline: for each roster size,
    mint a real quorum of seals over one header in every mode and measure
    (a) the exact wire bytes each hop ships (encode() minus encode_core())
    and (b) one span-verify call's wall time through `qc.verify_spans`.
    Honesty notes: `aggregate` verify is the pure-Python BN254 pairing
    (~1 s — correctness-first wire format, not a live-path speedup), and
    at tiny rosters `cert` saves only the per-seal index framing, so
    `vs_multi` is reported per mode rather than a blended headline."""
    from fisco_bcos_tpu.consensus import qc as _qc
    from fisco_bcos_tpu.crypto import agg as _agg
    from fisco_bcos_tpu.crypto.suite import make_suite
    from fisco_bcos_tpu.protocol import BlockHeader

    suite = make_suite(sm, backend=backend)
    rows = []
    for n in rosters:
        kps = [suite.generate_keypair(bytes([i + 1]) * 8 + b"seal-bench")
               for i in range(n)]
        sealers = sorted(kp.pub_bytes for kp in kps)
        by_pub = {kp.pub_bytes: kp for kp in kps}
        quorum = 2 * ((n - 1) // 3) + 1
        reg = _agg.AggKeyRegistry.from_seeds(
            [(pk, pk + b"bench-seed") for pk in sealers])
        secrets = {pk: _agg.derive_secret(pk + b"bench-seed")
                   for pk in sealers}

        def header_for(mode):
            h = BlockHeader(number=1, sealer_list=list(sealers))
            hh = h.hash(suite)
            if mode == "aggregate":
                sigs = [_agg.sign(secrets[sealers[i]], hh)
                        for i in range(quorum)]
                _qc.attach(h, _qc.mint_aggregate(
                    list(range(quorum)), _agg.aggregate_sigs(sigs), n))
                return h
            seals = [(i, suite.sign(by_pub[sealers[i]], hh))
                     for i in range(quorum)]
            if mode == "cert":
                _qc.attach(h, _qc.mint_cert(seals, n))
            else:
                h.signature_list = seals
            return h

        multi_bytes = None
        for mode in ("multi", "cert", "aggregate"):
            if mode == "aggregate" and n > 16:
                continue  # pairing cost is roster-independent; 2 rows pin it
            h = header_for(mode)
            nbytes = _qc.seal_wire_bytes(h)
            if mode == "multi":
                multi_bytes = nbytes
            t0 = time.perf_counter()
            ok = _qc.verify_spans([h], sealers, suite, agg_registry=reg)
            verify_ms = (time.perf_counter() - t0) * 1000.0
            if not bool(ok[0]):
                raise RuntimeError(f"seal bench self-check failed: {mode}")
            rows.append({
                "metric": "seal_bytes", "unit": "bytes",
                "suite": "sm" if sm else "ecdsa",
                "mode": mode, "sealers": n, "quorum": quorum,
                "seal_bytes_per_block": nbytes,
                "vs_multi": round(nbytes / multi_bytes, 3),
                "span_verify_ms": round(verify_ms, 2),
            })
    return rows


def run_proof_bench(sm: bool, backend: str, n_txs: int = 120,
                    hash_batches=None) -> list:
    """ZK proof plane bench (ISSUE 14): batched Poseidon hashing
    device-vs-host, plus proof rendering/serving/verification rates on a
    live solo chain.

    Honesty rules (PERF.md convention): the "device" Poseidon path is
    whatever jax backend is present — on a CPU-only host the vectorized
    XLA path LOSES to the Python bigint loop (the backend's per-op cost
    model, PERF.md r4) and the row says so via `device_backend` and a
    speedup < 1. The host-loop baseline is measured on a bounded
    subsample and scaled linearly (a pure per-item loop)."""
    import statistics as _stats

    import jax
    import numpy as np

    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.ops import merkle as om
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.rpc.cache import QueryCache
    from fisco_bcos_tpu.zk import poseidon as zp
    from fisco_bcos_tpu.zk import poseidon_jax as pj
    from fisco_bcos_tpu.zk import proof as zkproof

    suite_name = "sm" if sm else "ecdsa"
    platform = jax.devices()[0].platform
    if hash_batches is None:
        # CPU interpreters pay ~4 s/1k lanes on this path: keep the sweep
        # tiny there; a real device runs the full ladder
        hash_batches = (1024, 16384, 65536) if platform == "tpu" \
            else (512,)
    rows = []
    rng = np.random.default_rng(1)

    # -- part 1: batched Poseidon, device path vs host loop -----------------
    for B in hash_batches:
        lefts = [rng.bytes(32) for _ in range(B)]
        rights = [rng.bytes(32) for _ in range(B)]
        pj.hash2_batch(lefts, rights)  # compile warm-up
        t0 = time.perf_counter()
        dev_out = pj.hash2_batch(lefts, rights)
        dev_dt = time.perf_counter() - t0
        m = min(B, 1024)
        t0 = time.perf_counter()
        host_out = zp.hash2_batch_host(lefts[:m], rights[:m])
        host_dt = time.perf_counter() - t0
        assert dev_out[:m] == host_out  # bit-identity before any number
        dev_rate = B / dev_dt
        host_rate = m / host_dt
        rows.append({
            "metric": "poseidon_hashes_per_sec", "unit": "hashes/sec",
            "suite": suite_name, "batch": B,
            "device": round(dev_rate, 1), "host_loop": round(host_rate, 1),
            "speedup": round(dev_rate / host_rate, 3),
            "device_backend": platform,
            "host_subsample": m,
        })
    # Poseidon-Merkle tree (zk/merkle.py): the off-chain prover's
    # workload — B leaves, one batched hash call per level, then the
    # whole proof set verified in ONE batched call
    B = hash_batches[-1]
    leaves = [rng.bytes(32) for _ in range(B)]
    from fisco_bcos_tpu.zk import merkle as zmerkle
    levels = zmerkle.build_levels(leaves, hasher=pj.hash2_batch)  # warm
    t0 = time.perf_counter()
    levels = zmerkle.build_levels(leaves, hasher=pj.hash2_batch)
    tree_dt = time.perf_counter() - t0
    nprove = min(B, 256)
    items = [(leaves[i], zmerkle.proof_from_levels(levels, i),
              levels[-1][0]) for i in range(nprove)]
    t0 = time.perf_counter()
    okz = zmerkle.verify_batch(items, hasher=pj.hash2_batch)
    zver_dt = time.perf_counter() - t0
    assert okz.all()
    rows.append({
        "metric": "poseidon_merkle_tree", "unit": "leaves/sec",
        "suite": suite_name, "leaves": B, "levels": len(levels),
        "build_leaves_per_sec": round(B / tree_dt, 1),
        "verify_proofs_per_sec": round(nprove / zver_dt, 1),
        "device_backend": platform,
    })

    # -- part 2: proof serving on a live chain ------------------------------
    node = Node(NodeConfig(sm_crypto=sm, crypto_backend=backend,
                           min_seal_time=0.0))
    impl = node.make_rpc_impl()
    node.start()
    try:
        suite = node.suite
        kp = suite.generate_keypair(b"proof-bench")
        hashes: list[bytes] = []
        per_block = 40
        for s in range(0, n_txs, per_block):
            txs = [Transaction(
                to=pc.BALANCE_ADDRESS,
                input=pc.encode_call(
                    "register",
                    lambda w, i=i: w.blob(b"pb%d" % i).u64(1)),
                nonce=f"pb-{i}",
                block_limit=node.ledger.current_number() + 200
                ).sign(suite, kp)
                for i in range(s, min(s + per_block, n_txs))]
            node.txpool.submit_batch(txs)
            for tx in txs:
                h = tx.hash(suite)
                if node.txpool.wait_for_receipt(h, 60) is None:
                    raise RuntimeError("proof-bench tx never committed")
                hashes.append(h)
        numbers = sorted({node.ledger.receipt(h).block_number
                          for h in hashes})

        # render rate: both trees per block, every tx's bundle, into a
        # fresh cache (what the commit-time prime pays per block)
        cache = QueryCache(max_entries=4 * n_txs)
        t0 = time.perf_counter()
        rendered = sum(zkproof.render_block_proofs(
            node, cache, n, cache.generation()) for n in numbers)
        render_dt = time.perf_counter() - t0
        rows.append({
            "metric": "proofs_rendered_per_sec", "unit": "proofs/sec",
            "suite": suite_name, "txs": rendered,
            "blocks": len(numbers),
            "value": round(rendered / render_dt, 1),
        })

        # served rate: getProof against the primed cache (the steady state)
        docs = [impl.get_proof("group0", tx_hash="0x" + h.hex())
                for h in hashes]  # warm/populate
        t0 = time.perf_counter()
        for h in hashes:
            impl.get_proof("group0", tx_hash="0x" + h.hex())
        serve_dt = time.perf_counter() - t0
        rows.append({
            "metric": "proofs_served_per_sec", "unit": "proofs/sec",
            "suite": suite_name, "txs": len(hashes),
            "value": round(len(hashes) / serve_dt, 1),
        })

        # verification: batched (one hash call for every level of every
        # proof) vs the scalar per-proof loop
        items = [(h, zkproof.w16_proof_from_json(d["txProof"]),
                  bytes.fromhex(d["txsRoot"][2:]))
                 for h, d in zip(hashes, docs)]
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            ok = zkproof.verify_inclusion_batch(suite, items)
        batch_dt = (time.perf_counter() - t0) / reps
        assert ok.all()
        t0 = time.perf_counter()
        for _ in range(reps):
            scal = [om.verify_merkle_proof(leaf, proof, root,
                                           suite.hash_name)
                    for leaf, proof, root in items]
        scal_dt = (time.perf_counter() - t0) / reps
        assert all(scal)
        rows.append({
            "metric": "proofs_verified_per_sec", "unit": "proofs/sec",
            "suite": suite_name, "n_proofs": len(items),
            "batched": round(len(items) / batch_dt, 1),
            "scalar": round(len(items) / scal_dt, 1),
            "speedup": round(scal_dt / batch_dt, 3),
        })
        lane_note = node.system_status()["zk"]
        rows.append({
            "metric": "proof_bench_summary", "unit": "-",
            "suite": suite_name,
            "zk_status": lane_note,
            "e2e_block_mean_txs": round(_stats.mean(
                len(node.ledger.tx_hashes_by_number(n))
                for n in numbers), 1),
        })
    finally:
        node.stop()
    return rows


# -- overload mode (ISSUE 12: proof under fire) ------------------------------

_OVERLOAD_POOL = 2000  # pool sized so the watermarks are reachable in
#                        seconds of open-loop overload, not minutes


def _overload_cfg(plane: bool) -> dict:
    """NodeConfig overrides for the overload chains. plane=False is the
    pre-overload-control behavior (the A/B anchor): hard TXPOOL_FULL
    cliff at the limit, no busy controller, no edge buckets."""
    base = {"txpool_limit": _OVERLOAD_POOL}
    if not plane:
        base.update({"txpool_low_watermark": 1.0,
                     "txpool_high_watermark": 1.0,
                     "overload_enabled": False})
    return base


def _expired_in_committed_blocks(ledger) -> int:
    """Txs that landed in a block AFTER their block_limit — each one paid
    a seal slot for nothing. The plane's guarantee is that this is ZERO
    (seal re-checks expiry against the proposal's own height)."""
    bad = 0
    for n in range(1, ledger.current_number() + 1):
        blk = ledger.block_by_number(n, with_txs=True)
        if blk is None:
            continue
        for t in blk.transactions:
            if t.block_limit < n:
                bad += 1
    return bad


def _txpool_drop_counters() -> dict:
    from fisco_bcos_tpu.utils.metrics import REGISTRY
    c = REGISTRY.snapshot()["counters"]
    return {k: c.get(k, 0) for k in (
        "bcos_txpool_expired_total", "bcos_txpool_evicted_total",
        "bcos_txpool_deadline_shed_total",
        "bcos_ingest_deadline_shed_total")}


def _open_loop_window(ingress, wire_txs, rate: float, window_s: float):
    """Open-loop feeder: every few ms, submit the arrivals the Poisson-
    mean schedule owes (expected `rate`/s) straight into the ingress
    node's batch admission; arrivals are NEVER withheld because earlier
    ones were slow (that is what open-loop means). Returns admission
    outcome counts, per-call admission latency, and the window's
    committed throughput."""
    from fisco_bcos_tpu.protocol import Transaction, TransactionStatus

    txs = [Transaction.decode(raw) for raw in wire_txs]
    before = _txpool_drop_counters()
    ledger = ingress.ledger
    committed0 = ledger.total_tx_count()
    counts = {"offered": 0, "ok": 0, "full": 0, "deadline": 0, "other": 0}
    lat: list[float] = []
    i = 0
    t0 = time.perf_counter()
    deadline = t0 + window_s
    while time.perf_counter() < deadline and i < len(txs):
        due = int((time.perf_counter() - t0) * rate)
        k = min(due - counts["offered"], len(txs) - i, 256)
        if k <= 0:
            time.sleep(0.002)
            continue
        batch = txs[i:i + k]
        i += k
        ts = time.perf_counter()
        results = ingress.txpool.submit_batch(batch)
        lat.append(time.perf_counter() - ts)
        counts["offered"] += len(batch)
        for r in results:
            if r.status == TransactionStatus.OK:
                counts["ok"] += 1
            elif r.status == TransactionStatus.TXPOOL_FULL:
                counts["full"] += 1
            elif r.status == TransactionStatus.DEADLINE_UNMEETABLE:
                counts["deadline"] += 1
            else:
                counts["other"] += 1
    wall = time.perf_counter() - t0
    committed = ledger.total_tx_count() - committed0
    lat.sort()

    def pct(p):
        return lat[min(len(lat) - 1, int(p * len(lat)))] if lat else 0.0

    after = _txpool_drop_counters()
    return {
        **counts,
        "wall_seconds": round(wall, 3),
        "offered_tps": round(counts["offered"] / wall, 1),
        "committed_tps": round(committed / wall, 1),
        "shed_rate": round((counts["full"] + counts["deadline"])
                           / max(1, counts["offered"]), 4),
        "admission_call_p50_ms": round(pct(0.50) * 1000, 2),
        "admission_call_p99_ms": round(pct(0.99) * 1000, 2),
        "drops": {k: after[k] - before[k] for k in after},
    }


def _drain(ingress, timeout: float = 60.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if ingress.txpool.pending_count() == 0:
            return True
        time.sleep(0.1)
    return False


def run_overload_ladder(sm: bool, backend: str, tx_count_limit: int,
                        n_cap: int, window_s: float,
                        mults=(1, 2, 4)) -> list:
    """Capacity calibration + the 1x/2x/4x open-loop overload ladder on
    ONE plane-enabled 4-node chain (the pool drains between windows)."""
    from fisco_bcos_tpu.protocol import Transaction

    nodes, gateways, _ = _build_chain(sm, backend, tx_count_limit,
                                      cfg_overrides=_overload_cfg(True))
    ingress = nodes[0]
    rows = []
    try:
        for node in nodes:
            node.start()
        # capacity: closed-loop chunked burst, committed TPS
        print(f"overload: calibrating capacity ({n_cap} txs)...",
              file=sys.stderr, flush=True)
        cap_wire = _build_workload(sm, n_cap, block_limit=600,
                                   prefix="cap")
        t0 = time.perf_counter()
        admitted = 0
        for s in range(0, len(cap_wire), 512):
            results = ingress.txpool.submit_batch(
                [Transaction.decode(raw) for raw in cap_wire[s:s + 512]])
            admitted += sum(1 for r in results if int(r.status) == 0)
        # wait for what was ADMITTED, not n_cap: a large -n can cross the
        # pool's watermarks during the burst and shed the tail — that is
        # the plane working, not a wedged chain
        deadline = time.monotonic() + max(120.0, n_cap / 25)
        while time.monotonic() < deadline:
            if ingress.ledger.total_tx_count() >= admitted:
                break
            time.sleep(0.05)
        cap_wall = time.perf_counter() - t0
        committed = ingress.ledger.total_tx_count()
        if committed == 0 or committed < admitted // 2:
            raise RuntimeError(
                f"calibration wedged at {committed}/{admitted} admitted"
                f" ({n_cap} offered)")
        capacity = committed / cap_wall
        print(f"overload: measured capacity ~{capacity:.0f} TPS",
              file=sys.stderr, flush=True)

        base_tps = None
        offset = 0
        for mult in mults:
            rate = capacity * mult
            n_m = int(rate * window_s * 1.15) + 64
            print(f"overload: {mult}x window ({n_m} txs @ "
                  f"{rate:.0f}/s)...", file=sys.stderr, flush=True)
            wire = _build_workload(sm, n_m, block_limit=600,
                                   start=offset, prefix=f"ov{mult}")
            offset += n_m
            committed0 = ingress.ledger.total_tx_count()
            t_ep = time.perf_counter()
            win = _open_loop_window(ingress, wire, rate, window_s)
            drained = _drain(ingress)
            # SUSTAINED goodput: committed over the whole episode
            # (window + backlog drain) — under overload the pool keeps
            # the pipeline fed past the window, and shed load must not
            # depress what actually commits per second of episode
            elapsed = time.perf_counter() - t_ep
            sustained = (ingress.ledger.total_tx_count() - committed0) \
                / max(elapsed, 1e-9)
            if base_tps is None:
                base_tps = sustained
            rows.append({
                "metric": "overload_goodput",
                "suite": "sm" if sm else "ecdsa",
                "mult": mult,
                "capacity_tps": round(capacity, 1),
                "value": round(sustained, 1), "unit": "tx/sec",
                "goodput_vs_1x": round(sustained / max(base_tps, 0.001),
                                       3),
                "episode_seconds": round(elapsed, 3),
                "drained": drained,
                **win,
            })
        # the plane's hard guarantee, checked over EVERY committed block
        expired_sealed = _expired_in_committed_blocks(ingress.ledger)
        rows.append({
            "metric": "overload_seal_integrity",
            "suite": "sm" if sm else "ecdsa",
            "value": expired_sealed, "unit": "txs",
            "blocks_scanned": ingress.ledger.current_number(),
            "expired_after_seal_slot": expired_sealed,
        })
    finally:
        for node in nodes:
            node.stop()
        for gw in set(gateways):
            gw.stop()
    return rows


def run_overload_ab(sm: bool, backend: str, tx_count_limit: int,
                    capacity: float, window_s: float, reps: int) -> dict:
    """Interleaved plane-off/plane-on 1x open-loop runs (fresh chain per
    run) -> medians + the plane's measured cost at unsaturated load."""
    from fisco_bcos_tpu.protocol import Transaction  # noqa: F401

    results: dict[bool, list[float]] = {False: [], True: []}
    offset = 100_000  # nonce namespace away from the ladder's
    for rep in range(reps):
        for plane in (False, True):
            nodes, gateways, _ = _build_chain(
                sm, backend, tx_count_limit,
                cfg_overrides=_overload_cfg(plane))
            try:
                for node in nodes:
                    node.start()
                n_m = int(capacity * window_s * 1.15) + 64
                wire = _build_workload(sm, n_m, block_limit=600,
                                       start=offset,
                                       prefix=f"ab{rep}{int(plane)}")
                offset += n_m
                win = _open_loop_window(nodes[0], wire, capacity,
                                        window_s)
                results[plane].append(win["committed_tps"])
            finally:
                for node in nodes:
                    node.stop()
                for gw in set(gateways):
                    gw.stop()

    def med(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2] if vals else 0.0

    on, off = med(results[True]), med(results[False])
    return {
        "metric": "overload_ab", "unit": "x",
        "suite": "sm" if sm else "ecdsa",
        "value": round(on / max(off, 0.001), 3),
        "tps_plane_on_median": on, "tps_plane_off_median": off,
        "tps_plane_on_runs": results[True],
        "tps_plane_off_runs": results[False],
        "plane_cost_pct": round((1.0 - on / max(off, 0.001)) * 100, 2),
        "runs": reps,
    }


def run_lockcheck_ab(sm: bool, n: int, backend: str, tx_count_limit: int,
                     reps: int) -> dict:
    """Disarmed-lockcheck cost on the direct-ingest path.

    The disarmed plane's ONLY steady-state residue is the
    `note_blocking()` markers on the blocking call sites (the lock
    factories hand out plain threading primitives at construction, so
    armed-vs-absent differs by literally nothing at runtime for the
    locks themselves). A vs B, INTERLEAVED with fresh chains:

      A = the committed tree (markers live, checker disarmed)
      B = markers stubbed to a bare no-op (the plane-absent anchor)

    plus a micro-measurement of the disarmed marker crossing in ns.
    The acceptance bar is <1% on the A/B medians."""
    from fisco_bcos_tpu.analysis import lockcheck

    assert not lockcheck.armed(), \
        "lockcheck A/B must run DISARMED (unset BCOS_LOCKCHECK)"
    # micro: ns per disarmed crossing
    loops = 500_000
    t0 = time.perf_counter()
    for _ in range(loops):
        lockcheck.note_blocking("fsync")
    marker_ns = (time.perf_counter() - t0) / loops * 1e9

    results: dict[str, list[float]] = {"markers": [], "stubbed": []}
    real = lockcheck.note_blocking
    run_chain(sm, min(n, 300), backend, tx_count_limit)  # warm-up,
    #   discarded: first-run compile/alloc noise lands on neither side
    for _rep in range(reps):
        for mode in ("markers", "stubbed"):
            lockcheck.note_blocking = (
                real if mode == "markers" else (lambda *a, **k: None))
            try:
                row = run_chain(sm, n, backend, tx_count_limit)
            finally:
                lockcheck.note_blocking = real
            results[mode].append(row["tps"])

    def med(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2] if vals else 0.0

    with_m, without = med(results["markers"]), med(results["stubbed"])
    return {
        "metric": "lockcheck_ab", "unit": "x",
        "suite": "sm" if sm else "ecdsa",
        "value": round(with_m / max(without, 0.001), 3),
        "tps_markers_median": with_m, "tps_stubbed_median": without,
        "tps_markers_runs": results["markers"],
        "tps_stubbed_runs": results["stubbed"],
        "disarmed_cost_pct": round(
            (1.0 - with_m / max(without, 0.001)) * 100, 2),
        "marker_ns_per_crossing": round(marker_ns, 1),
        "runs": reps,
    }


def run_columnar_compare(sm: bool, n: int, backend: str,
                         tx_count_limit: int, reps: int = 3) -> dict:
    """Object-path vs columnar wire ingest, interleaved in ONE session.

    Both arms start from the same pre-signed wire frames and drive a
    fresh solo chain through the txpool's batch door; the ONLY variable
    is the substrate the door runs on:

      object:   `Transaction.decode` each frame, `submit_batch` — the
                per-tx marshalling the PR-16 attribution blamed for the
                ~0.19 ms-GIL-per-tx ceiling (per-field bytes copies,
                per-tx hash/encode, list-of-int limb packing);
      columnar: `decode_columns` + `submit_columns` — one arena, offset
                arrays, ONE `hash_batch`/`recover_addresses` over arena
                slices, `TxView`s only for rows that admit.

    Decode cost sits INSIDE the timed window for both arms — wire bytes
    in, committed txs out is the contract being compared. Run-to-run
    drift on the 2-core CI host dwarfs the effect, so the honest
    statistic is the median of adjacent-pair ratios (same discipline as
    profiler_overhead_ab), alternating which arm goes first."""
    import gc

    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.protocol.columnar import decode_columns

    blocks_needed = -(-n // max(1, tx_count_limit))
    block_limit = min(600, max(100, 2 * blocks_needed + 20))
    print(f"signing {n} txs (excluded from every timed window)...",
          file=sys.stderr, flush=True)
    wire_txs = _build_workload(sm, n, block_limit=block_limit,
                               prefix="cc")

    def solo_run(columnar: bool) -> tuple[float, int]:
        node = Node(NodeConfig(
            consensus="solo", sm_crypto=sm, crypto_backend=backend,
            min_seal_time=0.0, tx_count_limit=tx_count_limit,
            trace_sample_rate=0.0, trace_slow_ms=0.0))
        node.start()
        try:
            t0 = time.perf_counter()
            for s in range(0, len(wire_txs), 512):
                chunk = wire_txs[s:s + 512]
                if columnar:
                    node.txpool.submit_columns(decode_columns(chunk))
                else:
                    node.txpool.submit_batch(
                        [Transaction.decode(raw) for raw in chunk])
            deadline = time.monotonic() + max(120.0, n / 25)
            while time.monotonic() < deadline:
                if node.ledger.total_tx_count() >= n:
                    break
                time.sleep(0.02)
            t1 = time.perf_counter()
            committed = node.ledger.total_tx_count()
        finally:
            node.stop()
        return committed / max(1e-9, t1 - t0), committed

    results: dict[str, list[float]] = {"object": [], "columnar": []}
    ratios: list[float] = []
    committed_min = n
    solo_run(False)  # warm-up, discarded (compile/alloc noise lands on
    #                  neither side)
    for rep in range(reps):
        order = ("object", "columnar") if rep % 2 == 0 \
            else ("columnar", "object")
        pair = {}
        for mode in order:
            gc.collect()
            tps, committed = solo_run(mode == "columnar")
            results[mode].append(tps)
            pair[mode] = tps
            committed_min = min(committed_min, committed)
        ratios.append(pair["columnar"] / max(pair["object"], 0.001))

    obj = statistics.median(results["object"])
    col = statistics.median(results["columnar"])
    return {
        "metric": "columnar_tps", "unit": "tx/sec",
        "suite": "sm" if sm else "ecdsa",
        "value": round(col, 1),
        "tps_columnar_median": round(col, 1),
        "tps_object_median": round(obj, 1),
        # headline ratio: median of adjacent-pair ratios, NOT the ratio
        # of cross-run medians (drift-honest, same as the profiler A/B)
        "columnar_vs_object": round(statistics.median(ratios), 3),
        "pair_ratios": [round(r, 3) for r in ratios],
        "tps_columnar_runs": [round(v, 1) for v in results["columnar"]],
        "tps_object_runs": [round(v, 1) for v in results["object"]],
        "n": n, "runs": reps,
        "timed_out": committed_min < n,
    }


def run_profile_attrib(sm: bool, backend: str, n: int = 1500,
                       tx_count_limit: int = 1000, reps: int = 2) -> list:
    """GIL-holder attribution + profiler self-cost on the direct solo
    ingest path — the instrument for PERF r10's ~0.19 ms-GIL-per-tx
    ceiling (ROADMAP item 1 needs the FUNCTION names, not the total).

    Two measurements, one invocation:

      1. attribution A/B, same session: solo chain, profiler armed at a
         high-resolution hz, `n` txs submitted direct — ONCE through the
         object door (Transaction.decode + submit_batch) and once
         through the columnar door (decode_columns + submit_columns).
         Process CPU is measured independently via getrusage; the
         profiler must attribute >= 80% of it to named functions/stages
         or the summary row says so. Emits the top-GIL-holders table per
         stage for both paths and the recover_share_ab row — the
         "recover call-site share collapses under the columnar
         substrate" acceptance number.
      2. interleaved A/B: the ALWAYS-ON default hz vs disarmed (no
         sampler thread), `reps` runs each, fresh chain per run, medians
         — the < 3% self-overhead acceptance row.
    """
    import resource

    from fisco_bcos_tpu.analysis import profiler as prof
    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.protocol.columnar import decode_columns

    blocks_needed = -(-n // max(1, tx_count_limit))
    block_limit = min(600, max(100, 2 * blocks_needed + 20))
    print(f"signing {n} txs (excluded from every timed window)...",
          file=sys.stderr, flush=True)
    wire_txs = _build_workload(sm, n, block_limit=block_limit,
                               prefix="pa")

    def solo_run(profile_hz: float) -> tuple[float, int]:
        """One fresh solo chain, direct-ingest `n` txs -> (tps, committed).
        The profiler state is whatever `profile_hz` arms (0 = disarmed,
        no sampler thread — the plane-absent anchor)."""
        node = Node(NodeConfig(
            consensus="solo", sm_crypto=sm, crypto_backend=backend,
            min_seal_time=0.0, tx_count_limit=tx_count_limit,
            trace_sample_rate=0.0, trace_slow_ms=0.0,
            profile_hz=profile_hz, profile_burst_hz=0.0))
        txs = [Transaction.decode(raw) for raw in wire_txs]
        node.start()
        try:
            t0 = time.perf_counter()
            for s in range(0, len(txs), 512):
                node.txpool.submit_batch(txs[s:s + 512])
            deadline = time.monotonic() + max(120.0, n / 25)
            while time.monotonic() < deadline:
                if node.ledger.total_tx_count() >= n:
                    break
                time.sleep(0.02)
            t1 = time.perf_counter()
            committed = node.ledger.total_tx_count()
        finally:
            node.stop()
        return committed / max(1e-9, t1 - t0), committed

    rows = []
    suite_name = "sm" if sm else "ecdsa"

    # -- 1) attribution A/B (high-res sampling + independent CPU meter),
    #       object door then columnar door, same session -----------------
    def attrib_run(columnar: bool) -> dict:
        node = Node(NodeConfig(
            consensus="solo", sm_crypto=sm, crypto_backend=backend,
            min_seal_time=0.0, tx_count_limit=tx_count_limit,
            trace_sample_rate=0.0, trace_slow_ms=0.0,
            profile_hz=53.0, profile_ring=4096, profile_burst_hz=0.0))
        node.start()
        try:
            prof.PROFILER.reset()
            ru0 = resource.getrusage(resource.RUSAGE_SELF)
            t0 = time.perf_counter()
            for s in range(0, len(wire_txs), 512):
                chunk = wire_txs[s:s + 512]
                if columnar:
                    node.txpool.submit_columns(decode_columns(chunk))
                else:
                    node.txpool.submit_batch(
                        [Transaction.decode(raw) for raw in chunk])
            deadline = time.monotonic() + max(120.0, n / 25)
            while time.monotonic() < deadline:
                if node.ledger.total_tx_count() >= n:
                    break
                time.sleep(0.02)
            t1 = time.perf_counter()
            ru1 = resource.getrusage(resource.RUSAGE_SELF)
            committed = node.ledger.total_tx_count()
            attrib = prof.PROFILER.attribution()
        finally:
            node.stop()
        # measured GIL-held CPU: whole-process rusage over the window,
        # minus the sampler's own measured burn (overhead, not workload)
        cpu_s = (ru1.ru_utime - ru0.ru_utime) + \
            (ru1.ru_stime - ru0.ru_stime)
        workload_cpu = max(1e-9, cpu_s - attrib["profiler_cpu_seconds"])
        return {
            "attrib": attrib, "committed": committed,
            "tps": committed / max(1e-9, t1 - t0),
            "workload_cpu": workload_cpu,
            # the recover call-site share: every attributed leaf that is
            # a recover entry point (nativeec/suite, ecdsa or sm2) — the
            # per-tx marshalling PR 16 measured at ~58% on the object
            # path, which the columnar door exists to collapse
            "recover": sum(r["cpu_seconds"] for r in attrib["rows"]
                           if "recover" in r["func"]),
            # the event-driven-sealer acceptance number: attributed CPU
            # with the sealer thread sitting in threading-wait — PR 16's
            # table put 15.4% of the GIL budget here with the 0.02 s
            # idle poll; wakeup-driven sealing collapses this row
            "seal_wait": sum(r["cpu_seconds"] for r in attrib["rows"]
                             if r["role"] == "seal"
                             and r["func"] == "threading.py:wait"),
        }

    runs = {"object": attrib_run(False), "columnar": attrib_run(True)}
    for path, a in runs.items():
        committed, workload_cpu = a["committed"], a["workload_cpu"]
        attrib = a["attrib"]
        attributed = attrib["attributed_cpu_seconds"]
        for r in attrib["rows"][:12]:
            rows.append({
                "metric": "profile_attrib", "unit": "ms/tx",
                "suite": suite_name, "path": path,
                "role": r["role"], "stage": r["stage"], "func": r["func"],
                "cpu_ms_per_tx": round(1000.0 * r["cpu_seconds"]
                                       / max(1, committed), 4),
                "cpu_share_pct": round(100.0 * r["cpu_seconds"]
                                       / workload_cpu, 1),
            })
        rows.append({
            "metric": "profile_attrib_summary", "unit": "ms/tx",
            "suite": suite_name, "path": path, "txs": int(committed),
            "tps": round(a["tps"], 1),
            "gil_ms_per_tx": round(1000.0 * workload_cpu
                                   / max(1, committed), 4),
            "attributed_ms_per_tx": round(1000.0 * attributed
                                          / max(1, committed), 4),
            # the >= 80% acceptance number: named-function coverage of
            # the measured per-tx CPU (independent meters — rusage vs
            # /proc scan)
            "attributed_pct": round(100.0 * attributed / workload_cpu, 1),
            "seal_wait_share_pct": round(100.0 * a["seal_wait"]
                                         / workload_cpu, 1),
            "profiler_cpu_seconds": attrib["profiler_cpu_seconds"],
            "samples": attrib["samples"],
            "by_stage_ms_per_tx": {
                k: round(1000.0 * v / max(1, committed), 4)
                for k, v in list(attrib["by_stage"].items())[:8]},
        })
    obj, col = runs["object"], runs["columnar"]
    rows.append({
        # the tentpole acceptance row: what happened to the per-tx GIL
        # budget and the recover call-site share when the SAME wire
        # frames went through the columnar door instead — one process,
        # back-to-back, same profiler, same CPU meter
        "metric": "recover_share_ab", "unit": "pct",
        "suite": suite_name,
        "object_recover_share_pct": round(
            100.0 * obj["recover"] / obj["workload_cpu"], 1),
        "columnar_recover_share_pct": round(
            100.0 * col["recover"] / col["workload_cpu"], 1),
        "object_gil_ms_per_tx": round(
            1000.0 * obj["workload_cpu"] / max(1, obj["committed"]), 4),
        "columnar_gil_ms_per_tx": round(
            1000.0 * col["workload_cpu"] / max(1, col["committed"]), 4),
        # 1 / (GIL ms per tx): the solo per-process ceiling each
        # substrate implies, independent of this run's wall-clock noise
        "object_implied_ceiling_tps": round(
            obj["committed"] / max(1e-9, obj["workload_cpu"]), 0),
        "columnar_implied_ceiling_tps": round(
            col["committed"] / max(1e-9, col["workload_cpu"]), 0),
        "object_tps": round(obj["tps"], 1),
        "columnar_tps_run": round(col["tps"], 1),
    })

    # -- 2) interleaved A/B: always-on default hz vs no sampler thread -----
    import gc

    results: dict[str, list[float]] = {"armed": [], "disarmed": []}
    ratios: list[float] = []
    solo_run(0.0)  # warm-up, discarded (compile/alloc noise lands on
    #                neither side)
    for rep in range(reps):
        # alternate which side goes first, and compare WITHIN each rep
        # pair: the documented run-to-run drift on this host (PERF r10's
        # 1.45x swings, plus monotonic allocator growth inside one
        # process) is far larger than the effect under test, so the
        # honest statistic is the median of adjacent-pair ratios, not a
        # ratio of cross-run medians
        order = ("armed", "disarmed") if rep % 2 == 0 \
            else ("disarmed", "armed")
        pair = {}
        for mode in order:
            gc.collect()
            tps, _ = solo_run(5.0 if mode == "armed" else 0.0)
            results[mode].append(tps)
            pair[mode] = tps
        ratios.append(pair["armed"] / max(pair["disarmed"], 0.001))

    def med(vals):
        # true median: an upper-element pick on even run counts would
        # systematically report the more favorable pair ratio
        return statistics.median(vals) if vals else 0.0

    value = med(ratios)
    rows.append({
        "metric": "profiler_overhead_ab", "unit": "x",
        "suite": suite_name, "value": round(value, 3),
        "pair_ratios": [round(r, 3) for r in ratios],
        "tps_armed_median": round(med(results["armed"]), 1),
        "tps_disarmed_median": round(med(results["disarmed"]), 1),
        "tps_armed_runs": [round(v, 1) for v in results["armed"]],
        "tps_disarmed_runs": [round(v, 1) for v in results["disarmed"]],
        "overhead_pct": round((1.0 - value) * 100, 2),
        "hz": 5.0, "runs": reps,
    })
    return rows


def run_overload_fairness(sm: bool, backend: str, tx_count_limit: int,
                          capacity: float, fairness_s: float) -> dict:
    """Aggressor vs polite through the REAL RPC edge with per-client
    token buckets: 10:1 offered load, distinct x-api-key identities.
    Reports the polite client's committed blockspace share, its commit
    p99, the -32005 count and the reject-answer p99."""
    import threading

    from fisco_bcos_tpu.protocol import Transaction  # noqa: F401
    from fisco_bcos_tpu.sdk.client import RpcCallError, SdkClient

    # per-client write rate: a third of capacity each (capped low enough
    # that the HTTP aggressor threads can actually exceed it) — the chain
    # can absorb both clients at full budget, the aggressor's excess
    # cannot get in
    rate = max(20.0, min(capacity / 3.0, 80.0))
    polite_rate = 0.8 * rate
    nodes, gateways, _ = _build_chain(
        sm, backend, tx_count_limit, rpc_on_first=True,
        min_seal_time=0.2,
        cfg_overrides={**_overload_cfg(True),
                       "client_write_rate": rate})
    ingress = nodes[0]
    n_polite = int(polite_rate * fairness_s) + 16
    n_aggr = int(rate * fairness_s * 3) + 64  # cycles through on rejects
    print(f"overload: fairness mix (rate={rate:.0f}/client, "
          f"{n_aggr}+{n_polite} txs)...", file=sys.stderr, flush=True)
    aggr_wire = _build_workload(sm, n_aggr, block_limit=600, prefix="fa")
    pol_wire = _build_workload(sm, n_polite, block_limit=600, prefix="fp")
    try:
        for node in nodes:
            node.start()
        url = f"http://{ingress.rpc.host}:{ingress.rpc.port}"
        stop = threading.Event()
        stats = {"aggr_sent": 0, "aggr_ok": 0, "aggr_32005": 0,
                 "errors": []}
        reject_lat: list[float] = []
        pol_submits: dict[bytes, float] = {}
        pol_lock = threading.Lock()

        stats_lock = threading.Lock()

        def aggressor(worker: int, workers: int = 4):
            # several threads under ONE api-key identity: the offered
            # load must exceed the per-client bucket, which a single
            # synchronous HTTP loop cannot on this host
            sdk = SdkClient(url, api_key="aggr")
            i = worker
            while not stop.is_set():
                tx_hex = "0x" + aggr_wire[i % len(aggr_wire)].hex()
                i += workers
                t0 = time.perf_counter()
                try:
                    sdk.request("sendTransaction",
                                ["group0", "", tx_hex, False, False])
                    with stats_lock:
                        stats["aggr_sent"] += 1
                        stats["aggr_ok"] += 1
                except RpcCallError as exc:
                    with stats_lock:
                        stats["aggr_sent"] += 1
                        if exc.code == -32005:
                            stats["aggr_32005"] += 1
                    # admitted-duplicate and pool statuses: still offered
                    del t0  # latency measured by the paced prober
                except Exception as exc:  # noqa: BLE001
                    stats["errors"].append(f"aggr: {exc}")
                    return

        def polite():
            from fisco_bcos_tpu.protocol import Transaction as _Tx
            sdk = SdkClient(url, api_key="polite")
            t0 = time.perf_counter()
            for i, raw in enumerate(pol_wire):
                if stop.is_set():
                    return
                # paced open loop at 0.8x its budget: never throttled
                due = t0 + i / polite_rate
                lag = due - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                h = _Tx.decode(raw).hash(ingress.suite)
                try:
                    sdk.request("sendTransaction",
                                ["group0", "", "0x" + raw.hex(),
                                 False, False])
                    with pol_lock:
                        pol_submits[h] = time.perf_counter()
                except Exception as exc:  # noqa: BLE001
                    stats["errors"].append(f"polite: {exc}")
                    return

        def reject_prober():
            # paced probe under the AGGRESSOR's identity: once its bucket
            # is drained, every probe answers -32005 — this measures the
            # edge's reject-answer latency without the aggressor threads'
            # own client-side CPU starvation polluting the number
            sdk = SdkClient(url, api_key="aggr")
            tx_hex = "0x" + aggr_wire[0].hex()
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    sdk.request("sendTransaction",
                                ["group0", "", tx_hex, False, False])
                except RpcCallError as exc:
                    if exc.code == -32005:
                        reject_lat.append(time.perf_counter() - t0)
                except Exception:  # noqa: BLE001 — probe only
                    return
                time.sleep(0.05)

        pol_commit_lat: list[float] = []

        def pol_watcher():
            outstanding: dict[bytes, float] = {}
            while not stop.is_set() or outstanding:
                with pol_lock:
                    outstanding.update(pol_submits)
                    pol_submits.clear()
                done = []
                for h, ts in outstanding.items():
                    if ingress.ledger.receipt(h) is not None:
                        pol_commit_lat.append(time.perf_counter() - ts)
                        done.append(h)
                for h in done:
                    outstanding.pop(h)
                if stop.is_set() and not done:
                    break  # drain attempt after the window: stop polling
                time.sleep(0.05)

        h0 = ingress.ledger.current_number()
        threads = [threading.Thread(target=aggressor, args=(w,),
                                    daemon=True) for w in range(4)]
        threads += [threading.Thread(target=fn, daemon=True)
                    for fn in (polite, pol_watcher, reject_prober)]
        for th in threads:
            th.start()
        time.sleep(fairness_s)
        stop.set()
        for th in threads:
            th.join(timeout=30)
        if stats["errors"]:
            raise RuntimeError(stats["errors"][0])
        time.sleep(1.0)  # let in-flight commits land before the scan
        # committed blockspace share by nonce prefix over the window
        aggr_c = pol_c = 0
        for n in range(h0 + 1, ingress.ledger.current_number() + 1):
            blk = ingress.ledger.block_by_number(n, with_txs=True)
            if blk is None:
                continue
            for t in blk.transactions:
                if t.nonce.startswith("fa-"):
                    aggr_c += 1
                elif t.nonce.startswith("fp-"):
                    pol_c += 1
        reject_lat.sort()
        pol_commit_lat.sort()

        def pct(vals, p):
            return vals[min(len(vals) - 1, int(p * len(vals)))] \
                if vals else 0.0

        return {
            "metric": "overload_fairness", "unit": "share",
            "suite": "sm" if sm else "ecdsa",
            "value": round(pol_c / max(1, aggr_c + pol_c), 3),
            "polite_share": round(pol_c / max(1, aggr_c + pol_c), 3),
            "polite_committed": pol_c, "aggressor_committed": aggr_c,
            "polite_commit_p50_ms": round(
                pct(pol_commit_lat, 0.5) * 1000, 1),
            "polite_commit_p99_ms": round(
                pct(pol_commit_lat, 0.99) * 1000, 1),
            "aggr_offered": stats["aggr_sent"],
            "aggr_admitted": stats["aggr_ok"],
            "rate_limited_count": stats["aggr_32005"],
            "reject_p99_ms": round(pct(reject_lat, 0.99) * 1000, 2),
            "client_write_rate": rate,
        }
    finally:
        for node in nodes:
            node.stop()
        for gw in set(gateways):
            gw.stop()


def _emit_overload_mode(args, sm: bool) -> None:
    rows = run_overload_ladder(sm, args.backend, args.tx_count_limit,
                               max(500, args.n),
                               args.overload_window)
    capacity = rows[0]["capacity_tps"]
    for row in rows:
        print(_dumps(row), flush=True)
    ab = run_overload_ab(sm, args.backend, args.tx_count_limit, capacity,
                         args.overload_window, args.overload_ab_runs)
    print(_dumps(ab), flush=True)
    fair = run_overload_fairness(sm, args.backend, args.tx_count_limit,
                                 capacity, args.overload_fairness_s)
    print(_dumps(fair), flush=True)


# -- scenario mode (ISSUE 17: production-shaped load) ------------------------

def _scenario_spec(args, cross_dest: str = ""):
    from fisco_bcos_tpu.testing.scenario import ScenarioSpec
    return ScenarioSpec(
        name=args.scenario, accounts=args.scenario_accounts,
        hot_share=args.hot_share, cross_share=args.cross_share,
        value_bytes=args.value_bytes, cross_dest=cross_dest)


def _receipt_watcher(ledger, suite, txs, pending, pending_lock, stop):
    """Resolve sampled submit->commit latencies; returns sorted list."""
    from fisco_bcos_tpu.protocol import batch_hash

    hashes = batch_hash(txs, suite)
    resolved: list[float] = []

    def loop():
        outstanding: dict[int, float] = {}
        grace_until = None
        while True:
            with pending_lock:
                outstanding.update(pending)
                pending.clear()
            done = [k for k, ts in outstanding.items()
                    if ledger.receipt(hashes[k]) is not None]
            for k in done:
                resolved.append(time.perf_counter() - outstanding.pop(k))
            if stop.is_set():
                if not outstanding:
                    return
                if grace_until is None:
                    grace_until = time.monotonic() + 15.0
                elif time.monotonic() > grace_until:
                    return  # drain grace expired; samples stay partial
            time.sleep(0.05)

    return resolved, loop


def run_scenario(sm: bool, backend: str, tx_count_limit: int,
                 args) -> dict:
    """One production-shaped scenario, open-loop Poisson at
    `--scenario-intensity` times the chain's measured capacity, against
    a 4-node PBFT chain on the DISK backend (key pages + leveled
    compaction on their defaults — the deployment shape)."""
    import shutil
    import tempfile
    import threading

    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.testing import scenario as sc

    spec = _scenario_spec(args)
    work = tempfile.mkdtemp(prefix=f"scenario-{spec.name}-")
    nodes, gateways, _ = _build_chain(
        sm, backend, tx_count_limit,
        cfg_overrides={**_overload_cfg(True), "storage_backend": "disk",
                       "storage_path": work,
                       "storage_memtable_mb": args.scenario_memtable_mb})
    ingress = nodes[0]
    try:
        # pre-fund the account space by direct injection on EVERY node
        # (identical rows, changeset-delta state roots: consensus-safe)
        funded = 0
        for node in nodes:
            funded = sc.prefund_storage(node.storage, spec)
        print(f"scenario {spec.name}: pre-funded {funded} rows/node",
              file=sys.stderr, flush=True)
        for node in nodes:
            node.start()

        # capacity calibration: closed-loop burst of the SAME shape
        n_cap = max(400, args.n // 2)
        print(f"scenario {spec.name}: calibrating capacity "
              f"({n_cap} txs)...", file=sys.stderr, flush=True)
        cap_wire = sc.sign_workload(spec, sm, n_cap, block_limit=600)
        t0 = time.perf_counter()
        admitted = 0
        for s in range(0, len(cap_wire), 256):
            results = ingress.txpool.submit_batch(
                [Transaction.decode(raw) for raw in cap_wire[s:s + 256]])
            admitted += sum(1 for r in results if int(r.status) == 0)
        deadline = time.monotonic() + max(120.0, n_cap / 20)
        while time.monotonic() < deadline:
            if ingress.ledger.total_tx_count() >= admitted:
                break
            time.sleep(0.05)
        cap_wall = time.perf_counter() - t0
        committed = ingress.ledger.total_tx_count()
        if committed < max(1, admitted // 2):
            raise RuntimeError(
                f"scenario calibration wedged at {committed}/{admitted}")
        capacity = committed / cap_wall
        rate = capacity * args.scenario_intensity

        window_s = args.scenario_window
        n_w = int(rate * window_s * 1.3) + 64
        print(f"scenario {spec.name}: capacity ~{capacity:.0f} TPS, "
              f"window {n_w} txs @ {rate:.0f}/s...",
              file=sys.stderr, flush=True)
        wire = sc.sign_workload(spec, sm, n_w, block_limit=600,
                                start=n_cap)
        txs = [Transaction.decode(raw) for raw in wire]

        pending: dict[int, float] = {}
        pending_lock = threading.Lock()
        stop = threading.Event()
        resolved, watch_loop = _receipt_watcher(
            ingress.ledger, ingress.suite, txs, pending, pending_lock,
            stop)
        watcher = threading.Thread(target=watch_loop, daemon=True)
        watcher.start()

        def submit(batch):
            results = ingress.txpool.submit_batch(batch)
            return sum(1 for r in results if int(r.status) == 0)

        def on_sample(k, t_sub):
            with pending_lock:
                pending[k] = t_sub

        committed0 = ingress.ledger.total_tx_count()
        t_ep = time.perf_counter()
        win = sc.open_loop_poisson(submit, txs, rate, window_s,
                                   seed=spec.seed, on_sample=on_sample)
        drained = _drain(ingress)
        stop.set()
        watcher.join(timeout=30)
        elapsed = time.perf_counter() - t_ep
        sustained = (ingress.ledger.total_tx_count() - committed0) \
            / max(elapsed, 1e-9)
        lat = sorted(resolved)

        def pct(p):
            return lat[min(len(lat) - 1, int(p * len(lat)))] if lat \
                else 0.0

        st_stats = ingress.storage.stats()
        eng = st_stats.get("backend_stats", st_stats)
        storage_row = {
            "compaction_debt_bytes": eng.get("compaction_debt_bytes"),
            "levels": len(eng.get("levels", [])),
            "max_merge_secs": eng.get("max_merge_secs"),
            "key_page_size": st_stats.get("key_page_size"),
            "backend_reads": st_stats.get("backend_reads"),
            "cache_hits": st_stats.get("cache_hits"),
        }
        return {
            "metric": "scenario_" + spec.name.replace("-", "_"),
            "unit": "tx/sec", "suite": "sm" if sm else "ecdsa",
            "scenario": spec.name, "value": round(sustained, 1),
            "capacity_tps": round(capacity, 1),
            "intensity": args.scenario_intensity,
            "accounts": spec.accounts,
            "prefunded_rows": funded,
            "write_p50_ms": round(pct(0.50) * 1000, 1),
            "write_p99_ms": round(pct(0.99) * 1000, 1),
            "latency_samples": len(lat),
            "episode_seconds": round(elapsed, 3),
            "drained": drained,
            "storage": storage_row,
            **win,
        }
    finally:
        for node in nodes:
            node.stop()
        for gw in set(gateways):
            gw.stop()
        shutil.rmtree(work, ignore_errors=True)


def run_scenario_xshard(sm: bool, backend: str, tx_count_limit: int,
                        args) -> dict:
    """xshard-heavy: two solo groups in one process (GroupManager, the
    multi-group deployment shape), each fed open-loop Poisson arrivals
    where `--cross-share` of them are cross-group transferOut legs;
    reports goodput, write p99, and the settlement drain."""
    import threading

    from fisco_bcos_tpu.executor import precompiled as pc
    from fisco_bcos_tpu.init.group import GroupManager
    from fisco_bcos_tpu.init.node import NodeConfig
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.storage.memory import MemoryStorage
    from fisco_bcos_tpu.testing import scenario as sc

    gids = ["group0", "group1"]
    mgr = GroupManager(storage=MemoryStorage())
    nodes = {gid: mgr.add_group(NodeConfig(
        group_id=gid, consensus="solo", sm_crypto=sm,
        crypto_backend=backend, min_seal_time=0.0,
        tx_count_limit=tx_count_limit, ingest_lane=False))
        for gid in gids}
    specs = {gid: _scenario_spec(args, cross_dest=gids[1 - g])
             for g, gid in enumerate(gids)}
    mgr.start()
    try:
        for gid in gids:
            sc.prefund_storage(nodes[gid].storage, specs[gid])

        # calibration: closed-loop burst on group0 only (groups are
        # symmetric; per-group rate = capacity * intensity)
        n_cap = max(300, args.n // 3)
        cap_wire = sc.sign_workload(specs["group0"], sm, n_cap,
                                    block_limit=600, group_id="group0")
        ing0 = nodes["group0"]
        t0 = time.perf_counter()
        admitted = 0
        for s in range(0, len(cap_wire), 256):
            results = ing0.txpool.submit_batch(
                [Transaction.decode(raw) for raw in cap_wire[s:s + 256]])
            admitted += sum(1 for r in results if int(r.status) == 0)
        deadline = time.monotonic() + max(120.0, n_cap / 20)
        while time.monotonic() < deadline:
            if ing0.ledger.total_tx_count() >= admitted:
                break
            time.sleep(0.05)
        capacity = ing0.ledger.total_tx_count() / (time.perf_counter()
                                                   - t0)
        rate = capacity * args.scenario_intensity
        window_s = args.scenario_window
        n_w = int(rate * window_s * 1.3) + 64
        print(f"scenario xshard-heavy: capacity ~{capacity:.0f} TPS/"
              f"group, {n_w} txs/group @ {rate:.0f}/s...",
              file=sys.stderr, flush=True)

        workload = {}
        for gid in gids:
            wire = sc.sign_workload(specs[gid], sm, n_w, block_limit=600,
                                    group_id=gid, start=n_cap)
            workload[gid] = [Transaction.decode(raw) for raw in wire]

        pending: dict[int, float] = {}
        pending_lock = threading.Lock()
        stop = threading.Event()
        resolved, watch_loop = _receipt_watcher(
            ing0.ledger, ing0.suite, workload["group0"], pending,
            pending_lock, stop)
        watcher = threading.Thread(target=watch_loop, daemon=True)
        watcher.start()
        wins: dict[str, dict] = {}
        committed0 = sum(nodes[g].ledger.total_tx_count() for g in gids)
        barrier = threading.Barrier(len(gids) + 1)

        def feeder(gid):
            node = nodes[gid]

            def submit(batch):
                results = node.txpool.submit_batch(batch)
                return sum(1 for r in results if int(r.status) == 0)

            on_sample = None
            if gid == "group0":
                def on_sample(k, t_sub):
                    with pending_lock:
                        pending[k] = t_sub
            barrier.wait()
            wins[gid] = sc.open_loop_poisson(
                submit, workload[gid], rate, window_s,
                seed=specs[gid].seed, on_sample=on_sample)

        threads = [threading.Thread(target=feeder, args=(gid,),
                                    daemon=True) for gid in gids]
        for th in threads:
            th.start()
        barrier.wait()
        t_ep = time.perf_counter()
        for th in threads:
            th.join(timeout=window_s + 120)
        drained = all(_drain(nodes[g]) for g in gids)
        t_clients = time.perf_counter()
        # settlement drain: every cross-group escrow finished everywhere
        deadline = time.monotonic() + 120.0
        settled = True
        while time.monotonic() < deadline:
            if sum(len(list(nodes[g].storage.keys(pc.T_XSHARD_PEND)))
                   for g in gids) == 0:
                break
            time.sleep(0.05)
        else:
            settled = False
        stop.set()
        watcher.join(timeout=30)
        t_end = time.perf_counter()
        committed = sum(nodes[g].ledger.total_tx_count()
                        for g in gids) - committed0
        lat = sorted(resolved)

        def pct(p):
            return lat[min(len(lat) - 1, int(p * len(lat)))] if lat \
                else 0.0

        coord = mgr.coordinator.stats() if mgr.coordinator else {}
        return {
            "metric": "scenario_xshard_heavy", "unit": "tx/sec",
            "suite": "sm" if sm else "ecdsa",
            "scenario": "xshard-heavy",
            "value": round(committed / max(t_clients - t_ep, 1e-9), 1),
            "capacity_tps": round(capacity, 1),
            "intensity": args.scenario_intensity,
            "cross_share": args.cross_share,
            "offered": sum(w["offered"] for w in wins.values()),
            "admitted": sum(w["admitted"] for w in wins.values()),
            "shed_rate": round(
                sum(w["shed"] for w in wins.values())
                / max(1, sum(w["offered"] for w in wins.values())), 4),
            "write_p50_ms": round(pct(0.50) * 1000, 1),
            "write_p99_ms": round(pct(0.99) * 1000, 1),
            "latency_samples": len(lat),
            "drained": drained, "settled": settled,
            "settle_drain_seconds": round(t_end - t_clients, 3),
            "cross_completed": coord.get("completed_total", 0),
            "cross_aborted": coord.get("aborted_total", 0),
        }
    finally:
        mgr.stop()


def _emit_scenario_mode(args, sm: bool) -> None:
    if args.scenario == "xshard-heavy":
        row = run_scenario_xshard(sm, args.backend, args.tx_count_limit,
                                  args)
    else:
        row = run_scenario(sm, args.backend, args.tx_count_limit, args)
    print(_dumps(row), flush=True)


# -- compaction-curve mode (ISSUE 17: GB-scale merge-cost growth) ------------

def run_compaction_curve(target_mb: int, memtable_mb: int,
                         value_kb: int, seg_mb: int = 8) -> list:
    """Max single-merge cost vs dataset size, leveled vs the full-merge
    baseline, measured by DRIVING compaction synchronously (auto_compact
    off — every merge's seconds/bytes are attributed exactly).

    The leveled engine's claim: a merge reads one source segment plus
    the overlapping slice of the next level, so max merge cost goes
    FLAT as the dataset grows. The baseline (an effectively infinite
    level-1 target, i.e. the old single-level engine: every compaction
    rewrites everything) grows linearly — both curves land in PERF.md.
    """
    import shutil
    import tempfile

    from fisco_bcos_tpu.storage.engine import DiskStorage

    rng = random.Random(17)
    value = rng.getrandbits(8 * value_kb * 1024).to_bytes(
        value_kb * 1024, "big")
    checkpoints = [mb for mb in (32, 64, 128, 256, 512, 1024, 2048)
                   if mb <= target_mb]
    if checkpoints[-1] != target_mb:
        checkpoints.append(target_mb)
    rows = []
    for mode in ("leveled", "full"):
        work = tempfile.mkdtemp(prefix=f"compact-curve-{mode}-")
        st = DiskStorage(
            work, memtable_bytes=memtable_mb << 20, max_segments=4,
            auto_compact=False,
            level_base_bytes=(1 << 60) if mode == "full"
            else 4 * (memtable_mb << 20),
            seg_target_bytes=seg_mb << 20)
        try:
            written = 0
            ckpt_iter = iter(checkpoints)
            ckpt = next(ckpt_iter)
            max_secs = max_in = 0.0
            merges = 0
            t_start = time.perf_counter()
            batch_rows = max(1, (2 << 20) // len(value))
            while written < target_mb << 20:
                batch = [(rng.getrandbits(128).to_bytes(16, "big"), value)
                         for _ in range(batch_rows)]
                st.set_batch("t_curve", batch)
                written += batch_rows * (len(value) + 16)
                while st.needs_compaction():
                    if not st.compact_once(force=False):
                        break
                    last = st.stats()["last_merge"]
                    merges += 1
                    max_secs = max(max_secs, last["secs"])
                    max_in = max(max_in, last["input_bytes"])
                if written >= ckpt << 20:
                    rows.append({
                        "metric": "compaction_curve", "unit": "sec",
                        "mode": mode, "dataset_mb": ckpt,
                        "value": round(max_secs, 3),
                        "max_merge_secs": round(max_secs, 3),
                        "max_merge_input_mb": round(max_in / (1 << 20),
                                                    1),
                        "merges": merges,
                        "disk_mb": round(st.disk_bytes() / (1 << 20), 1),
                        "write_wall_s": round(
                            time.perf_counter() - t_start, 1),
                    })
                    print(_dumps(rows[-1]), flush=True)
                    max_secs = max_in = 0.0  # per-window max
                    merges = 0
                    ckpt = next(ckpt_iter, 1 << 30)
            assert st.audit() == [], st.audit()
        finally:
            st.close()
            shutil.rmtree(work, ignore_errors=True)
    # growth summary: last-window max merge at full size, per mode
    by_mode = {m: [r for r in rows if r["mode"] == m]
               for m in ("leveled", "full")}
    if all(by_mode.values()):
        lv, fl = by_mode["leveled"][-1], by_mode["full"][-1]
        summary = {
            "metric": "compaction_curve_summary", "unit": "x",
            "dataset_mb": lv["dataset_mb"],
            "value": round(fl["max_merge_input_mb"]
                           / max(lv["max_merge_input_mb"], 0.1), 1),
            "leveled_max_merge_mb": lv["max_merge_input_mb"],
            "full_max_merge_mb": fl["max_merge_input_mb"],
            "leveled_max_merge_secs": lv["max_merge_secs"],
            "full_max_merge_secs": fl["max_merge_secs"],
        }
        print(_dumps(summary), flush=True)
        rows.append(summary)
    return rows


def run_storage_child(backend: str, n: int, tx_count_limit: int,
                      memtable_mb: int) -> dict:
    """ONE backend's sustained-write run in THIS process (the parent
    forks a fresh interpreter per backend so peak RSS is honest): a solo
    single-node chain ingests n register txs, then the data directory is
    re-opened cold to time restart recovery."""
    import resource
    import shutil
    import tempfile

    from fisco_bcos_tpu.init.node import Node, NodeConfig
    from fisco_bcos_tpu.ledger.ledger import Ledger
    from fisco_bcos_tpu.protocol import Transaction
    from fisco_bcos_tpu.storage import make_storage

    work = tempfile.mkdtemp(prefix=f"storage-bench-{backend}-")
    data = os.path.join(work, "data")
    try:
        blocks_needed = -(-n // max(1, tx_count_limit))
        block_limit = min(600, max(100, 2 * blocks_needed + 20))
        wire_txs = _build_workload(False, n, block_limit=block_limit)
        node = Node(NodeConfig(
            consensus="solo", crypto_backend="host", min_seal_time=0.0,
            tx_count_limit=tx_count_limit, storage_path=data,
            storage_backend=backend, storage_memtable_mb=memtable_mb))
        node.start()
        t0 = time.perf_counter()
        for s in range(0, len(wire_txs), 512):
            node.txpool.submit_batch(
                [Transaction.decode(raw) for raw in wire_txs[s:s + 512]])
        deadline = time.monotonic() + max(120.0, n / 20)
        while time.monotonic() < deadline:
            if node.ledger.total_tx_count() >= n:
                break
            time.sleep(0.05)
        t_end = time.perf_counter()
        committed = node.ledger.total_tx_count()
        blocks = node.ledger.current_number()
        node.stop()
        close = getattr(node.storage, "close", None)
        if close is not None:
            close()
        engine_stats = None
        stats = getattr(node.storage, "stats", None)
        if stats is not None:
            engine_stats = stats()
        dataset = sum(os.path.getsize(os.path.join(r, f))
                      for r, _, fs in os.walk(data) for f in fs) \
            if os.path.isdir(data) else 0

        restart_s = None
        if backend != "memory":
            t0r = time.perf_counter()
            st2 = make_storage(backend, data, memtable_mb=memtable_mb)
            led2 = Ledger(st2, node.suite)
            assert led2.current_number() == blocks, \
                (led2.current_number(), blocks)
            assert led2.header_by_number(blocks) is not None
            restart_s = round(time.perf_counter() - t0r, 3)
            st2.close()
        rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
        row = {
            "metric": "storage_backend_run", "backend": backend,
            "txs_committed": int(committed), "blocks": int(blocks),
            "tps": round(committed / (t_end - t0), 1) if t_end > t0 else 0,
            "wall_seconds": round(t_end - t0, 3),
            "restart_seconds": restart_s,
            "peak_rss_mb": round(rss_mb, 1),
            "dataset_mb": round(dataset / (1 << 20), 2),
            "memtable_mb": memtable_mb,
            "timed_out": committed < n,
        }
        if engine_stats is not None:
            row["segments"] = engine_stats["segment_count"]
            row["bloom_skip_rate"] = engine_stats["bloom_skip_rate"]
        return row
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _emit_storage_compare(args) -> None:
    """Fork one child per backend (honest peak RSS), emit each backend's
    row plus a `storage_compare` summary row for bench.py pickup."""
    import subprocess

    rows = {}
    for backend in ("memory", "wal", "disk"):
        r = subprocess.run(
            [sys.executable, "-u", os.path.abspath(__file__),
             "--storage-child", backend, "-n", str(args.n),
             "--tx-count-limit", str(args.tx_count_limit),
             "--storage-memtable-mb", str(args.storage_memtable_mb)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            timeout=1200)
        row = None
        for ln in r.stdout.splitlines():
            if ln.startswith("{"):
                row = json.loads(ln)
        if row is None:
            print(_dumps({"metric": "storage_backend_run",
                              "backend": backend, "error":
                              f"child rc={r.returncode}"}), flush=True)
            continue
        rows[backend] = row
        print(_dumps(row), flush=True)
    disk, mem = rows.get("disk"), rows.get("memory")
    wal = rows.get("wal")
    if disk and mem:
        print(_dumps({
            "metric": "storage_compare", "value": disk["tps"],
            "unit": "tx/sec", "n": args.n,
            "memtable_mb": args.storage_memtable_mb,
            "disk_tps": disk["tps"], "memory_tps": mem["tps"],
            "wal_tps": wal["tps"] if wal else None,
            "disk_vs_memory_tps": round(disk["tps"] / mem["tps"], 3)
            if mem["tps"] else None,
            "restart_disk_seconds": disk["restart_seconds"],
            "restart_wal_seconds": wal["restart_seconds"] if wal else None,
            "peak_rss_disk_mb": disk["peak_rss_mb"],
            "peak_rss_memory_mb": mem["peak_rss_mb"],
            "disk_dataset_mb": disk["dataset_mb"],
            "disk_segments": disk.get("segments"),
            "timed_out": bool(disk["timed_out"] or mem["timed_out"]),
        }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", type=int, default=2000)
    ap.add_argument("--backend", default="host",
                    choices=["auto", "host", "device"])
    ap.add_argument("--suite", default="ecdsa",
                    choices=["ecdsa", "sm", "both"])
    ap.add_argument("--tx-count-limit", type=int, default=1000)
    ap.add_argument("--transport", default="fake", choices=["fake", "p2p"],
                    help="fake = in-process bus; p2p = real TCP sessions")
    ap.add_argument("--tls", action="store_true",
                    help="with --transport p2p: dual-cert SM-TLS sessions")
    ap.add_argument("--rpc-clients", type=int, default=0, metavar="N",
                    help="concurrent-ingest mode: N HTTP JSON-RPC clients "
                         "through the continuous-batching lane")
    ap.add_argument("--rpc-compare", action="store_true",
                    help="with --rpc-clients: also run the per-request "
                         "baseline (lane off) and a single-client run")
    ap.add_argument("--read-clients", type=int, default=0, metavar="N",
                    help="read-plane mode: N keep-alive HTTP clients with "
                         "a mixed getBlock/getReceipt/call workload")
    ap.add_argument("--read-requests", type=int, default=2000,
                    help="with --read-clients: total requests across "
                         "clients")
    ap.add_argument("--read-compare", action="store_true",
                    help="with --read-clients: also run the per-request/"
                         "no-cache baseline (fresh connection, cache off)")
    ap.add_argument("--subscribers", type=int, default=0, metavar="N",
                    help="push-plane mode: N WS newBlockHeaders "
                         "subscribers, commit-to-client notify p50/p99 "
                         "and fan-out events/s")
    ap.add_argument("--sub-blocks", type=int, default=12,
                    help="with --subscribers: blocks committed while the "
                         "subscribers listen")
    ap.add_argument("--sub-compare", action="store_true",
                    help="with --subscribers: also report the poll-vs-"
                         "push A/B — read QPS N pollers would need for "
                         "the push plane's p99 freshness vs measured "
                         "polling capacity")
    ap.add_argument("--groups", type=int, default=0, metavar="G",
                    help="multi-group mode: G solo groups in one process "
                         "(shared crypto lane, per-group storage "
                         "namespaces), each fed -n txs directly")
    ap.add_argument("--cross-shard-pct", type=float, default=0.0,
                    help="with --groups: this percent of each group's "
                         "workload is cross-group transferOut legs to the "
                         "next group (settlement lag reported)")
    ap.add_argument("--groups-compare", action="store_true",
                    help="with --groups: also run the same workload on 1 "
                         "group first (the same-session scaling anchor)")
    ap.add_argument("--groups-runs", type=int, default=1, metavar="R",
                    help="with --groups: repeat each config R times "
                         "INTERLEAVED and report medians (the 2-core CI "
                         "host is noisy; use 3 for honest A/B)")
    ap.add_argument("--no-crypto-lane", action="store_true",
                    help="with --groups: per-group suites instead of the "
                         "shared crypto lane (the merge-off anchor)")
    ap.add_argument("--sync-bench", action="store_true",
                    help="join-time mode: full-replay vs snap-sync catch-up "
                         "against the same source chain")
    ap.add_argument("--sync-blocks", type=int, default=40,
                    help="with --sync-bench: source chain length in blocks")
    ap.add_argument("--storage-compare", action="store_true",
                    help="storage mode: sustained-write TPS, restart "
                         "seconds, and peak RSS for the memory/wal/disk "
                         "backends, one fresh process per backend")
    ap.add_argument("--storage-child", default=None, metavar="BACKEND",
                    help=argparse.SUPPRESS)  # internal: one backend's run
    ap.add_argument("--storage-memtable-mb", type=int, default=4,
                    help="with --storage-compare: disk-engine memtable cap "
                         "(small by default so the dataset spills to "
                         "segments and RSS boundedness is actually tested)")
    ap.add_argument("--scenario", default=None,
                    choices=["mint-storm", "airdrop-sweep", "hot-key",
                             "wide-table", "xshard-heavy"],
                    help="production-shaped load mode: pre-funded "
                         "account space, open-loop Poisson arrivals at "
                         "--scenario-intensity x measured capacity, on "
                         "the disk backend (testing/scenario.py)")
    ap.add_argument("--scenario-accounts", type=int, default=100_000,
                    help="pre-funded account space (direct injection)")
    ap.add_argument("--scenario-intensity", type=float, default=1.0,
                    help="offered load as a multiple of calibrated "
                         "capacity (2.0 = sustained 2x overload)")
    ap.add_argument("--scenario-window", type=float, default=8.0,
                    help="seconds per open-loop scenario window")
    ap.add_argument("--scenario-memtable-mb", type=int, default=16,
                    help="disk-engine memtable cap during scenarios")
    ap.add_argument("--hot-share", type=float, default=0.9,
                    help="hot-key: fraction of arrivals on the hot set")
    ap.add_argument("--cross-share", type=float, default=0.5,
                    help="xshard-heavy: cross-group arrival fraction")
    ap.add_argument("--value-bytes", type=int, default=2048,
                    help="wide-table: value width per row")
    ap.add_argument("--compaction-curve", action="store_true",
                    help="max single-merge cost vs dataset size, "
                         "leveled vs full-merge baseline, by direct "
                         "GB-scale writes into the disk engine")
    ap.add_argument("--curve-mb", type=int, default=512,
                    help="with --compaction-curve: dataset size to grow")
    ap.add_argument("--curve-memtable-mb", type=int, default=8,
                    help="with --compaction-curve: memtable cap")
    ap.add_argument("--curve-value-kb", type=int, default=4,
                    help="with --compaction-curve: row value width")
    ap.add_argument("--overload", action="store_true",
                    help="overload mode: capacity calibration, open-loop "
                         "1x/2x/4x Poisson ladder (goodput, shed rate, "
                         "expired-in-pool, admission latency), plane-"
                         "on/off A/B at 1x, and the 10:1 aggressor-vs-"
                         "polite fairness mix through the RPC edge")
    ap.add_argument("--overload-window", type=float, default=5.0,
                    help="with --overload: seconds per open-loop window")
    ap.add_argument("--overload-ab-runs", type=int, default=2,
                    help="with --overload: interleaved plane-off/on reps")
    ap.add_argument("--overload-fairness-s", type=float, default=10.0,
                    help="with --overload: fairness-mix duration")
    ap.add_argument("--proof-bench", action="store_true",
                    help="ZK proof plane: batched Poseidon device-vs-host "
                         "sweep + proofs rendered/served/verified per sec "
                         "on a live solo chain")
    ap.add_argument("--proof-txs", type=int, default=120,
                    help="committed txs backing the proof-serving rows")
    ap.add_argument("--trace-profile", action="store_true",
                    help="latency-attribution mode: closed-loop traced "
                         "txs through a 4-node chain at sample_rate=1; "
                         "emits the per-stage decomposition table and its "
                         "reconciliation against measured e2e p50")
    ap.add_argument("--trace-txs", type=int, default=24,
                    help="with --trace-profile: closed-loop tx count")
    ap.add_argument("--seal-mode", default="multi",
                    choices=["multi", "cert", "aggregate"],
                    help="with --trace-profile: commit-seal carriage the "
                         "cluster mints (consensus/qc.py) — A/B the "
                         "consensus stages across modes")
    ap.add_argument("--seal-bench", action="store_true",
                    help="commit-seal carriage bytes + span-verify cost "
                         "per seal_mode across roster sizes (offline, "
                         "deterministic)")
    ap.add_argument("--profile-attrib", action="store_true",
                    help="GIL-holder attribution on the direct solo "
                         "ingest path (top functions per stage vs an "
                         "independent rusage CPU meter) plus the "
                         "armed-vs-disarmed profiler self-cost A/B "
                         "(analysis/profiler.py)")
    ap.add_argument("--profile-runs", type=int, default=2, metavar="R",
                    help="with --profile-attrib: interleaved A/B "
                         "repetitions per side (default 2)")
    ap.add_argument("--lockcheck-ab", action="store_true",
                    help="lockcheck-cost mode: interleaved direct-ingest "
                         "runs with the disarmed blocking markers live vs "
                         "stubbed out; medians + ns/crossing (the <1%% "
                         "disarmed-overhead acceptance row)")
    ap.add_argument("--lockcheck-runs", type=int, default=3, metavar="R",
                    help="with --lockcheck-ab: interleaved reps per side")
    ap.add_argument("--columnar-compare", action="store_true",
                    help="columnar-substrate A/B: object-path "
                         "(Transaction.decode + submit_batch) vs columnar "
                         "wire ingest (decode_columns + submit_columns) "
                         "on a fresh solo chain per run, INTERLEAVED; "
                         "emits the columnar_tps row with both medians "
                         "and the adjacent-pair ratio")
    ap.add_argument("--columnar-runs", type=int, default=3, metavar="R",
                    help="with --columnar-compare: interleaved reps per "
                         "side (default 3; the CI host is noisy)")
    ap.add_argument("--workers", type=int, default=0, metavar="W",
                    help="out-of-process execution workers per node "
                         "([scheduler] workers): the 4-node run executes "
                         "blocks in W subprocesses behind the scheduler "
                         "seam and emits an exec_worker_occupancy row "
                         "from the pools' timed-window stats")
    ap.add_argument("--pipeline-profile", action="store_true",
                    help="direct mode: also emit pipeline_tps and a per-"
                         "stage (fill/execute/roots/consensus_wait/commit) "
                         "occupancy breakdown from the ingress node")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="disable pipelined block production (serial "
                         "execute-then-commit — the before/after anchor)")
    args = ap.parse_args()

    suites = [False, True] if args.suite == "both" else \
        [args.suite == "sm"]
    if args.storage_child:
        print(_dumps(run_storage_child(
            args.storage_child, args.n, args.tx_count_limit,
            args.storage_memtable_mb)), flush=True)
        return
    if args.storage_compare:
        _emit_storage_compare(args)
        return
    if args.sync_bench:
        for sm in suites:
            for row in run_sync_bench(sm, args.sync_blocks):
                print(_dumps(row), flush=True)
        return
    if args.compaction_curve:
        run_compaction_curve(args.curve_mb, args.curve_memtable_mb,
                             args.curve_value_kb)
        return
    if args.scenario:
        for sm in suites:
            _emit_scenario_mode(args, sm)
        return
    if args.overload:
        for sm in suites:
            _emit_overload_mode(args, sm)
        return
    if args.trace_profile:
        for sm in suites:
            for row in run_trace_profile(sm, args.backend, args.trace_txs,
                                         seal_mode=args.seal_mode):
                print(_dumps(row), flush=True)
        return
    if args.seal_bench:
        for sm in suites:
            for row in run_seal_bench(sm, args.backend):
                print(_dumps(row), flush=True)
        return
    if args.profile_attrib:
        for sm in suites:
            for row in run_profile_attrib(sm, args.backend, args.n,
                                          args.tx_count_limit,
                                          args.profile_runs):
                print(_dumps(row), flush=True)
        return
    if args.proof_bench:
        for sm in suites:
            for row in run_proof_bench(sm, args.backend, args.proof_txs):
                print(_dumps(row), flush=True)
        return
    if args.lockcheck_ab:
        for sm in suites:
            print(_dumps(run_lockcheck_ab(
                sm, args.n, args.backend, args.tx_count_limit,
                args.lockcheck_runs)), flush=True)
        return
    if args.columnar_compare:
        for sm in suites:
            print(_dumps(run_columnar_compare(
                sm, args.n, args.backend, args.tx_count_limit,
                args.columnar_runs)), flush=True)
        return
    if args.groups > 0:
        for sm in suites:
            _emit_groups_mode(args, sm)
        return
    if args.subscribers > 0:
        for sm in suites:
            _emit_sub_mode(args, sm)
        return
    if args.read_clients > 0:
        for sm in suites:
            _emit_read_mode(args, sm)
        return
    if args.rpc_clients > 0:
        for sm in suites:
            _emit_rpc_mode(args, sm)
        return
    for sm in suites:
        res = run_chain(sm, args.n, args.backend, args.tx_count_limit,
                        transport=args.transport, tls=args.tls,
                        pipeline=not args.no_pipeline,
                        profile=args.pipeline_profile,
                        workers=args.workers)
        suffix = ""
        if args.transport == "p2p":
            suffix = "_tls" if res["tls"] else "_tcp"
        pstats = res.pop("pipeline_stats", None)
        wstats = res.pop("exec_worker_stats", None)
        res.update({"metric": f"chain_tps_4node_{res['suite']}" + suffix,
                    "value": res["tps"], "unit": "tx/sec"})
        print(_dumps(res), flush=True)
        if wstats is not None:
            # pool engagement over the timed window, whole chain: blocks
            # the subprocesses executed, fallbacks taken, and per-worker
            # busy-fraction (value = mean occupancy across every worker
            # on every node — the "did the pool actually absorb
            # execution" number the perf gate tracks)
            occ = [w["occupancy"] for st in wstats
                   for w in st["per_worker"]]
            print(_dumps({
                "metric": "exec_worker_occupancy", "unit": "occupancy",
                "suite": res["suite"], "workers": args.workers,
                "value": round(statistics.mean(occ), 3) if occ else 0.0,
                "pool_blocks": sum(w["blocks"] for st in wstats
                                   for w in st["per_worker"]),
                "exec_fallbacks": sum(st["fallbacks"] for st in wstats),
                "per_node": [{
                    "fallbacks": st["fallbacks"],
                    "occupancy": [round(w["occupancy"], 3)
                                  for w in st["per_worker"]],
                    "blocks": [w["blocks"] for w in st["per_worker"]],
                } for st in wstats],
            }), flush=True)
        if args.pipeline_profile:
            print(_dumps({
                "metric": "pipeline_tps", "value": res["tps"],
                "unit": "tx/sec", "suite": res["suite"],
                "pipeline": res["pipeline"], "blocks": res["blocks"],
                "txs_committed": res["txs_committed"],
                "timed_out": res["txs_committed"] < args.n,
            }), flush=True)
            wall = max(res["wall_seconds"], 1e-9)
            stages = (pstats or {}).get("stages", {})
            print(_dumps({
                "metric": "pipeline_profile", "unit": "occupancy",
                "suite": res["suite"], "pipeline": res["pipeline"],
                "wall_seconds": res["wall_seconds"],
                # fraction of the timed window each stage kept busy on the
                # ingress node; stages can sum past 1.0 exactly when the
                # pipeline overlaps them — that overlap IS the win, and the
                # biggest stage is where the next order of magnitude lives
                "occupancy": {k: round(v["seconds"] / wall, 3)
                              for k, v in stages.items()},
                "stage_seconds": {k: v["seconds"]
                                  for k, v in stages.items()},
                "blocks_profiled": max(
                    [v["count"] for v in stages.values()] or [0]),
                "speculative_execs": (pstats or {}).get(
                    "speculative_execs", 0),
                "overlap_commits": (pstats or {}).get("overlap_commits", 0),
            }), flush=True)


if __name__ == "__main__":
    main()
