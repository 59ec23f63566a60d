#!/usr/bin/env bash
# One-command sanitizer + differential-fuzz gate for the native engines
# (VERDICT r4 #8; SURVEY §5 row 34 — the reference's
# cmake -DSANITIZE_ADDRESS/-DSANITIZE_THREAD CI jobs, cmake/Options.cmake:57).
#
#   tools/sanitize_ci.sh            # full gate: ASan+UBSan, TSan, fuzz
#   tools/sanitize_ci.sh --fast     # skip the @slow deep differential fuzz
#   tools/sanitize_ci.sh --lint     # ONLY the concurrency-correctness
#                                   # plane: bcoslint (lexical) AND
#                                   # bcosflow (whole-program plane
#                                   # contracts) clean against their
#                                   # committed baselines — same exit-code
#                                   # convention: 1 iff a NEW finding —
#                                   # then an ARMED
#                                   # (BCOS_LOCKCHECK=1) 4-node smoke
#                                   # asserting zero lock-order cycles and
#                                   # zero blocking-while-locked hits with
#                                   # bcos_lock_* hold metrics live
#   tools/sanitize_ci.sh --chaos    # ONLY the multi-process fault gate:
#                                   # 4 OS-process TLS chain, kill -9 a node
#                                   # mid-stream, assert it rejoins to the
#                                   # same state root (tests/test_chaos_e2e)
#   tools/sanitize_ci.sh --gameday  # ONLY the game-day orchestration gate:
#                                   # the ci-smoke fault schedule
#                                   # (tools/gameday.py) against a real
#                                   # 4-node cluster under scenario load —
#                                   # clean audit + converged heads +
#                                   # health SLO + bounded write p99 +
#                                   # byte-identical c_balance, with the
#                                   # gameday_* rows under the perf gate
#   tools/sanitize_ci.sh --faults   # ONLY the failpoint/health smoke: boot
#                                   # a 4-node chain, arm one storage and
#                                   # one consensus failpoint at runtime
#                                   # via the ops endpoint (/failpoints),
#                                   # assert convergence, a clean
#                                   # getAuditReport on every node, and
#                                   # the /healthz + bcos_node_health
#                                   # gauge round-trip
#   tools/sanitize_ci.sh --ingest   # ONLY the continuous-batching smoke:
#                                   # short chain_bench --rpc-clients run,
#                                   # assert the lane coalesces (mean batch
#                                   # > 1) and emits an rpc_ingest_tps row
#   tools/sanitize_ci.sh --snapshot # ONLY the checkpoint smoke: export a
#                                   # snapshot from a live WAL-backed chain,
#                                   # wipe a fresh data dir, import, verify
#                                   # identical head hash + state root and
#                                   # emit the snap_sync_seconds bench row
#   tools/sanitize_ci.sh --pipeline # ONLY the pipelined-block-production
#                                   # smoke: 4-node chain, speculative
#                                   # execution + off-thread commit engage,
#                                   # byte-identical state across nodes, and
#                                   # the stage-occupancy bench row
#   tools/sanitize_ci.sh --rpc      # ONLY the read-plane smoke: boot a
#                                   # node, issue a keep-alive JSON-RPC 2.0
#                                   # batch, assert cache-hit metrics
#                                   # increment and a post-commit query
#                                   # serves the cached bytes
#   tools/sanitize_ci.sh --subs     # ONLY the push-plane smoke: boot a
#                                   # real daemon, attach 200 WS
#                                   # subscribers through the admission
#                                   # plane, kill one commit mid-stream
#                                   # (storage failpoint), assert no
#                                   # stale push ever reached a client
#                                   # and commit->client notify latency
#                                   # stays bounded
#   tools/sanitize_ci.sh --storage  # ONLY the disk-engine smoke: boot a
#                                   # [storage] backend = disk daemon,
#                                   # commit writes, kill -9 it, re-boot
#                                   # and verify manifest + WAL-tail
#                                   # recovery (no full-log replay) with
#                                   # identical balances + head, then the
#                                   # storage_compare bench row
#   tools/sanitize_ci.sh --obs      # ONLY the observability smoke: boot a
#                                   # daemon, submit txs under a client
#                                   # traceparent, fetch the trace by id
#                                   # via getTrace, parse /metrics off the
#                                   # RPC edge, reconcile the
#                                   # bcos_tx_stage_seconds stage sums
#                                   # against measured e2e latency, and
#                                   # emit the trace_profile_summary row
#   tools/sanitize_ci.sh --overload # ONLY the overload-control smoke:
#                                   # 4 real daemons with per-client edge
#                                   # budgets, an aggressor floods while a
#                                   # polite client keeps committing with
#                                   # bounded latency, -32005 rejects are
#                                   # observed, and health returns to ok
#                                   # after the storm; then the
#                                   # chain_bench --overload goodput row
#   tools/sanitize_ci.sh --zk       # ONLY the ZK proof plane smoke: real
#                                   # daemons, commit txs, fetch getProof
#                                   # over JSON-RPC, verify tx/receipt/
#                                   # state proofs client-side against the
#                                   # sealed header roots, reject tampered
#                                   # proof/value/root, round-trip the
#                                   # batched verifyProofs entry, then the
#                                   # chain_bench --proof-bench rows
#   tools/sanitize_ci.sh --profile  # ONLY the continuous-profiling smoke:
#                                   # real 4-node daemon chain, /profile
#                                   # returns folded stacks naming a
#                                   # scheduler + lane frame, a slow-span
#                                   # burst profile is retrievable by its
#                                   # trace id via getTrace, bcos_lane_*
#                                   # occupancy series live on /metrics,
#                                   # chain_bench --profile-attrib row,
#                                   # then tools/perf_gate.py report-only
#                                   # against the recorded trajectory
#   tools/sanitize_ci.sh --workers  # ONLY the out-of-process execution
#                                   # smoke: 4 real daemons with
#                                   # [scheduler] workers = 1, RPC writes,
#                                   # SIGKILL one node's execution worker
#                                   # mid-stream — the scheduler falls
#                                   # back in-process, the health plane
#                                   # respawns the worker, the respawned
#                                   # worker executes blocks, the chain
#                                   # converges to identical heads +
#                                   # byte-identical c_balance with a
#                                   # clean getAuditReport everywhere
#   tools/sanitize_ci.sh --seals    # ONLY the quorum-certificate smoke:
#                                   # 4 real TLS daemons with [consensus]
#                                   # seal_mode = cert, RPC writes,
#                                   # converged heads + clean audit on
#                                   # every node, every committed header
#                                   # carries ONE certificate whose wire
#                                   # bytes undercut the same quorum as
#                                   # 2f+1 loose seals, and the seal-bytes
#                                   # gauge + cert-verify counters are
#                                   # live on getSystemStatus.consensus
#   tools/sanitize_ci.sh --groups   # ONLY the multi-group smoke: ONE
#                                   # daemon hosting two groups ([groups]
#                                   # ini), disjoint writes routed by the
#                                   # group RPC param, per-group head
#                                   # hashes diverge, a cross-group
#                                   # transfer settles atomically, and the
#                                   # shared crypto lane's batch metric
#                                   # shows real (>1) merged batches
#
# Exit 0 = every stage clean. Each stage rebuilds the sanitizer variants
# from the CURRENT sources (the src-hash stamp keeps them honest) and runs
# the relevant suites with the sanitized libraries injected via the
# FBTPU_*_LIB loader seams.

set -euo pipefail
cd "$(dirname "$0")/.."

FAST=0
[ "${1:-}" = "--fast" ] && FAST=1

run_lint_stage() {
  echo "== [lint] bcoslint: repo invariants vs the committed baseline"
  local t0 t1
  t0=$SECONDS
  python tools/bcoslint.py
  t1=$SECONDS
  echo "== [lint] bcoslint clean in $((t1 - t0))s"
  echo "== [lint] bcosflow: whole-program plane contracts vs the baseline"
  t0=$SECONDS
  python tools/bcosflow.py
  t1=$SECONDS
  echo "== [lint] bcosflow clean in $((t1 - t0))s"
  echo "== [lint] armed lockcheck smoke: 4-node chain under BCOS_LOCKCHECK=1"
  BCOS_LOCKCHECK=1 JAX_PLATFORMS=cpu \
    timeout -k 10 600 python - <<'EOF'
import sys, time
sys.path.insert(0, "benchmark")
from fisco_bcos_tpu.analysis import lockcheck as lc
assert lc.armed(), "BCOS_LOCKCHECK=1 did not arm the checker"
from chain_bench import _build_chain
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.protocol import Transaction

nodes, gateways, _ = _build_chain(False, "host", 50)
suite = nodes[0].suite
kp = suite.generate_keypair(b"lint-smoke")
txs = [Transaction(to=pc.BALANCE_ADDRESS,
                   input=pc.encode_call(
                       "register",
                       lambda w, i=i: w.blob(b"ls%d" % i).u64(1 + i)),
                   nonce=f"ls-{i}", block_limit=300).sign(suite, kp)
       for i in range(120)]
for node in nodes:
    node.start()
try:
    for s in range(0, 120, 30):
        nodes[(s // 30) % 4].txpool.submit_batch(txs[s:s + 30])
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        if all(n.ledger.total_tx_count() >= 120 for n in nodes):
            break
        time.sleep(0.05)
    assert all(n.ledger.total_tx_count() >= 120 for n in nodes), \
        [n.ledger.total_tx_count() for n in nodes]
finally:
    for node in nodes:
        node.stop()
    for gw in set(gateways):
        gw.stop()
rep = lc.report()
assert rep["edges"], "armed run recorded no lock-order edges at all"
lc.assert_clean()
from fisco_bcos_tpu.utils.metrics import REGISTRY
snap = REGISTRY.snapshot()
holds = [k for k in snap["histograms"] if k.startswith("bcos_lock_hold")]
assert holds, "no bcos_lock_hold_seconds series emitted"
print("sanitize_ci: LINT STAGE CLEAN "
      f"(edges={len(rep['edges'])}, cycles=0, blocking=0, "
      f"lock_series={len(holds)})")
EOF
}

run_profile_stage() {
  echo "== [profile] continuous-profiling smoke: real 4-node daemon chain," \
       "/profile folded stacks + flamegraph, slow-span burst by trace id,"
  echo "==           bcos_profile_*//bcos_lane_* series, perf gate" \
       "report-only vs the recorded trajectory"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python - <<'EOF'
import configparser, http.client, json, os, re, shutil, signal
import subprocess, sys, tempfile, time
sys.path.insert(0, "tools")
from build_chain import build_chain
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.sdk.client import SdkClient, TransactionBuilder
from fisco_bcos_tpu.crypto.suite import make_suite

work = tempfile.mkdtemp(prefix="profile-smoke-")
procs = []
try:
    from fisco_bcos_tpu.testing.chaos import free_port_block
    port = free_port_block(8)
    info = build_chain(work, 4, consensus="pbft", rpc_base_port=port,
                       p2p_base_port=port + 4, crypto_backend="host")
    # arm the plane's burst path deterministically: sampled client traces
    # + a slow-span threshold every sendTransaction span clears
    for ent in info["nodes"]:
        ini = os.path.join(ent["dir"], "config.ini")
        cp = configparser.ConfigParser(strict=False)
        cp.read(ini)
        cp["trace"]["slow_ms"] = "5"
        cp["profile"]["hz"] = "19"
        cp["profile"]["burst_s"] = "0.5"
        with open(ini, "w") as f:
            cp.write(f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for ent in info["nodes"]:
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "fisco_bcos_tpu", ent["dir"],
             "--log-file", os.path.join(ent["dir"], "daemon.log")],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env))
    cli = SdkClient(f"http://127.0.0.1:{port}", group=info["group_id"])
    end = time.monotonic() + 120
    while time.monotonic() < end:
        try:
            cli.get_block_number(); break
        except Exception:
            time.sleep(0.25)
    else:
        raise TimeoutError("rpc never came up")

    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"profile-smoke")
    builder = TransactionBuilder(suite, None, chain_id=info["chain_id"],
                                 group_id=info["group_id"])
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    tid = os.urandom(16).hex()
    for i in range(6):
        tx = builder.build(kp, pc.BALANCE_ADDRESS,
                           pc.encode_call("register",
                                          lambda w, i=i: w.blob(b"pf%d" % i)
                                          .u64(10 + i)),
                           nonce=f"pf{i}", block_limit=100)
        body = json.dumps({"jsonrpc": "2.0", "id": i,
                           "method": "sendTransaction",
                           "params": [info["group_id"], "",
                                      "0x" + tx.encode().hex()]})
        conn.request("POST", "/", body=body.encode(),
                     headers={"traceparent":
                              f"00-{tid}-00f067aa0ba902b7-01"})
        resp = json.loads(conn.getresponse().read())
        assert resp["result"]["status"] == 0, resp

    # 1) /profile (rpc edge): non-empty folded stacks naming at least one
    # scheduler and one lane frame (the continuous-batching ingest lane
    # dispatcher IS resident on every node; role prefix `ingest`)
    conn.request("GET", "/profile?seconds=2")
    r = conn.getresponse(); folded = r.read().decode()
    assert r.status == 200 and folded.strip(), (r.status, folded[:200])
    assert "scheduler.py:" in folded, folded[:800]
    assert "ingest;" in folded and "ingest.py:" in folded, folded[:800]
    # 2) the flamegraph renderer serves self-contained HTML
    conn.request("GET", "/profile?fmt=flame")
    r = conn.getresponse(); html = r.read().decode()
    assert r.status == 200 and "<html" in html and "FOLDED" in html

    # 3) slow-span burst: retrievable BY TRACE ID via getTrace (poll — the
    # burst runs 0.5 s after the span fires) and flagged in listTraces
    deadline = time.monotonic() + 30
    prof = None
    while time.monotonic() < deadline:
        doc = cli.request("getTrace", [info["group_id"], "", tid])
        prof = doc.get("profile")
        if prof:
            break
        time.sleep(0.5)
    assert prof and prof["folded"].strip(), "no burst profile for trace"
    assert prof["traceId"] == tid and prof["samples"] > 0, prof
    lst = cli.request("listTraces", [info["group_id"], "", 50])
    flagged = [t for t in lst["traces"] if t.get("profiled")]
    assert any(t["traceId"] == tid for t in flagged), lst["traces"][:3]

    # 4) profiler + getSystemStatus surfaces
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    assert "bcos_profile_samples_total" in text, text[:400]
    st = cli.request("getSystemStatus", [info["group_id"], ""])
    assert st["profile"]["armed"] and st["profile"]["samples"] > 0, \
        st["profile"]
    print("sanitize_ci: PROFILE daemon smoke clean "
          f"(folded_lines={len(folded.splitlines())}, "
          f"burst_samples={prof['samples']}, "
          f"profiled_traces={len(flagged)})")
finally:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGKILL)
            p.wait(timeout=30)
    shutil.rmtree(work, ignore_errors=True)
EOF
  echo "== [profile] crypto-lane occupancy telemetry: 2 groups, one shared" \
       "lane, bcos_lane_* series live"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python - <<'EOF'
import shutil, tempfile, threading, time
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.init.daemon import NodeDaemon
from fisco_bcos_tpu.init.node import NodeConfig
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.tool.config import ChainConfig, save_node_config
from fisco_bcos_tpu.utils.metrics import REGISTRY
from fisco_bcos_tpu.crypto.suite import make_suite

work = tempfile.mkdtemp(prefix="lane-occ-smoke-")
try:
    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"lane-occ")
    cfg = NodeConfig(groups=["group0", "group1"], consensus="solo",
                     crypto_backend="host", min_seal_time=0.0,
                     storage_path="data", rpc_port=0, p2p_port=0)
    chain = ChainConfig(consensus_type="solo", sealers=[kp.pub_bytes])
    save_node_config(work, cfg, chain, kp.secret)
    daemon = NodeDaemon(work)
    daemon.start()
    try:
        nodes = [daemon.manager.node(g) for g in ("group0", "group1")]
        bursts = [[Transaction(to=pc.BALANCE_ADDRESS,
                               input=pc.encode_call(
                                   "register",
                                   lambda w, i=i: w.blob(
                                       b"%s-%d" % (g.encode(), i)).u64(1)),
                               nonce=f"lo-{g}-{i}", group_id=g,
                               block_limit=100).sign(suite, kp)
                   for i in range(64)]
                  for g in ("group0", "group1")]
        ths = [threading.Thread(
            target=lambda n=n, b=b: n.txpool.submit_batch(b), daemon=True)
            for n, b in zip(nodes, bursts)]
        for t in ths: t.start()
        for t in ths: t.join(60)
        time.sleep(0.5)  # let the lane dispatcher drain its last batch
        # occupancy telemetry on the shared lane (crypto/lane.py)
        lane = daemon.manager.crypto_lane_stats()["ecdsa"]
        occ = lane["occupancy"]
        assert occ and any(o["device_calls"] > 0 for o in occ.values()), occ
        text = REGISTRY.prometheus_text()
        for series in ("bcos_lane_dispatch_seconds", "bcos_lane_batch_items",
                       "bcos_lane_merge_requests"):
            assert series in text, f"missing {series}"
        # the lane dispatcher thread shows up under the `lane` role in a
        # live capture (the profiler names the crypto lane frame)
        from fisco_bcos_tpu.analysis.profiler import PROFILER
        folded = PROFILER.capture(1.0)
        assert "lane;" in folded and "lane.py:" in folded, folded[:800]
        print("sanitize_ci: PROFILE lane-occupancy clean "
              f"(ops={sorted(occ)}, "
              f"mean_batch={lane['mean_device_batch']})")
    finally:
        daemon.shutdown()
finally:
    shutil.rmtree(work, ignore_errors=True)
EOF
  echo "== [profile] chain_bench --profile-attrib: GIL-holder table +" \
       "self-cost A/B"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python benchmark/chain_bench.py --profile-attrib -n 2000 \
    --profile-runs 1 --backend host 2>/dev/null \
    | grep '"metric": "profile_attrib_summary"'
  echo "== [profile] perf gate, report-only, vs the recorded trajectory"
  LAST_BENCH="$(ls BENCH_r*.json 2>/dev/null | tail -1)"
  if [ -n "$LAST_BENCH" ]; then
    python tools/perf_gate.py --candidate "$LAST_BENCH" --report-only
  else
    echo "sanitize_ci: no BENCH_r*.json recorded; perf gate skipped"
  fi
}

if [ "${1:-}" = "--lint" ]; then
  run_lint_stage
  exit 0
fi

if [ "${1:-}" = "--profile" ]; then
  run_profile_stage
  echo "sanitize_ci: PROFILE STAGE CLEAN"
  exit 0
fi

if [ "${1:-}" = "--ingest" ]; then
  echo "== [ingest] continuous-batching lane smoke: 4 HTTP clients," \
       "200 txs through the 4-node chain's ingest lane"
  OUT="$(JAX_PLATFORMS=cpu timeout -k 10 600 \
    python benchmark/chain_bench.py --rpc-clients 4 -n 200 --backend host \
    2>/dev/null | grep '"metric": "rpc_ingest_tps"')"
  echo "$OUT"
  python - "$OUT" <<'EOF'
import json, sys
row = json.loads(sys.argv[1])
assert not row.get("timed_out"), f"chain wedged: {row}"
assert row["txs_committed"] >= 200, row
assert row["mean_batch"] > 1.0, f"lane not coalescing: {row}"
assert row["recover_calls_per_tx"] < 1.0, row
print("sanitize_ci: INGEST STAGE CLEAN "
      f"(tps={row['tps']}, mean_batch={row['mean_batch']}, "
      f"recover/tx={row['recover_calls_per_tx']})")
EOF
  exit 0
fi

if [ "${1:-}" = "--rpc" ]; then
  echo "== [rpc] read-plane smoke: keep-alive batch request +" \
       "commit-coherent query cache"
  JAX_PLATFORMS=cpu timeout -k 10 300 \
    python - <<'EOF'
import json
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.sdk.client import SdkClient

node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                       rpc_port=0))
node.start()
try:
    kp = node.suite.generate_keypair(b"rpc-smoke")
    def register(i):
        tx = Transaction(to=pc.BALANCE_ADDRESS,
                         input=pc.encode_call(
                             "register",
                             lambda w: w.blob(b"rs%d" % i).u64(10 + i)),
                         nonce=f"rs{i}", block_limit=100).sign(node.suite, kp)
        rc = node.txpool.wait_for_receipt(
            node.send_transaction(tx).tx_hash, 30)
        assert rc is not None and rc.status == 0, rc
    for i in range(3):
        register(i)

    sdk = SdkClient(f"http://{node.rpc.host}:{node.rpc.port}")
    # ONE keep-alive connection, ONE JSON-RPC 2.0 batch body
    head = node.ledger.current_number()
    resps = sdk.request_batch([
        ("getBlockNumber", ["group0", ""]),
        ("getBlockByNumber", ["group0", "", head, False, False]),
        ("getBlockByNumber", ["group0", "", head, False, False]),
    ])
    assert len(resps) == 3 and all("result" in r for r in resps), resps
    assert resps[0]["result"] == head
    assert json.dumps(resps[1]["result"]) == json.dumps(resps[2]["result"])
    s0 = node.query_cache.stats()
    assert s0["hits"] >= 1, s0  # identical in-batch query served cached

    # post-commit: a NEW block's responses serve from the primed cache,
    # byte-for-byte identical across requests on the same connection
    register(3)
    new_head = node.ledger.current_number()
    assert new_head > head
    b1 = sdk.get_block_by_number(new_head)
    b2 = sdk.get_block_by_number(new_head)
    assert json.dumps(b1) == json.dumps(b2)
    s1 = node.query_cache.stats()
    assert s1["hits"] > s0["hits"], (s0, s1)
    print("sanitize_ci: RPC STAGE CLEAN "
          f"(hits={s1['hits']}, hit_rate={s1['hit_rate']}, "
          f"entries={s1['entries']})")
finally:
    node.stop()
EOF
  exit 0
fi

if [ "${1:-}" = "--subs" ]; then
  echo "== [subs] push-plane smoke: real daemon, 200 WS subscribers" \
       "through admission, one commit killed mid-stream, no stale push"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python - <<'EOF'
import configparser, os, signal, subprocess, sys, tempfile, threading, time
import urllib.request
sys.path.insert(0, "tools")
from build_chain import build_chain
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.crypto.suite import make_suite
from fisco_bcos_tpu.sdk.client import SdkClient, TransactionBuilder
from fisco_bcos_tpu.sdk.ws import WsSdkClient
from fisco_bcos_tpu.testing.chaos import free_port_block

N_SUBS, N_TX = 200, 12
work = tempfile.mkdtemp(prefix="subs-smoke-")
proc, subs = None, []
try:
    port = free_port_block(4)
    info = build_chain(work, 1, consensus="solo", rpc_base_port=port,
                       p2p_base_port=port + 1, metrics_base_port=port + 2,
                       crypto_backend="host")
    node_dir = info["nodes"][0]["dir"]
    ws_port = port + 3
    cfgp = os.path.join(node_dir, "config.ini")
    cp = configparser.ConfigParser()
    cp.read(cfgp)
    cp["rpc"]["ws_port"] = str(ws_port)
    with open(cfgp, "w") as f:
        cp.write(f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BCOS_FAILPOINTS_OPS="1")
    proc = subprocess.Popen(
        [sys.executable, "-m", "fisco_bcos_tpu", node_dir,
         "--log-file", os.path.join(node_dir, "daemon.log")],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)
    cli = SdkClient(f"http://127.0.0.1:{port}", group=info["group_id"])
    end = time.monotonic() + 120
    while time.monotonic() < end:
        try:
            cli.get_block_number()
            break
        except Exception:
            time.sleep(0.25)
    else:
        raise TimeoutError("rpc never came up")

    # the subscriber fleet rides the SAME admission plane as RPC reads
    print(f"attaching {N_SUBS} WS subscribers...", flush=True)
    subs = [WsSdkClient("127.0.0.1", ws_port, group=info["group_id"])
            for _ in range(N_SUBS)]
    for c in subs:
        c.subscribe("newBlockHeaders")

    # probe drains ITS stream live: per-event latency vs the sealed-at
    # stamp (generous cross-process bound — includes execute + commit)
    probe = subs[0]
    probe_lat = []

    def drain_probe():
        while True:
            ev = probe.next_event(timeout=1.0)
            if ev is None:
                if stop_probe.is_set():
                    return
                continue
            ts = (ev.get("result") or {}).get("timestamp")
            if ts:
                probe_lat.append(time.time() * 1000 - ts)

    stop_probe = threading.Event()
    pt = threading.Thread(target=drain_probe, daemon=True)
    pt.start()

    # the attach storm can trip the health plane into degraded (writes
    # shed) on small hosts — wait for ok, then ride out residual sheds
    def wait_ok(deadline=60):
        end = time.monotonic() + deadline
        while time.monotonic() < end:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port + 2}/healthz",
                    timeout=5).read()
                return
            except Exception:
                time.sleep(0.5)

    wait_ok()
    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"subs-smoke")
    builder = TransactionBuilder(suite, None, chain_id=info["chain_id"],
                                 group_id=info["group_id"])
    for i in range(N_TX):
        if i == 4:
            # kill ONE commit mid-stream: the aborted block must never
            # be pushed to any subscriber (double-invalidation contract)
            url = (f"http://127.0.0.1:{port + 2}/failpoints"
                   f"?arm=scheduler.commit.entry=raise*1")
            urllib.request.urlopen(url, timeout=10).read()
        tx = builder.build(kp, pc.BALANCE_ADDRESS,
                           pc.encode_call("register",
                                          lambda w, i=i: w.blob(b"sb%d" % i)
                                          .u64(10 + i)),
                           nonce=f"sb{i}", block_limit=500)
        for attempt in range(40):
            try:
                cli.send_transaction(tx, wait=False)
                break
            except Exception:  # degraded shed / brief edge hiccup
                time.sleep(0.5)
        else:
            raise RuntimeError(f"tx {i} shed for 20s straight")
        time.sleep(0.2)
    end = time.monotonic() + 120
    while time.monotonic() < end:
        if cli.request("getTotalTransactionCount",
                       [info["group_id"], ""])["transactionCount"] >= N_TX:
            break
        time.sleep(0.25)
    head = cli.get_block_number()
    assert head >= 8, f"chain wedged at {head} after the killed commit"
    canon = {n: cli.request("getBlockHashByNumber",
                            [info["group_id"], "", n])
             for n in range(1, head + 1)}

    # every subscriber sees the final head; every pushed header matches
    # the canonical chain byte-for-byte (no stale push survived the
    # killed commit), across ALL 200 streams
    events = 0
    for ci, c in enumerate(subs[1:], start=1):
        saw_head, end = False, time.monotonic() + 30
        while not saw_head and time.monotonic() < end:
            ev = c.next_event(timeout=1.0)
            if ev is None:
                continue
            r = ev.get("result") or {}
            events += 1
            assert r.get("hash") == canon.get(r.get("number")), \
                (ci, r.get("number"), r.get("hash"))
            saw_head = r.get("number") == head
        assert saw_head, f"subscriber {ci} never saw head {head}"
    stop_probe.set()
    pt.join(timeout=5)
    lat = sorted(probe_lat)
    p99 = lat[int(0.99 * (len(lat) - 1))] if lat else 0.0
    assert lat and p99 < 5000, f"notify p99 {p99:.0f}ms (n={len(lat)})"
    print(f"sanitize_ci: SUBS STAGE CLEAN (head={head}, "
          f"events={events}, notify_p99={p99:.0f}ms)")
finally:
    for c in subs:
        try:
            c.close()
        except Exception:
            pass
    if proc is not None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
EOF
  exit 0
fi

if [ "${1:-}" = "--snapshot" ]; then
  echo "== [snapshot] checkpoint smoke: export -> wipe -> import ->" \
       "verify state root (WAL-backed solo chain)"
  JAX_PLATFORMS=cpu timeout -k 10 300 \
    python - <<'EOF'
import shutil, tempfile
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.init.node import Node, NodeConfig
from fisco_bcos_tpu.ledger.ledger import Ledger
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.snapshot import export_snapshot, install_snapshot
from fisco_bcos_tpu.storage.wal import WalStorage

work = tempfile.mkdtemp(prefix="snap-smoke-")
try:
    node = Node(NodeConfig(crypto_backend="host", min_seal_time=0.0,
                           storage_path=work + "/data"))
    node.start()
    kp = node.suite.generate_keypair(b"snap-smoke")
    for i in range(5):
        tx = Transaction(to=pc.BALANCE_ADDRESS,
                         input=pc.encode_call(
                             "register",
                             lambda w, i=i: w.blob(b"a%d" % i).u64(1)),
                         nonce=f"s{i}", block_limit=100).sign(node.suite, kp)
        rc = node.txpool.wait_for_receipt(
            node.send_transaction(tx).tx_hash, 30)
        assert rc is not None and rc.status == 0, rc
    node.stop()
    head = node.ledger.current_number()
    want_hash = node.ledger.header_by_number(head).hash(node.suite)
    want_root = node.ledger.header_by_number(head).state_root
    manifest, chunks = export_snapshot(node.storage, node.ledger,
                                       node.suite, chunk_bytes=4096)
    node.storage.close()

    # disaster: the data dir is gone; import into a brand-new WAL store
    shutil.rmtree(work + "/data")
    fresh = WalStorage(work + "/data2")
    import numpy as np
    def verify_seals(header):
        sealer = node.keypair.pub_bytes
        assert list(header.sealer_list) == [sealer]
        hh = header.hash(node.suite)
        ok = node.suite.verify_batch(
            [hh], [header.signature_list[0][1]], [sealer])
        return bool(np.asarray(ok)[0])
    install_snapshot(manifest, chunks, fresh, node.suite, verify_seals)
    led = Ledger(fresh, node.suite)
    assert led.current_number() == head == manifest.height
    assert led.header_by_number(head).hash(node.suite) == want_hash
    assert led.header_by_number(head).state_root == want_root
    # executor state travelled too, not just chain metadata: the balances
    # the register txs wrote must be byte-identical on the imported side
    bal_keys = list(node.storage.keys("c_balance"))
    assert bal_keys and list(fresh.keys("c_balance")) == bal_keys
    for k in bal_keys:
        assert fresh.get("c_balance", k) == node.storage.get("c_balance", k)
    fresh.close()
    print("sanitize_ci: SNAPSHOT STAGE CLEAN "
          f"(height={head}, chunks={manifest.chunk_count}, "
          f"bytes={manifest.total_bytes})")
finally:
    shutil.rmtree(work, ignore_errors=True)
EOF
  echo "== [snapshot] join-time bench row (replay vs snap-sync)"
  JAX_PLATFORMS=cpu timeout -k 10 300 \
    python benchmark/chain_bench.py --sync-bench --sync-blocks 20 \
    2>/dev/null | grep '"metric": "snap_sync_seconds"'
  exit 0
fi

if [ "${1:-}" = "--pipeline" ]; then
  echo "== [pipeline] pipelined block production smoke: 4-node chain," \
       "speculative execution + off-thread commit, byte-identical state"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python - <<'EOF'
import sys, time
sys.path.insert(0, "benchmark")
from chain_bench import _build_chain
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.protocol import Transaction

nodes, gateways, _ = _build_chain(False, "host", 50)
# slow node 0's storage commit slightly so commit(N) reliably overlaps
# the next height's consensus+execution (the smoke must PROVE the
# pipeline engaged, not just that the chain still works)
orig = nodes[0].storage.commit
nodes[0].storage.commit = lambda n, _o=orig: (time.sleep(0.1), _o(n))[1]
suite = nodes[0].suite
kp = suite.generate_keypair(b"pipe-smoke")
txs = [Transaction(to=pc.BALANCE_ADDRESS,
                   input=pc.encode_call(
                       "register",
                       lambda w, i=i: w.blob(b"ps%d" % i).u64(1 + i)),
                   nonce=f"ps-{i}", block_limit=300).sign(suite, kp)
       for i in range(300)]
for node in nodes:
    node.start()
try:
    for s in range(0, 300, 75):
        nodes[(s // 75) % 4].txpool.submit_batch(txs[s:s + 75])
    deadline = time.monotonic() + 240
    while time.monotonic() < deadline:
        if all(n.ledger.total_tx_count() >= 300 for n in nodes):
            break
        time.sleep(0.05)
    assert all(n.ledger.total_tx_count() == 300 for n in nodes), \
        [n.ledger.total_tx_count() for n in nodes]
    stats = nodes[0].scheduler.pipeline_stats()
    assert stats["speculative_execs"] >= 1, \
        f"pipeline never engaged: {stats}"
    # byte-identical replicated state across all 4 nodes: head hash AND
    # the executor's balance table (per-changeset state_root alone does
    # NOT prove full-state equality — see PR 4's c_ prefix lesson)
    head = nodes[0].ledger.current_number()
    want_hash = nodes[0].ledger.header_by_number(head).hash(suite)
    bal_keys = sorted(nodes[0].storage.keys("c_balance"))
    assert bal_keys, "no balance rows written"
    for n in nodes[1:]:
        assert n.ledger.current_number() == head
        assert n.ledger.header_by_number(head).hash(suite) == want_hash
        assert sorted(n.storage.keys("c_balance")) == bal_keys
        for k in bal_keys:
            assert n.storage.get("c_balance", k) == \
                nodes[0].storage.get("c_balance", k)
    print("sanitize_ci: PIPELINE STAGE CLEAN "
          f"(blocks={head}, speculative_execs={stats['speculative_execs']}, "
          f"overlap_commits={stats['overlap_commits']}, "
          f"commit_stage_s={stats['stages'].get('commit', {}).get('seconds')})")
finally:
    for node in nodes:
        node.stop()
    for gw in set(gateways):
        gw.stop()
EOF
  echo "== [pipeline] stage-occupancy bench row"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python benchmark/chain_bench.py -n 1000 --backend host \
    --pipeline-profile 2>/dev/null | grep '"metric": "pipeline_'
  exit 0
fi

if [ "${1:-}" = "--workers" ]; then
  echo "== [workers] out-of-process execution smoke: 4 daemons with" \
       "[scheduler] workers = 1, SIGKILL a worker mid-stream, scheduler" \
       "falls back + health plane respawns, chain converges, clean audit"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python tools/workers_smoke.py
  echo "== [workers] columnar A/B bench row (object vs columnar ingest)"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python benchmark/chain_bench.py --columnar-compare -n 1000 \
    --backend host 2>/dev/null | grep '"metric": "columnar_tps"'
  echo "sanitize_ci: WORKERS STAGE CLEAN"
  exit 0
fi

if [ "${1:-}" = "--seals" ]; then
  echo "== [seals] quorum-certificate smoke: 4 TLS daemons in" \
       "seal_mode=cert, converged heads, clean audit, ONE cert per" \
       "block with fewer wire bytes than its own quorum as loose seals"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python - <<'EOF'
import tempfile
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.sdk.client import TransactionBuilder
from fisco_bcos_tpu.testing.chaos import ChaosHarness

out = tempfile.mkdtemp(prefix="seals-smoke-")
with ChaosHarness(out, tls=True,
                  config_overrides={"seal_mode": "cert"}) as h:
    h.start_all()
    for i in range(h.n):
        h.wait_rpc_up(i)
    suite = h.suite()
    kp = suite.generate_keypair(b"seals-smoke")
    builder = TransactionBuilder(suite, None, chain_id=h.info["chain_id"],
                                 group_id=h.info["group_id"])
    for s in range(8):
        tx = builder.build(kp, pc.BALANCE_ADDRESS,
                           pc.encode_call("register",
                                          lambda w: w.blob(b"s%d" % s)
                                          .u64(1)),
                           nonce=f"s-{s}", block_limit=500)
        h.client(s % h.n).send_transaction(tx, wait=False)
    h.wait_until(lambda: min(h.total_txs(i) for i in range(h.n)) >= 8,
                 timeout=240, what="commits in cert mode")
    height = h.wait_converged(range(h.n), min_height=1, timeout=240)
    ssz = suite.signature_size
    ratios = []
    for i in range(h.n):
        rep = h.audit_report(i)
        assert rep["ok"], (i, rep)
        cons = h.client(i).request("getSystemStatus",
                                   [h.info["group_id"], ""])["consensus"]
        assert cons["sealMode"] == "cert", cons
        signers, cert_bytes = (cons["sealSignersPerBlock"],
                               cons["sealBytesPerBlock"])
        assert signers >= 3 and cert_bytes > 0, cons
        # the SAME quorum as legacy loose seals: i64 idx + blob frame +
        # signature per entry, plus the list length word
        loose = signers * (8 + 4 + ssz) + 8
        assert cert_bytes < loose, (i, cert_bytes, loose)
        ratios.append(round(cert_bytes / loose, 3))
    gauge = [ln for ln in h.metrics_text(0).splitlines()
             if ln.startswith("bcos_consensus_seal_bytes_per_block")]
    assert gauge, "seal-bytes gauge missing from /metrics"
    print(f"sanitize_ci: SEALS STAGE CLEAN (height={height}, "
          f"cert_vs_loose={ratios})")
EOF
  exit 0
fi

if [ "${1:-}" = "--groups" ]; then
  echo "== [groups] multi-group smoke: one daemon, two groups, routed RPC," \
       "cross-group transfer, shared crypto lane"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python - <<'EOF'
import json, shutil, tempfile, threading, time
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.init.daemon import NodeDaemon
from fisco_bcos_tpu.init.node import NodeConfig
from fisco_bcos_tpu.protocol import Transaction
from fisco_bcos_tpu.sdk.client import SdkClient
from fisco_bcos_tpu.tool.config import ChainConfig, save_node_config

work = tempfile.mkdtemp(prefix="groups-smoke-")
try:
    from fisco_bcos_tpu.crypto.suite import make_suite
    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"groups-smoke")
    cfg = NodeConfig(groups=["group0", "group1"], consensus="solo",
                     crypto_backend="host", min_seal_time=0.0,
                     storage_path="data", rpc_port=0, p2p_port=0)
    chain = ChainConfig(consensus_type="solo", sealers=[kp.pub_bytes])
    save_node_config(work, cfg, chain, kp.secret)
    daemon = NodeDaemon(work)
    daemon.start()
    try:
        assert daemon.manager is not None, "daemon did not boot multigroup"
        assert daemon.manager.groups() == ["group0", "group1"]
        url = f"http://127.0.0.1:{daemon.rpc.port}"
        sdk = SdkClient(url)

        def register(group, account, amount, nonce):
            tx = Transaction(to=pc.BALANCE_ADDRESS,
                             input=pc.encode_call(
                                 "register",
                                 lambda w: w.blob(account).u64(amount)),
                             nonce=nonce, group_id=group,
                             block_limit=100).sign(suite, kp)
            return sdk.request("sendTransaction",
                               [group, "", "0x" + tx.encode().hex(),
                                False, True, 30.0])

        # disjoint writes routed by the group param over ONE edge
        rc = register("group0", b"alice", 100, "g0-a")
        assert rc["status"] == 0, rc
        rc = register("group1", b"bob", 7, "g1-b")
        assert rc["status"] == 0, rc
        h0 = sdk.request("getBlockHashByNumber", ["group0", "", 1])
        h1 = sdk.request("getBlockHashByNumber", ["group1", "", 1])
        assert h0 and h1 and h0 != h1, "group heads did not diverge"

        # a real (>1) verify batch through the shared crypto lane: one
        # in-process burst per group, submitted concurrently
        nodes = [daemon.manager.node(g) for g in ("group0", "group1")]
        bursts = [[Transaction(to=pc.BALANCE_ADDRESS,
                               input=pc.encode_call(
                                   "register",
                                   lambda w, i=i: w.blob(
                                       b"%s-%d" % (g.encode(), i)).u64(1)),
                               nonce=f"b-{g}-{i}", group_id=g,
                               block_limit=100).sign(suite, kp)
                   for i in range(64)]
                  for g in ("group0", "group1")]
        ths = [threading.Thread(
            target=lambda n=n, b=b: n.txpool.submit_batch(b), daemon=True)
            for n, b in zip(nodes, bursts)]
        for t in ths: t.start()
        for t in ths: t.join(60)
        lane = daemon.manager.crypto_lane_stats()["ecdsa"]
        assert lane["mean_device_batch"] > 1.0, lane

        # cross-group transfer via RPC settles atomically
        tx = Transaction(to=pc.XSHARD_ADDRESS,
                         input=pc.encode_call(
                             "transferOut",
                             lambda w: w.blob(b"smoke-x").text("group1")
                             .blob(b"alice").blob(b"bob").u64(30)),
                         nonce="x-s", group_id="group0",
                         block_limit=100).sign(suite, kp)
        rc = sdk.request("sendTransaction",
                         ["group0", "", "0x" + tx.encode().hex(),
                          False, True, 30.0])
        assert rc["status"] == 0, rc
        bal_call = pc.encode_call("balanceOf", lambda w: w.blob(b"bob"))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            out = sdk.request("call", ["group1", "",
                                       "0x" + pc.BALANCE_ADDRESS.hex(),
                                       "0x" + bal_call.hex()])
            if int(out["output"][2:], 16) == 37:
                break
            time.sleep(0.1)
        else:
            raise AssertionError("cross-group credit never landed")
        out = sdk.request("call", ["group0", "",
                                   "0x" + pc.BALANCE_ADDRESS.hex(),
                                   "0x" + pc.encode_call(
                                       "balanceOf",
                                       lambda w: w.blob(b"alice")).hex()])
        assert int(out["output"][2:], 16) == 70
        # and an unknown group answers the dedicated error object
        try:
            sdk.request("getBlockNumber", ["nope"])
            raise AssertionError("unknown group did not error")
        except Exception as exc:
            assert "-32004" in str(exc) or "unknown group" in str(exc), exc
        print("sanitize_ci: GROUPS STAGE CLEAN "
              f"(lane_mean_batch={lane['mean_device_batch']}, "
              f"merged_calls={lane['merged_calls']}, "
              f"xshard={daemon.manager.coordinator.stats()})")
    finally:
        daemon.shutdown()
finally:
    shutil.rmtree(work, ignore_errors=True)
EOF
  echo "== [groups] scaling bench row (2 groups vs 1, interleaved medians)"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python benchmark/chain_bench.py --groups 2 --groups-compare \
    --cross-shard-pct 10 -n 1000 --backend host 2>/dev/null \
    | grep '"metric": "groups'
  exit 0
fi

if [ "${1:-}" = "--storage" ]; then
  echo "== [storage] disk-engine smoke: boot disk backend, write," \
       "SIGKILL, re-boot without replay, verify"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python - <<'EOF'
import os, re, shutil, signal, subprocess, sys, tempfile, time
sys.path.insert(0, "tools")
from build_chain import build_chain
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.sdk.client import SdkClient, TransactionBuilder
from fisco_bcos_tpu.crypto.suite import make_suite

work = tempfile.mkdtemp(prefix="storage-smoke-")
proc = None
try:
    from fisco_bcos_tpu.testing.chaos import free_port_block
    port = free_port_block(2)
    info = build_chain(work, 1, consensus="solo", rpc_base_port=port,
                       p2p_base_port=port + 1,
                       crypto_backend="host", storage_backend="disk")
    node_dir = info["nodes"][0]["dir"]
    # flush on every commit: kill -9 lands mid-flush/compaction territory
    cfgp = os.path.join(node_dir, "config.ini")
    cfg = open(cfgp).read()
    cfg = cfg.replace("memtable_mb = 64", "memtable_mb = 0")
    cfg = cfg.replace("compact_segments = 8", "compact_segments = 2")
    open(cfgp, "w").write(cfg)
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def boot():
        return subprocess.Popen(
            [sys.executable, "-m", "fisco_bcos_tpu", node_dir,
             "--log-file", os.path.join(node_dir, "daemon.log")],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)

    def wait_rpc(cli, deadline=120):
        end = time.monotonic() + deadline
        while time.monotonic() < end:
            try:
                return cli.get_block_number()
            except Exception:
                time.sleep(0.25)
        raise TimeoutError("rpc never came up")

    proc = boot()
    cli = SdkClient(f"http://127.0.0.1:{port}", group=info["group_id"])
    wait_rpc(cli)
    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"storage-smoke")
    builder = TransactionBuilder(suite, None, chain_id=info["chain_id"],
                                 group_id=info["group_id"])
    for i in range(6):
        tx = builder.build(kp, pc.BALANCE_ADDRESS,
                           pc.encode_call("register",
                                          lambda w, i=i: w.blob(b"sk%d" % i)
                                          .u64(10 + i)),
                           nonce=f"ss{i}", block_limit=100)
        rc = cli.send_transaction(tx, wait=True)
        assert rc["status"] == 0, rc
    head = cli.get_block_number()
    head_hash = cli.request("getBlockHashByNumber",
                            [info["group_id"], "", head])
    assert head >= 1

    proc.send_signal(signal.SIGKILL)   # no flush, no goodbye
    proc.wait(timeout=30)
    proc = boot()                      # same data dir
    wait_rpc(cli)
    log = open(os.path.join(node_dir, "daemon.log")).read()
    recov = re.findall(r"\[ENGINE\]\[recovered\].*?segments=(\d+)"
                       r".*?wal_records=(\d+)", log)
    assert recov, "no engine recovery badge after kill -9"
    segments, wal_records = map(int, recov[-1])
    assert segments >= 1, "boot found no durable segments"
    assert wal_records <= 6, \
        f"boot replayed {wal_records} WAL records — that is a full replay"
    assert cli.get_block_number() == head
    assert cli.request("getBlockHashByNumber",
                       [info["group_id"], "", head]) == head_hash
    for i in range(6):
        out = cli.request("call", [info["group_id"], "",
                                   "0x" + pc.BALANCE_ADDRESS.hex(),
                                   "0x" + pc.encode_call(
                                       "balanceOf",
                                       lambda w, i=i: w.blob(b"sk%d" % i)
                                   ).hex()])
        assert int(out["output"][2:], 16) == 10 + i
    print("sanitize_ci: STORAGE STAGE CLEAN "
          f"(head={head}, segments={segments}, "
          f"wal_tail_records={wal_records})")
finally:
    if proc is not None and proc.poll() is None:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    shutil.rmtree(work, ignore_errors=True)
EOF
  echo "== [storage] disk-vs-memory bench row"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python benchmark/chain_bench.py --storage-compare -n 400 \
    --tx-count-limit 100 --storage-memtable-mb 1 2>/dev/null \
    | grep '"metric": "storage_compare"'
  echo "== [storage] wide-table scenario: key pages default-on," \
       "read-amp counters live"
  WT_ROW="$(JAX_PLATFORMS=cpu timeout -k 10 900 \
    python benchmark/chain_bench.py --scenario wide-table -n 400 \
    --scenario-accounts 2000 --scenario-window 4 \
    --tx-count-limit 100 2>/dev/null \
    | grep '"metric": "scenario_wide_table"')"
  WT_ROW="$WT_ROW" python - <<'EOF'
import json, os
row = json.loads(os.environ["WT_ROW"])
st = row["storage"]
assert st["key_page_size"] and st["key_page_size"] > 0, \
    f"key pages not on by default for disk: {st}"
assert st["backend_reads"] and st["backend_reads"] > 0, \
    f"read-amp counter backend_reads dead: {st}"
assert st["cache_hits"] and st["cache_hits"] > 0, \
    f"read-amp counter cache_hits dead: {st}"
print("sanitize_ci: STORAGE STAGE read-amp live "
      f"(key_page={st['key_page_size']}B, "
      f"backend_reads={st['backend_reads']}, "
      f"cache_hits={st['cache_hits']})")
EOF
  exit 0
fi

if [ "${1:-}" = "--obs" ]; then
  echo "== [obs] observability smoke: daemon + client traceparent ->" \
       "getTrace by id, /metrics parses, stage sums ~ e2e"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python - <<'EOF'
import http.client, json, os, re, shutil, signal, subprocess, sys
import tempfile, time
sys.path.insert(0, "tools")
from build_chain import build_chain
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.sdk.client import SdkClient, TransactionBuilder
from fisco_bcos_tpu.crypto.suite import make_suite

work = tempfile.mkdtemp(prefix="obs-smoke-")
proc = None
try:
    from fisco_bcos_tpu.testing.chaos import free_port_block
    port = free_port_block(2)
    info = build_chain(work, 1, consensus="solo", rpc_base_port=port,
                       p2p_base_port=port + 1, crypto_backend="host")
    node_dir = info["nodes"][0]["dir"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "fisco_bcos_tpu", node_dir,
         "--log-file", os.path.join(node_dir, "daemon.log")],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env)
    cli = SdkClient(f"http://127.0.0.1:{port}", group=info["group_id"])
    end = time.monotonic() + 120
    while time.monotonic() < end:
        try:
            cli.get_block_number(); break
        except Exception:
            time.sleep(0.25)
    else:
        raise TimeoutError("rpc never came up")

    suite = make_suite(False, backend="host")
    kp = suite.generate_keypair(b"obs-smoke")
    builder = TransactionBuilder(suite, None, chain_id=info["chain_id"],
                                 group_id=info["group_id"])
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    tid = os.urandom(16).hex()
    e2e = []
    for i in range(8):
        tx = builder.build(kp, pc.BALANCE_ADDRESS,
                           pc.encode_call("register",
                                          lambda w, i=i: w.blob(b"ob%d" % i)
                                          .u64(10 + i)),
                           nonce=f"ob{i}", block_limit=100)
        body = json.dumps({"jsonrpc": "2.0", "id": i,
                           "method": "sendTransaction",
                           "params": [info["group_id"], "",
                                      "0x" + tx.encode().hex()]})
        t0 = time.perf_counter()
        # client-supplied W3C traceparent, sampled flag SET: the node
        # must retain this trace regardless of its local sample_rate
        conn.request("POST", "/", body=body.encode(),
                     headers={"traceparent":
                              f"00-{tid}-00f067aa0ba902b7-01"})
        r = conn.getresponse()
        assert r.getheader("traceparent", "").startswith(f"00-{tid}")
        resp = json.loads(r.read())
        assert resp["result"]["status"] == 0, resp
        e2e.append(time.perf_counter() - t0)

    # 1) the trace is retrievable BY ID via RPC and covers the write path
    spans = cli.request("getTrace", [info["group_id"], "", tid])["spans"]
    names = {s["name"] for s in spans}
    assert {"rpc.sendTransaction", "stage.execute", "stage.commit",
            "stage.notify"} <= names, sorted(names)

    # 2) /metrics (served from the RPC event-loop edge) parses cleanly
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    line_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_]+="(\\.|[^"\\])*"'
        r'(,[a-zA-Z_]+="(\\.|[^"\\])*")*\})? [0-9.eE+-]+(\s[0-9]+)?$')
    bad = [l for l in text.splitlines()
           if l and not l.startswith("#") and not line_re.match(l)]
    assert not bad, f"unparseable exposition lines: {bad[:3]}"

    # 3) bcos_tx_stage_seconds stage sums ~ measured e2e: mean per-block
    # stage-sum must land in the same ballpark as the closed-loop mean
    sums = {}
    for m in re.finditer(r'bcos_tx_stage_seconds_sum\{stage="(\w+)"\} '
                         r'([0-9.eE+-]+)', text):
        sums[m.group(1)] = float(m.group(2))
    blocks = cli.get_block_number()
    # stages that lie inside another (crypto and gossip in admit, queueing
    # = seal_wait in round_wait) and the client's own turn are not summed
    stage_mean = sum(v for k, v in sums.items()
                     if k not in ("crypto", "gossip", "queueing",
                                  "rpc_no_request")) / max(1, blocks)
    e2e_mean = sum(e2e) / len(e2e)
    ratio = stage_mean / e2e_mean
    assert 0.2 <= ratio <= 2.0, (sums, stage_mean, e2e_mean)
    print("sanitize_ci: OBS STAGE CLEAN "
          f"(spans={len(spans)}, stages={sorted(sums)}, "
          f"stage_mean={stage_mean*1000:.1f}ms, "
          f"e2e_mean={e2e_mean*1000:.1f}ms, ratio={ratio:.2f})")
finally:
    if proc is not None and proc.poll() is None:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    shutil.rmtree(work, ignore_errors=True)
EOF
  echo "== [obs] trace-profile decomposition row"
  JAX_PLATFORMS=cpu timeout -k 10 600 \
    python benchmark/chain_bench.py --trace-profile --trace-txs 16 \
    --backend host 2>/dev/null | grep '"metric": "trace_profile_summary"'
  exit 0
fi

if [ "${1:-}" = "--faults" ]; then
  echo "== [faults] failpoint/health smoke: 4-node chain, arm one storage" \
       "and one consensus failpoint via the ops endpoint, assert" \
       "convergence + clean getAuditReport + health gauge round-trip"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python - <<'EOF'
import tempfile, time
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.sdk.client import TransactionBuilder
from fisco_bcos_tpu.testing.chaos import ChaosHarness

out = tempfile.mkdtemp(prefix="faults-smoke-")
with ChaosHarness(out, tls=False) as h:
    h.start_all()
    for i in range(h.n):
        h.wait_rpc_up(i)
    # health gauge round-trip while healthy
    code, doc = h.healthz(0)
    assert code == 200 and doc["state"] == "ok", (code, doc)
    gauge = [ln for ln in h.metrics_text(0).splitlines()
             if ln.startswith("bcos_node_health")]
    assert gauge and float(gauge[0].split()[-1]) == 0.0, gauge

    suite = h.suite()
    kp = suite.generate_keypair(b"faults-smoke")
    builder = TransactionBuilder(suite, None, chain_id=h.info["chain_id"],
                                 group_id=h.info["group_id"])
    sent = 0
    def burst(n):
        global sent
        for _ in range(n):
            tx = builder.build(kp, pc.BALANCE_ADDRESS,
                               pc.encode_call("register",
                                              lambda w: w.blob(b"s%d" % sent)
                                              .u64(1)),
                               nonce=f"s-{sent}", block_limit=500)
            h.client(sent % h.n).send_transaction(tx, wait=False)
            sent += 1
    burst(4)
    h.wait_until(lambda: min(h.total_txs(i) for i in range(h.n)) >= 2,
                 timeout=180, what="baseline commits")

    # one STORAGE failpoint + one CONSENSUS-pipeline failpoint, armed at
    # runtime over the ops endpoint, each firing a handful of times
    h.arm_failpoint(1, "storage.wal.append_before_fsync", "enospc*2")
    h.arm_failpoint(2, "scheduler.2pc.commit", "raise*2")
    burst(8)
    h.wait_until(lambda: min(h.total_txs(i) for i in range(h.n)) >= 8,
                 timeout=240, what="commits through the armed faults")
    height = h.wait_converged(range(h.n), min_height=1, timeout=240)
    for i in range(h.n):
        rep = h.audit_report(i)
        assert rep["ok"], (i, rep)
        fps = h.failpoints(i)
        assert "scheduler.2pc.commit" in fps["sites"], fps
    # every node back to ok (faults exhausted their budgets + self-healed)
    h.wait_until(lambda: all(h.healthz(i)[0] == 200 for i in range(h.n)),
                 timeout=120, what="health returned to ok on every node")
    print(f"sanitize_ci: FAULTS STAGE CLEAN (height={height}, "
          f"txs={min(h.total_txs(i) for i in range(h.n))})")
EOF
  exit 0
fi

if [ "${1:-}" = "--overload" ]; then
  echo "== [overload] brownout smoke: 4 real daemons, aggressor floods a" \
       "rate-limited edge while a polite client keeps committing;" \
       "-32005 observed, health returns to ok after the storm"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python - <<'EOF'
import tempfile, threading, time
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.sdk.client import RpcCallError, SdkClient, \
    TransactionBuilder
from fisco_bcos_tpu.testing.chaos import ChaosHarness

out = tempfile.mkdtemp(prefix="overload-smoke-")
STORM_S = 8.0
with ChaosHarness(out, tls=False,
                  config_overrides={"client_write_rate": 20.0,
                                    "txpool_limit": 2000}) as h:
    h.start_all()
    for i in range(h.n):
        h.wait_rpc_up(i)
    suite = h.suite()
    kp = suite.generate_keypair(b"overload-smoke")
    builder = TransactionBuilder(suite, None, chain_id=h.info["chain_id"],
                                 group_id=h.info["group_id"])
    port = h.info["nodes"][0]["rpc_port"]
    stop = threading.Event()
    stats = {"r32005": 0, "aggr_ok": 0, "pol_lat": [], "errors": []}

    def aggressor(w):
        sdk = SdkClient(f"http://127.0.0.1:{port}",
                        group=h.info["group_id"], api_key="aggr")
        i = 0
        while not stop.is_set():
            tx = builder.build(kp, pc.BALANCE_ADDRESS,
                               pc.encode_call("register",
                                              lambda w2: w2.blob(
                                                  b"ag%d-%d" % (w, i))
                                              .u64(1)),
                               nonce=f"ag-{w}-{i}", block_limit=500)
            i += 1
            try:
                sdk.send_transaction(tx, wait=False)
                stats["aggr_ok"] += 1
            except RpcCallError as exc:
                if exc.code == -32005:
                    stats["r32005"] += 1
            except Exception as exc:
                stats["errors"].append(f"aggr: {exc}")
                return

    def polite():
        sdk = SdkClient(f"http://127.0.0.1:{port}",
                        group=h.info["group_id"], api_key="polite",
                        timeout=30.0)
        i = 0
        while not stop.is_set():
            tx = builder.build(kp, pc.BALANCE_ADDRESS,
                               pc.encode_call("register",
                                              lambda w2: w2.blob(
                                                  b"po%d" % i).u64(1)),
                               nonce=f"po-{i}", block_limit=500)
            i += 1
            t0 = time.perf_counter()
            try:
                sdk.send_transaction(tx, wait=True)  # full commit RTT
                stats["pol_lat"].append(time.perf_counter() - t0)
            except Exception as exc:
                stats["errors"].append(f"polite: {exc}")
                return
            time.sleep(0.2)  # ~5/s: well inside its own budget

    threads = [threading.Thread(target=aggressor, args=(w,), daemon=True)
               for w in range(2)] + [threading.Thread(target=polite,
                                                      daemon=True)]
    for t in threads:
        t.start()
    time.sleep(STORM_S)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    assert not stats["errors"], stats["errors"][:3]
    lat = sorted(stats["pol_lat"])
    assert lat, "polite client never completed a commit"
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))]
    # the polite client's commits stay bounded THROUGH the storm
    assert p99 < 10.0, f"polite commit p99 {p99:.1f}s"
    assert stats["r32005"] > 0, "aggressor was never rate limited"
    # the overload/admission surfaces are live on the ops plane
    code, doc = h._ops_get(0, "/status")
    assert code == 200 and doc.get("admission"), doc.get("admission")
    assert doc["admission"]["rejected_writes"] > 0 or \
        doc["admission"]["rejected_fair_share"] > 0, doc["admission"]
    # after the storm: every node back to ok (busy cleared, nothing stuck)
    h.wait_until(lambda: all(
        h.healthz(i)[0] == 200 and h.healthz(i)[1]["state"] == "ok"
        for i in range(h.n)), timeout=120,
        what="health back to ok on every node")
    print(f"sanitize_ci: OVERLOAD STAGE CLEAN "
          f"(polite_p99={p99*1000:.0f}ms over {len(lat)} commits, "
          f"rate_limited={stats['r32005']}, "
          f"aggr_admitted={stats['aggr_ok']})")
EOF
  echo "== [overload] chain_bench --overload goodput/fairness rows"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python benchmark/chain_bench.py --overload -n 600 \
    --overload-window 3 --overload-ab-runs 1 --overload-fairness-s 6 \
    --backend host 2>/dev/null | grep -E \
    '"metric": "overload_(goodput|fairness|seal_integrity)"'
  exit 0
fi

if [ "${1:-}" = "--zk" ]; then
  echo "== [zk] proof plane smoke: real daemons, getProof over RPC," \
       "client-side verification, tamper-detect, batched verifyProofs"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python - <<'EOF'
import tempfile
from fisco_bcos_tpu.executor import precompiled as pc
from fisco_bcos_tpu.executor.executor import state_leaf_payload
from fisco_bcos_tpu.sdk.client import TransactionBuilder
from fisco_bcos_tpu.testing.chaos import ChaosHarness
from fisco_bcos_tpu.zk import proof as zkproof


def unhex(s):
    return bytes.fromhex(s[2:] if s.startswith("0x") else s)


out = tempfile.mkdtemp(prefix="zk-smoke-")
with ChaosHarness(out, tls=False) as h:
    h.start_all()
    for i in range(h.n):
        h.wait_rpc_up(i)
    suite = h.suite()
    builder = TransactionBuilder(suite, None, chain_id=h.info["chain_id"],
                                 group_id=h.info["group_id"])
    kp = suite.generate_keypair(b"zk-smoke")
    sdk = h.client(0)
    # fire-and-forget so several txs share a block (multi-level proofs),
    # then poll receipts
    tx_hashes = []
    for i in range(6):
        tx = builder.build(kp, pc.BALANCE_ADDRESS,
                           pc.encode_call("register",
                                          lambda w, i=i: w.blob(
                                              b"zk%d" % i).u64(1 + i)),
                           nonce=f"zk-{i}", block_limit=500)
        r = sdk.send_transaction(tx, wait=False)
        tx_hashes.append(unhex(r["transactionHash"]))
    h.wait_until(lambda: all(
        sdk.get_transaction_receipt("0x" + th.hex()) is not None
        for th in tx_hashes), timeout=180, what="zk txs committed")
    group = h.info["group_id"]

    checked = 0
    for th in tx_hashes:
        doc = sdk.request("getProof", [group, "", "0x" + th.hex()])
        assert doc["found"], doc
        # anchor the roots to the node's committed header (the light
        # client would quorum-verify this header's seals; the in-repo
        # test suite covers that path over p2p)
        hdr = sdk.get_block_by_number(doc["blockNumber"], only_header=True)
        assert unhex(doc["txsRoot"]) == unhex(hdr["txsRoot"])
        assert unhex(doc["receiptsRoot"]) == unhex(hdr["receiptsRoot"])
        items = [(th, zkproof.w16_proof_from_json(doc["txProof"]),
                  unhex(doc["txsRoot"]))]
        ok = zkproof.verify_inclusion_batch(suite, items)
        assert ok.all(), "tx proof rejected"
        # tampered leaf / root / proof sibling must all reject
        leaf, proof, root = items[0]
        bad_leaf = bytes([leaf[0] ^ 1]) + leaf[1:]
        assert not zkproof.verify_inclusion_batch(
            suite, [(bad_leaf, proof, root)]).any()
        assert not zkproof.verify_inclusion_batch(
            suite, [(leaf, proof, b"\x05" * 32)]).any()
        if proof:
            sibs, pos = proof[0]
            forged = [([b"\x06" * 32] * len(sibs), pos)] + proof[1:]
            assert not zkproof.verify_inclusion_batch(
                suite, [(leaf, forged, root)]).any()
        checked += 1

    # batched verifyProofs: N good + 1 forged in ONE call
    docs = [sdk.request("getProof", [group, "", "0x" + th.hex()])
            for th in tx_hashes]
    proofs = [{"leaf": "0x" + th.hex(), "proof": d["txProof"],
               "root": d["txsRoot"]} for th, d in zip(tx_hashes, docs)]
    proofs.append({"leaf": "0x" + b"\x09".hex() * 32,
                   "proof": docs[0]["txProof"],
                   "root": docs[0]["txsRoot"]})
    res = sdk.request("verifyProofs", [group, "", proofs])
    assert res["results"][:-1] == [True] * len(tx_hashes), res
    assert res["results"][-1] is False
    assert res["verified"] == len(tx_hashes)

    # state proof: prove the head block's write of a c_balance row, with
    # the leaf recomputed client-side from the claimed value
    n = docs[-1]["blockNumber"] if docs else 1
    sp = sdk.request("getProof", [group])  # no-op shape check
    doc = sdk.request("getProof",
                      {"group": group, "number": n,
                       "state_keys": [["c_balance", "0x" + b"zk5".hex()]]})
    entry = doc["stateEntries"][0]
    assert entry["present"], entry
    value = (6).to_bytes(16, "big")  # register zk5 -> 1 + 5, 16-byte be
    leaf = suite.hash(state_leaf_payload("c_balance", b"zk5", value))
    assert leaf == unhex(entry["leafDigest"]), "state leaf mismatch"
    hdr = sdk.get_block_by_number(n, only_header=True)
    assert unhex(entry["stateRoot"]) == unhex(hdr["stateRoot"])
    ok = zkproof.verify_inclusion_batch(
        suite, [(leaf, zkproof.w16_proof_from_json(entry["stateProof"]),
                 unhex(entry["stateRoot"]))])
    assert ok.all(), "state proof rejected"
    # lying value -> different leaf -> rejected
    bad = suite.hash(state_leaf_payload("c_balance", b"zk5",
                                        (7).to_bytes(8, "big")))
    assert not zkproof.verify_inclusion_batch(
        suite, [(bad, zkproof.w16_proof_from_json(entry["stateProof"]),
                 unhex(entry["stateRoot"]))]).any()

    # the zk counters are live on the status plane
    code, st = h._ops_get(0, "/status")
    assert code == 200 and st.get("zk", {}).get("proofsVerified", 0) > 0, \
        st.get("zk")
    print(f"sanitize_ci: ZK STAGE CLEAN (proofs_checked={checked}, "
          f"verify_batch={res['verified']}+1neg, "
          f"zk_status={st['zk']})")
EOF
  echo "== [zk] chain_bench --proof-bench rows"
  JAX_PLATFORMS=cpu timeout -k 10 900 \
    python benchmark/chain_bench.py --proof-bench --proof-txs 60 \
    --backend host 2>/dev/null | grep -E \
    '"metric": "(poseidon_hashes|proofs_(rendered|served|verified))_per_sec"'
  exit 0
fi

if [ "${1:-}" = "--gameday" ]; then
  echo "== [gameday] ci-smoke fault schedule on a real 4-node cluster:" \
       "kill -9 + asymmetric partition/heal + armed WAL-crash failpoint" \
       "under scenario load; clean audit, converged heads, health SLO," \
       "bounded write p99, byte-identical c_balance"
  GD_OUT="$(mktemp -d)"
  JAX_PLATFORMS=cpu timeout -k 15 1200 \
    python tools/gameday.py --schedule ci-smoke \
    -o "$GD_OUT/cluster" --report "$GD_OUT/report.json" \
    | tee "$GD_OUT/rows.jsonl"
  grep -q '"metric": "gameday_post_soak_tps"' "$GD_OUT/rows.jsonl"
  echo "== [gameday] perf gate, report-only, gameday_* rows vs trajectory"
  python tools/perf_gate.py --candidate "$GD_OUT/rows.jsonl" --report-only
  rm -rf "$GD_OUT"
  echo "sanitize_ci: GAMEDAY STAGE CLEAN"
  exit 0
fi

if [ "${1:-}" = "--chaos" ]; then
  echo "== [chaos] crash/fault e2e: kill -9 rejoin, leader view change," \
       "degraded link (4 OS processes, SM-TLS, real JSON-RPC)"
  JAX_PLATFORMS=cpu \
    python -m pytest tests/test_chaos_e2e.py -q -m slow -p no:cacheprovider
  echo "sanitize_ci: CHAOS STAGE CLEAN"
  exit 0
fi

# default full gate: the static/lint plane runs FIRST (cheapest, catches
# the most common regression class before any sanitizer rebuild)
run_lint_stage

LIBASAN="$(g++ -print-file-name=libasan.so)"
LIBTSAN="$(g++ -print-file-name=libtsan.so)"
LIBSTDCPP="$(g++ -print-file-name=libstdc++.so.6)"

echo "== [1/5] ASan+UBSan build (nevm, ncrypto, bcoskv)"
make -C native SANITIZE=address -j"$(nproc)"

echo "== [2/5] ASan+UBSan: native EVM + EC + storage suites"
# libstdc++ must ride LD_PRELOAD beside libasan: the EVM's C++ exceptions
# trip the __cxa_throw interceptor CHECK under dlopen otherwise (runtime
# artifact, not a library bug)
ASAN_OPTIONS=detect_leaks=0 \
  LD_PRELOAD="$LIBASAN $LIBSTDCPP" \
  FBTPU_NEVM_LIB=native/build/libnevm.asan.so \
  FBTPU_NCRYPTO_LIB=native/build/libncrypto.asan.so \
  FBTPU_BCOSKV_LIB=native/build/libbcoskv.asan.so \
  python -m pytest tests/test_nevm.py tests/test_nativeec.py \
      tests/test_native_storage.py -q -x

if [ "$FAST" = 0 ]; then
  echo "== [3/5] ASan+UBSan: deep differential fuzz (Python vs native EVM)"
  ASAN_OPTIONS=detect_leaks=0 \
    LD_PRELOAD="$LIBASAN $LIBSTDCPP" \
    FBTPU_NEVM_LIB=native/build/libnevm.asan.so \
    python -m pytest tests/test_nevm.py -q -x -m slow
else
  echo "== [3/5] SKIPPED (--fast): deep differential fuzz"
fi

echo "== [4/5] TSan build + native-storage race stress"
make -C native SANITIZE=thread -j"$(nproc)"
TSAN_OPTIONS="ignore_noninstrumented_modules=1" \
  LD_PRELOAD="$LIBTSAN $LIBSTDCPP" \
  FBTPU_BCOSKV_LIB=native/build/libbcoskv.tsan.so \
  python -m pytest tests/test_native_storage.py tests/test_race_stress.py \
      -q -x

echo "== [5/5] continuous-profiling smoke + perf gate (report-only)"
run_profile_stage

echo "sanitize_ci: ALL STAGES CLEAN"
