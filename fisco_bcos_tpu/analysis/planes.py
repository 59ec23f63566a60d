"""Execution-plane contracts — ONE declaration, consumed by two layers.

The repo runs as a small set of long-lived threads ("planes"), each with a
job narrow enough to carry a *contract* about what it must never do: the
RPC event loop multiplexes every keep-alive socket, so one fsync on it
stalls every client; the commit notifier fans a durable commit out to
observers, so one blocking socket send on it stalls commit notification
for the whole node (the PR-13 WS finding); the crypto-lane dispatcher
feeds the device, so a host sync mid-merge serialises every group's
batches. The hardware-BFT line (PAPERS.md, arxiv 1612.04997) is the
architectural argument: consensus-thread code must stay free of blocking
edges or message crypto becomes the scalability bound.

Consumers:
  * tools/bcosflow.py — the whole-program analyzer: classifies thread
    roots into these planes (via analysis/profiler's thread-role registry
    plus the tables below) and propagates blocking-effect summaries over
    the interprocedural call graph to enforce each contract statically.
  * humans — the README "plane contract" table renders from this file's
    semantics; keep them in sync.

Blocking-effect kinds are analysis/lockorder.BLOCKING_KINDS (`fsync`,
`socket_send`, `suite_batch`, `subprocess`, `sleep`) — the same vocabulary
the runtime lockcheck markers and the bcoslint lexical rule use.
"""

from __future__ import annotations

# plane -> frozenset of forbidden blocking kinds. A plane absent here (or
# mapped to an empty set) carries no contract: worker-pool jobs EXIST to
# block, WS session readers reply synchronously on their own thread.
PLANE_CONTRACTS: dict[str, frozenset] = {
    # ONE thread owns every RPC socket (rpc/edge.py); anything blocking
    # on it is a node-wide stall. Its own non-blocking sock.send() is not
    # a blocking kind — sendall on it would be.
    "edge": frozenset({"fsync", "socket_send", "suite_batch",
                       "subprocess", "sleep"}),
    # scheduler commit-notifier: observers run after every durable
    # commit; a blocking observer stalls commit notification repo-wide.
    "notify": frozenset({"fsync", "socket_send", "suite_batch",
                         "subprocess", "sleep"}),
    # PBFT consensus worker: blocking edges here stretch every round's
    # RTT (consensus_pre/wait already dominate the committed-tx p50).
    # suite_batch is deliberately ALLOWED — verifying proposals is the
    # engine's job; the lane merges it with everyone else's batches.
    "pbft": frozenset({"fsync", "subprocess", "sleep"}),
    # sealer loop: fills proposals; durability belongs to the commit
    # stage, never to sealing.
    "seal": frozenset({"fsync", "subprocess"}),
    # crypto-lane dispatcher: the device feed; a sleep or disk write here
    # starves every group's crypto at once.
    "lane": frozenset({"fsync", "socket_send", "subprocess", "sleep"}),
    # ingest-lane dispatcher: admission batching; crypto (suite_batch)
    # is its job, disk and sockets are not.
    "ingest": frozenset({"fsync", "socket_send", "subprocess"}),
    # scheduler commit worker: the 2PC + WAL fsync IS this thread's job —
    # and in the split-service deployment so is the prepare/commit RPC to
    # the remote storage participant (socket_send allowed for that).
    "commit": frozenset({"subprocess"}),
    # block-sync / snapshot workers: they fsync installs by design.
    "sync": frozenset({"subprocess"}),
    # p2p reader/writer + gateway delivery threads: frame plumbing only.
    "net": frozenset({"fsync", "subprocess"}),
    # storage compactor: merges segments (fsync is the job).
    "compaction": frozenset({"socket_send", "subprocess", "suite_batch"}),
    # WS outbox drainer (rpc/ws_server _push_loop): sends best-effort
    # frames — sending is the job, everything else is not.
    "outbox": frozenset({"fsync", "subprocess", "suite_batch"}),
}

# Thread-name prefixes whose profiler role (analysis/profiler._ROLE_PREFIXES,
# the subsystem a thread's CPU is charged to) is too coarse for contract
# purposes. Consulted FIRST: the profiler puts every "ws-" thread and the
# subscription fan-out under "edge" and the crypto lane's dispatcher under
# "crypto", which is right for CPU by role but not here: the event loop
# must not send, the per-session WS reader and the fan-out may, and the
# dispatcher carries the lane's own contract.
EXTRA_ROLE_PREFIXES: tuple[tuple[str, str], ...] = (
    ("ws-push", "outbox"),
    ("ws-dispatch", "worker"),
    ("ws-", "ws-session"),
    ("sub-fanout", "other"),
    ("tx-sync", "sync"),
    ("svc-", "worker"),
    ("crypto-lane", "lane"),
)

# Roots whose thread name is dynamic at the spawn site (name=self._name
# etc.) — keyed by bcosflow qualname (module path minus the package
# prefix), value = plane.
ROOT_OVERRIDES: dict[str, str] = {
    "rpc.edge.EventLoopHttpServer._loop": "edge",
    "rpc.edge.WorkerPool._run": "worker",
    "scheduler.scheduler.Scheduler._notify_loop": "notify",
    "scheduler.scheduler.Scheduler._commit_loop": "commit",
    "utils.worker.Worker._run": "other",  # concrete plane = subclass's
}

# Callback-registration APIs: a function VALUE passed through one of
# these runs on the named plane, not the caller's. This is how the
# analyzer sees through the one layer of indirection that hid the PR-13
# WS bug (commit observer -> eventsub pump -> socket send).
CALLBACK_PLANES: dict[str, str] = {
    "add_commit_observer": "notify",   # scheduler commit fan-out
    "try_submit": "worker",            # rpc/edge WorkerPool
    "submit": "worker",                # thread-pool style executors
    "call_soon": "edge",               # (future-proofing; unused today)
}

# Constructor keyword callbacks: (class name, kwarg) -> plane the callback
# runs on. WsServer invokes these from per-session reader threads.
CTOR_CALLBACK_KWARGS: dict[tuple[str, str], str] = {
    ("WsServer", "on_message"): "ws-session",
    ("WsServer", "on_open"): "ws-session",
    ("WsServer", "on_close"): "ws-session",
}

# Module prefixes (repo-relative) where host<->device syncs are the
# SANCTIONED demux boundary of the crypto lane: the dispatcher's _do_*
# handlers and the suite's batch entry points materialise device results
# ONCE per merged batch. A host sync reachable from the lane anywhere
# DEEPER (ops/, zk/ kernels) is a mid-pipeline stall — the recompile/sync
# hazards the padding-bucket discipline exists to prevent.
LANE_SYNC_BOUNDARY: tuple[str, ...] = (
    "fisco_bcos_tpu/crypto/",
)

# Planes whose reachable code is the wire->lane->seal hot path: the
# per-item-allocation pass (bcosflow rule `hot-loop-alloc`) only reports
# inside these, as the guard rail for the ROADMAP-1 columnar refactor
# (the Blockchain Machine's typed-dataflow contract: pipeline stages
# never re-materialise per-item Python objects).
HOT_PATH_PLANES: frozenset = frozenset({"ingest", "lane", "seal"})

# ... and only inside these module prefixes: the validate pipeline's data
# plane. Connection plumbing (net/, services/) is reachable from the same
# roots but runs per-connection, not per-item — flagging its loops would
# drown the signal the rule exists for.
HOT_ALLOC_SCOPE: tuple[str, ...] = (
    "fisco_bcos_tpu/txpool/",
    "fisco_bcos_tpu/crypto/",
    "fisco_bcos_tpu/protocol/",
    "fisco_bcos_tpu/sealer/",
)

# Columnar substrate entry points (ROADMAP-1, landed): these ARE the hot
# path now — wire frames enter as batches here from the ingest lane, the
# gossip receiver and the RPC edge, so the per-item-allocation guard rail
# must cover everything they reach even when a caller sits outside the
# thread-root planes above (e.g. submit_columns called straight off the
# p2p reader). Keyed by bcosflow qualname, value = plane label.
HOT_PATH_EXTRA_ROOTS: dict[str, str] = {
    "protocol.columnar.decode_columns": "ingest",
    "txpool.txpool.TxPool.submit_columns": "ingest",
}
