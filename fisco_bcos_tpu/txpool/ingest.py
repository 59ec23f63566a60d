"""IngestLane — continuous-batching front door for the txpool.

The framework's thesis is batch-first validation (`TxPool.submit_columns`
-> ONE device recover per packet), yet the serving edge defeats it when
every JSON-RPC `sendTransaction` calls `submit(tx)` — a batch of one —
so each independent client pays a full recover (~162 us native; device
amortization needs hundreds of lanes to win, see PERF.md). Hardware
validators get their wins exactly by aggregating independent submissions
in front of the verify engine (Blockchain Machine, arXiv:2104.06968;
FPGA ECDSA engine, arXiv:2112.02229); inference servers call the same
shape continuous batching. This lane is that aggregation layer:

  * concurrent submitters enqueue (wire frame, future, span context) into
    a BOUNDED queue — a full queue rejects with `TxPoolIsFull` instead of
    growing without bound (admission control, not buffering); a frame is
    never decoded into a `Transaction` on the way;
  * one dispatcher thread drains up to `max_batch` frames per cycle and
    makes ONE `protocol.columnar.decode_columns` and ONE
    `TxPool.submit_columns` call for the drained set, resolving each
    submitter's future with its per-tx result;
  * the coalescing window is ADAPTIVE: near-zero when idle (a lone tx is
    dispatched immediately, no latency tax), growing toward
    `max_wait_ms` as the arrival rate rises, and sized against the
    crypto suite's padding buckets (crypto.suite.BUCKETS) so drained
    batches land on compiled-executable boundaries instead of paying a
    bucket's padding for a handful of txs.

Producers wired through the lane: `rpc/server.py` send_transaction (HTTP
and WS share `JsonRpcImpl`; `submit_wire`, `submit_wire_cohort`),
`net/txsync.py` gossip ingestion (`submit_many_wire_nowait`), and the
in-process `Node.send_transaction` surface (`submit`, which queues the
transaction's frame). `TransactionSync.fetch_missing` calls
`submit_columns` itself: it already holds a full batch and needs its
results synchronously inside proposal verification.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional, Sequence

from ..analysis import lockcheck as lc
from ..protocol import Transaction
from ..protocol.columnar import decode_columns
from ..utils import otrace
from ..utils.log import LOG, badge, metric
from ..utils.metrics import REGISTRY
from ..utils.task import Task
from .txpool import TxSubmitResult

from ..crypto.suite import BUCKETS as _SUITE_BUCKETS

# batch-size histogram / coalescing-target buckets: derived from the
# suite's padding buckets so the lane tracks any retuning of the
# compiled-executable grid (1 prepended: a lone idle tx is its own batch)
_SIZE_BUCKETS = (1,) + tuple(_SUITE_BUCKETS)

# the most independent submissions one dispatch coalesces on a chain whose
# blocks are smaller than this: the suite's 4096 bucket, one device call
COALESCE_BATCH = 4096


def lane_limits(tx_count_limit: int, txpool_limit: int) -> tuple[int, int]:
    """-> (max_batch, queue_cap) of a node's lane, from what the chain and
    the pool say; neither is a knob.

    One dispatch hands `admit` at most a full block (a client's batch of
    up to `tx_count_limit` reaches the pool, the recover and the sealer
    as one piece), and on a chain of small blocks up to COALESCE_BATCH,
    as far as the pool has room beside a sealed block that waits for its
    commit. The queue holds one batch more than the one in admission.
    tx_count_limit 1000 under the pool's default 15000 gives 4096 and
    8192, the constants these were."""
    max_batch = max(1, tx_count_limit,
                    min(COALESCE_BATCH, txpool_limit - tx_count_limit))
    return max_batch, 2 * max_batch


class TxPoolIsFull(RuntimeError):
    """Ingest queue at capacity — backpressure, not an internal error.

    Carries no result object: the tx never entered admission. RPC maps it
    to TransactionStatus.TXPOOL_FULL for wire compatibility."""


class LaneStopped(RuntimeError):
    """Submission raced the lane's shutdown. Distinct from arbitrary
    dispatch errors so callers can fall back to the direct pool path
    WITHOUT mistaking an already-admitted batch's failure for it."""


class _Entry:
    __slots__ = ("wire", "task", "t_enq", "ctx")

    def __init__(self, wire: bytes, task: Optional[Task], ctx=None):
        self.wire = wire  # the raw frame: a column row at dispatch
        self.task = task  # None: fire-and-forget (gossip), nobody awaits
        self.t_enq = time.monotonic()
        # otrace span context of the submitting trace (None when the
        # submission isn't traced): the dispatcher records this entry's
        # queue-to-admission span under it and hands it to the pool with
        # the row, whose view carries it on to the sealer and gossip
        self.ctx = ctx


class IngestLane:
    """Coalesces concurrent submissions into device-sized
    `submit_columns` calls. Thread-safe; one dispatcher thread."""

    def __init__(self, txpool, max_batch: int = 4096,
                 max_wait_ms: float = 15.0, queue_cap: int = 8192,
                 broadcast: bool = True, registry=None,
                 trace_label: str = ""):
        self.txpool = txpool
        self.trace_label = trace_label  # span node attribution
        self.stages = otrace.stages(trace_label)
        # `lane_wait`: open from the first entry queued (under _cv) until
        # the dispatcher takes the batch that holds it
        self._lane_wait: Optional[otrace.Stage] = None
        # metrics sink: a multi-group node passes a group-labeled view
        # (utils.metrics.for_group) so G lanes don't silently aggregate
        self._reg = registry if registry is not None else REGISTRY
        self.max_batch = max(1, int(max_batch))
        self.max_wait = max(0.0, float(max_wait_ms)) / 1000.0
        self.queue_cap = max(1, int(queue_cap))
        self.broadcast = broadcast
        self._q: deque[_Entry] = deque()
        self._cv = lc.make_condition("ingest.queue")
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # EWMA arrival rate (txs/sec) and mean dispatched batch size,
        # updated once per dispatch cycle — steer the coalescing window
        # without per-enqueue bookkeeping. The batch EWMA is the
        # closed-loop load signal: concurrent submitters each have at
        # most one tx in flight, so a depressed arrival RATE can coexist
        # with heavy concurrency (every submitter blocked on a dispatch),
        # and batches > 1 are the reliable tell.
        self._rate = 0.0
        self._batch_ewma = 1.0
        # EWMA of the intra-batch arrival gap (spread between a batch's
        # first and last enqueue over its size): the quiesce threshold is
        # "a few typical gaps of silence", so tightly-clustered closed-loop
        # cohorts dispatch within ~ms of assembling while slow open-loop
        # trickles still coalesce over the patient window
        self._gap_ewma = 0.0
        self._last_dispatch = time.monotonic()
        # totals for stats()/bench (the registry mirrors them as metrics)
        self._txs_total = 0
        self._batches_total = 0
        self._rejected_total = 0
        self._dropped_total = 0

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop = False
        self._thread = threading.Thread(target=self._run, name="tx-ingest",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Stop the dispatcher, draining the queue first so no submitter is
        left holding an unsettled future."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                # wedged dispatcher (e.g. stuck inside admission):
                # keep the reference so a later start() can't spawn a
                # SECOND dispatcher over the same queue — the lane stays
                # stopped and callers use their direct-path fallbacks
                LOG.error(badge("INGEST", "dispatcher-wedged-at-stop"))
                return
            self._thread = None
        # anything still queued (dispatcher died / join timed out): reject
        with self._cv:
            leftovers = list(self._q)
            self._q.clear()
        for e in leftovers:
            if e.task is not None:
                e.task.reject(LaneStopped("ingest lane stopped"))

    # -- producer API ------------------------------------------------------
    def submit_async(self, tx: Transaction) -> Task:
        """Enqueue one tx (its frame, under its own trace context or the
        thread's); -> Task[TxSubmitResult]. Raises TxPoolIsFull when the
        queue is at capacity (bounded-memory backpressure)."""
        with otrace.ctx_scope(getattr(tx, "_otrace", None)):
            return self.submit_wire_cohort([tx.encode()])[0]

    def submit(self, tx: Transaction, timeout: float = 30.0
               ) -> TxSubmitResult:
        """Blocking single-tx submission through the batching lane."""
        return self.submit_async(tx).result(timeout)

    def submit_wire_cohort(self, raws: Sequence[bytes]) -> list[Task]:
        """Enqueue a cohort of raw wire frames UNDER ONE LOCK HOLD; ->
        [Task[TxSubmitResult]] in order. The dispatcher can only ever see
        all of them or none, so a client's JSON-RPC batch reaches the
        batch recover as one piece — enqueued one by one, the dispatcher
        wakes on the first frame and drains whatever trickled in, which
        is how a 1,000-tx batch became batches of a few dozen, none of
        them device-sized. All-or-nothing: raises TxPoolIsFull when the
        cohort does not fit."""
        ctx = otrace.current()
        entries = [_Entry(raw, Task(), ctx) for raw in raws]
        with self._cv:
            if self._stop:
                raise LaneStopped("ingest lane stopped")
            if len(self._q) + len(entries) > self.queue_cap:
                self._rejected_total += len(entries)
                self._reg.inc("bcos_ingest_rejected_total", len(entries))
                raise TxPoolIsFull(
                    f"ingest queue at capacity ({self.queue_cap})")
            self._q.extend(entries)
            self._queued_locked()
            depth = len(self._q)
            self._cv.notify_all()
        self._reg.set_gauge("bcos_ingest_queue_depth", depth)
        return [e.task for e in entries]

    def submit_wire(self, raw: bytes, timeout: float = 30.0
                    ) -> TxSubmitResult:
        """Blocking single-frame submission (a lone `sendTransaction`)."""
        return self.submit_wire_cohort([raw])[0].result(timeout)

    def submit_many_wire_nowait(self, wires: Sequence[bytes]) -> int:
        """Fire-and-forget bulk enqueue of raw wire frames (gossip
        ingestion): accepts what fits under the cap and DROPS the rest
        (-> count accepted). Gossip may drop under overload — the pool
        anti-entropy sweep re-delivers; blocking the p2p reader thread on
        a full queue would back the network plane up behind the verify
        engine instead."""
        if not wires:
            return 0
        accepted = 0
        with self._cv:
            if self._stop:
                return 0
            room = self.queue_cap - len(self._q)
            for w in wires[:max(0, room)]:
                self._q.append(_Entry(w, None))
                accepted += 1
            depth = len(self._q)
            dropped = len(wires) - accepted
            self._dropped_total += dropped
            if accepted:
                self._queued_locked()
                self._cv.notify_all()
        if dropped:
            self._reg.inc("bcos_ingest_dropped_total", dropped)
            metric("ingest.drop", n=dropped)
        self._reg.set_gauge("bcos_ingest_queue_depth", depth)
        return accepted

    def _queued_locked(self) -> None:
        if self._lane_wait is None:
            self._lane_wait = self.stages.stage("lane_wait",
                                                t0=self._q[0].t_enq)

    # -- adaptive coalescing -----------------------------------------------
    def _plan(self, queued: int) -> tuple[int, float]:
        """-> (target_batch, window_seconds) for this cycle.

        Idle (low arrival rate AND recent batches of ~1): dispatch
        immediately — a lone RPC tx must not pay a coalescing tax. Under
        load (either signal): target the smallest padding bucket covering
        what's queued plus the load estimate (capped at max_batch) so the
        drained batch fills the executable it will be padded to, and open
        a window toward max_wait. The dispatcher additionally early-exits
        the window when arrivals quiesce (see _run), so the window is an
        upper bound, not a tax."""
        if queued >= self.max_batch:
            return self.max_batch, 0.0
        # busyness is judged over a FIXED horizon, not max_wait: with a
        # small window the gate `rate * max_wait >= 2` could never open
        # (closed-loop submitters post ~1 tx per round trip, so the rate
        # only rises AFTER coalescing starts — a catch-22)
        expected = self._rate * max(self.max_wait, 0.1)
        if expected < 2.0 and self._batch_ewma < 1.5:
            return max(1, queued), 0.0
        want = min(self.max_batch,
                   max(queued, int(self._rate * self.max_wait),
                       int(self._batch_ewma * 2)))
        target = self.max_batch
        for b in _SIZE_BUCKETS:
            if want <= b:
                target = min(b, self.max_batch)
                break
        if queued >= target:
            return target, 0.0
        return target, self.max_wait

    # -- dispatcher --------------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._q and not self._stop:
                    self._cv.wait()
                if not self._q and self._stop:
                    return
                target, window = self._plan(len(self._q))
                if window > 0.0:
                    # park up to `window` for the target, but early-exit
                    # once arrivals quiesce: concurrent submitters re-post
                    # within a few ms of each other after their previous
                    # dispatch resolves, so a short silence means the
                    # in-flight cohort has fully landed. The quiesce
                    # threshold is ADAPTIVE: while the queue is still below
                    # the steady cohort size (the batch EWMA), wait the
                    # patient window/4 — trickling open-loop arrivals keep
                    # coalescing; once a full cohort is in, a ~2 ms silence
                    # suffices. Closed-loop clients' end-to-end rate is
                    # 1/admission-latency, so the old fixed window/4 idle
                    # AFTER the cohort arrived was a direct TPS ceiling.
                    deadline = time.monotonic() + window
                    cohort = max(2.0, self._batch_ewma)
                    gappy = window / 4.0
                    if self._gap_ewma > 0.0:
                        gappy = min(gappy, max(0.005, 8.0 * self._gap_ewma))
                    while (len(self._q) < target and not self._stop):
                        left = deadline - time.monotonic()
                        if left <= 0.0:
                            break
                        before = len(self._q)
                        quiet = 0.002 if before >= cohort else gappy
                        self._cv.wait(min(left, quiet))
                        if len(self._q) == before:
                            break  # quiesced: the cohort is in
                batch = [self._q.popleft()
                         for _ in range(min(len(self._q), self.max_batch))]
                depth = len(self._q)
                waited, self._lane_wait = self._lane_wait, None
                if depth:  # what stays queued has been waiting as well
                    self._queued_locked()
            self._reg.set_gauge("bcos_ingest_queue_depth", depth)
            try:
                self._dispatch(batch, waited)
            except Exception as exc:  # noqa: BLE001 — lane must survive
                LOG.exception(badge("INGEST", "dispatch-failed",
                                    n=len(batch)))
                for e in batch:
                    if e.task is not None:
                        e.task.reject(exc)

    def _dispatch(self, batch: list[_Entry],
                  waited: Optional[otrace.Stage] = None) -> None:
        # latency attribution: the batch's coalesce time ends here
        now = waited.stop() if waited is not None else time.monotonic()
        # one decode and one pool call == one device recover for the
        # drained set; a frame that does not parse, or whose block_limit
        # passed while it sat in the queue, is answered by the pool's
        # precheck before any crypto
        t0 = time.perf_counter()
        with self.stages.stage("admit"):
            cols = decode_columns([e.wire for e in batch])
            cols.traces = {i: e.ctx for i, e in enumerate(batch)
                           if e.ctx is not None}
            results = self.txpool.submit_columns(
                cols, broadcast=self.broadcast)
            for e, res in zip(batch, results):
                if e.task is not None:
                    e.task.resolve(res)
        dt = time.perf_counter() - t0
        # traced submissions additionally get their own enqueue-to-admitted
        # span (one per traced entry, linked to the shared batch by the
        # batch-size attribute)
        t_done = time.monotonic()
        for e in batch:
            if e.ctx is not None and e.ctx.sampled:
                otrace.TRACER.record(
                    "ingest.admit", e.ctx, e.t_enq, t_done,
                    attrs={"batch": len(batch),
                           "node": self.trace_label})
        # rate EWMA: arrivals per second over the inter-dispatch gap
        gap = max(1e-6, now - self._last_dispatch)
        self._last_dispatch = now
        inst = len(batch) / gap
        self._rate = inst if self._rate == 0.0 else \
            0.3 * inst + 0.7 * self._rate
        self._batch_ewma = 0.3 * len(batch) + 0.7 * self._batch_ewma
        if len(batch) > 1:
            spread = (batch[-1].t_enq - batch[0].t_enq) / (len(batch) - 1)
            self._gap_ewma = spread if self._gap_ewma == 0.0 else \
                0.3 * spread + 0.7 * self._gap_ewma
        with self._cv:
            self._txs_total += len(batch)
            self._batches_total += 1
        self._reg.inc("bcos_ingest_txs_total", len(batch))
        self._reg.inc("bcos_ingest_batches_total")
        self._reg.observe("bcos_ingest_batch_size", len(batch),
                         buckets=_SIZE_BUCKETS)
        self._reg.observe("bcos_ingest_coalesce_delay_seconds",
                         now - batch[0].t_enq)
        self._reg.observe("bcos_ingest_per_tx_seconds", dt / len(batch))
        metric("ingest.batch", n=len(batch), ms=int(dt * 1000),
               rate=int(self._rate))

    # -- introspection -----------------------------------------------------
    def queue_fraction(self) -> float:
        """Queue occupancy 0..1 — the overload controller's ingest signal
        (utils/overload.py). Lock-free read of a len()."""
        return len(self._q) / max(1, self.queue_cap)

    def stats(self) -> dict:
        with self._cv:
            txs, batches = self._txs_total, self._batches_total
            return {
                "txs_total": txs,
                "batches_total": batches,
                "mean_batch": round(txs / batches, 2) if batches else 0.0,
                "queue_depth": len(self._q),
                "rejected_total": self._rejected_total,
                "dropped_total": self._dropped_total,
                "rate_tx_per_sec": round(self._rate, 1),
            }
