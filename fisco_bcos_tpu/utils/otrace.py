"""otrace — zero-dependency, OpenTelemetry-shaped tracing plane.

Reference counterpart: the reference answers "where does a transaction's
wall-clock go" with stage-stamped METRIC lines (BlockTrace /
DmcStepRecorder, bcos-scheduler/src/BlockExecutive.cpp:761-801) scraped
into a Prometheus/Grafana bundle. That attributes latency per *stage* but
cannot follow ONE transaction across threads and nodes. This module adds
the missing cross-cutting view with OpenTelemetry's data model — sampled
spans with a trace_id/span_id/parent chain, W3C `traceparent` context
propagation — while staying stdlib-only:

  * `SpanContext` — (trace_id, span_id, sampled); parses/renders the W3C
    `traceparent` header and packs to 25 bytes for the p2p frame envelope
    (net/front.py appends it to every outbound frame, so a block's
    consensus spans stitch across all nodes of a real chain).
  * `Tracer` — process-wide (`TRACER`, like metrics.REGISTRY): bounded
    in-process ring buffer of finished spans, queryable via the
    `getTrace`/`listTraces` RPC methods and the `/trace` ops endpoint.
  * sampling: new roots are sampled at `sample_rate`; an INCOMING context
    (client traceparent, p2p envelope) carries its own sampled flag and is
    honored — a client that asks for its trace gets it regardless of the
    node's local rate. Spans that exceed `slow_ms` are ALWAYS retained in
    a separate slow ring (never sampled out) and logged, so tail latency
    stays observable at sample_rate=0.
  * propagation inside a process is a per-thread context stack
    (`ctx_scope`/`current`): the serving edge, the p2p delivery thread and
    the consensus worker each scope the context they carry, and
    cross-thread handoffs (ingest lane entries, sealed blocks, PBFT
    messages) pin the context onto the carried object.

  * stages (`stages(owner).stage(name)` / `.block(number)`): the ONE
    stamp of the latency attribution plane. A cohort's or a block's stage
    is stamped once and feeds three sinks — the always-on per-node
    aggregate behind `getSystemStatus()["trace"]["stages"]`, the sampled
    span + the dashboard's per-stage histogram (`STAGE_HISTOGRAM`), and a
    `jax.profiler.TraceAnnotation` of the same name, so that a profiler
    session sees the stage on the device's clock. The per-block holder
    (`BlockStages`) also carries the block's bound span context, so
    sealer/consensus/scheduler stamp one block without threading it
    through every signature. A stage that starts and stops on one thread
    also reads that thread's CPU clock at both ends (work against wait:
    the aggregate's `cpu_seconds` over `cpu_wall_seconds`) and, while it
    is open, labels the thread for the sampling profiler
    (`THREAD_STAGES`), so the flamegraph speaks the stage table's names.

Cost contract: with no context attached and sampling off, the
instrumented hot paths pay one branch (plus, where slow-capture applies,
one monotonic clock read); span dicts are only materialised for sampled
or slow spans. Stages are stamped per cohort and per block, never per
transaction: two clock reads, one locked add, one histogram observation
and an inert TraceMe each, and two thread CPU clock reads where the
stage stays on its thread.
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from typing import Optional

from .log import LOG, badge
from .metrics import REGISTRY


def _rand_id(nbytes: int) -> bytes:
    """Trace/span ids need uniqueness, not cryptographic strength:
    random.getrandbits stays in-process (~10x cheaper than an os.urandom
    syscall), which matters because an id pair is minted per RPC request
    even when the span ends up unsampled. All-zero ids are invalid per
    the W3C spec, hence the `max(..., 1)`."""
    return max(random.getrandbits(nbytes * 8), 1).to_bytes(nbytes, "big")


_WIRE_LEN = 16 + 8 + 1  # trace_id + span_id + flags


class SpanContext:
    """Immutable (trace_id, span_id, sampled) triple — the propagated part
    of a span, W3C Trace Context shaped."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: bytes, span_id: bytes, sampled: bool):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = bool(sampled)

    def traceparent(self) -> str:
        return (f"00-{self.trace_id.hex()}-{self.span_id.hex()}-"
                f"{'01' if self.sampled else '00'}")

    def pack(self) -> bytes:
        """25-byte wire form for the p2p frame envelope."""
        return self.trace_id + self.span_id + (b"\x01" if self.sampled
                                               else b"\x00")

    def __repr__(self) -> str:  # debugging only
        return f"SpanContext({self.traceparent()})"


def parse_traceparent(value) -> Optional[SpanContext]:
    """W3C traceparent header -> SpanContext, or None if malformed.
    Accepts any version (only version 00's field layout is read, per
    spec's forward-compatibility rule)."""
    if not isinstance(value, str):
        return None
    parts = value.strip().split("-")
    if len(parts) < 4:
        return None
    ver, tid, sid, flags = parts[0], parts[1], parts[2], parts[3]
    if len(ver) != 2 or len(tid) != 32 or len(sid) != 16 or len(flags) < 2:
        return None
    try:
        trace_id = bytes.fromhex(tid)
        span_id = bytes.fromhex(sid)
        sampled = bool(int(flags[:2], 16) & 0x01)
    except ValueError:
        return None
    if trace_id == bytes(16) or span_id == bytes(8):
        return None  # all-zero ids are invalid per spec
    return SpanContext(trace_id, span_id, sampled)


def unpack_ctx(data: bytes) -> Optional[SpanContext]:
    """Inverse of SpanContext.pack (p2p envelope)."""
    if len(data) != _WIRE_LEN:
        return None
    trace_id, span_id = data[:16], data[16:24]
    if trace_id == bytes(16) or span_id == bytes(8):
        return None
    return SpanContext(trace_id, span_id, data[24] & 0x01 != 0)


# -- per-thread context stack ---------------------------------------------
_tls = threading.local()


def current() -> Optional[SpanContext]:
    """The thread's active span context (innermost ctx_scope), or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


class ctx_scope:
    """`with ctx_scope(ctx): ...` — pushes `ctx` as the thread's current
    context. A None ctx is a no-op scope, so callers never branch."""

    __slots__ = ("ctx",)

    def __init__(self, ctx: Optional[SpanContext]):
        self.ctx = ctx

    def __enter__(self):
        if self.ctx is not None:
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            stack.append(self.ctx)
        return self.ctx

    def __exit__(self, *exc):
        if self.ctx is not None:
            _tls.stack.pop()
        return False


def wire_bytes() -> bytes:
    """Current context packed for the p2p frame envelope — b"" when there
    is nothing worth propagating (no context, or unsampled)."""
    ctx = current()
    if ctx is None or not ctx.sampled:
        return b""
    return ctx.pack()


# -- spans ----------------------------------------------------------------
class _Span:
    """A live span. `end()` (or context-manager exit) records it into the
    tracer's ring when sampled, and into the slow ring when it exceeded
    the slow threshold (regardless of sampling)."""

    __slots__ = ("tracer", "name", "ctx", "parent_id", "attrs", "_t0",
                 "_scope", "_ended")

    def __init__(self, tracer: "Tracer", name: str,
                 ctx: SpanContext, parent_id: bytes,
                 attrs: Optional[dict]):
        self.tracer = tracer
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.attrs = dict(attrs) if attrs else {}
        self._t0 = time.monotonic()
        self._scope = None
        self._ended = False

    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def end(self) -> None:
        if self._ended:
            return
        self._ended = True
        self.tracer._finish(self.name, self.ctx, self.parent_id,
                            self._t0, time.monotonic(), self.attrs)

    def __enter__(self):
        self._scope = ctx_scope(self.ctx)
        self._scope.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        if self._scope is not None:
            self._scope.__exit__(exc_type, exc, tb)
        self.end()
        return False


class _NullSpan:
    """No-op span returned when the tracer has nothing to do — one object,
    zero per-call allocation."""

    __slots__ = ()

    def set_attr(self, key: str, value) -> None:
        pass

    def end(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Process-wide span sink + sampler (`TRACER` is the default, like
    metrics.REGISTRY — one node per process in deployments; in-process
    test clusters share it and tell nodes apart by span attributes)."""

    def __init__(self, sample_rate: float = 0.0, ring_size: int = 4096,
                 slow_ms: float = 0.0, slow_ring: int = 512):
        self._lock = threading.Lock()
        # slow-span observers: callback(span_dict), fired OUTSIDE the ring
        # lock on the already-slow path only (the profiler's burst-capture
        # trigger, analysis/profiler.py). Observers must never raise.
        self.on_slow: list = []
        self.configure(sample_rate=sample_rate, ring_size=ring_size,
                       slow_ms=slow_ms, slow_ring=slow_ring)

    def configure(self, sample_rate: Optional[float] = None,
                  ring_size: Optional[int] = None,
                  slow_ms: Optional[float] = None,
                  slow_ring: Optional[int] = None) -> None:
        """Apply [trace] knobs. Ring resizes clear the affected ring (a
        deque's maxlen is immutable); same-size reconfiguration keeps
        recorded spans."""
        with self._lock:
            if sample_rate is not None:
                self.sample_rate = max(0.0, min(1.0, float(sample_rate)))
            if slow_ms is not None:
                self.slow_s = max(0.0, float(slow_ms)) / 1000.0
            if ring_size is not None:
                ring_size = max(16, int(ring_size))
                if getattr(self, "_ring", None) is None or \
                        self._ring.maxlen != ring_size:
                    self._ring: deque = deque(maxlen=ring_size)
            if slow_ring is not None:
                slow_ring = max(16, int(slow_ring))
                if getattr(self, "_slow", None) is None or \
                        self._slow.maxlen != slow_ring:
                    self._slow: deque = deque(maxlen=slow_ring)
            if not hasattr(self, "_dropped"):
                self._dropped = 0
                self._recorded = 0

    def reset(self) -> None:
        """Drop every recorded span (tests, bench warm-up)."""
        with self._lock:
            self._ring.clear()
            self._slow.clear()
            self._dropped = 0
            self._recorded = 0

    # -- context construction ----------------------------------------------
    def idle(self) -> bool:
        """True when span bookkeeping can be skipped entirely — the ONE
        branch the instrumented-but-unsampled hot path pays."""
        return self.sample_rate <= 0.0 and self.slow_s <= 0.0

    def new_root(self) -> SpanContext:
        """Fresh trace; sampled per sample_rate."""
        sampled = self.sample_rate > 0.0 and (
            self.sample_rate >= 1.0 or random.random() < self.sample_rate)
        return SpanContext(_rand_id(16), _rand_id(8), sampled)

    @staticmethod
    def child_of(parent: SpanContext) -> SpanContext:
        return SpanContext(parent.trace_id, _rand_id(8), parent.sampled)

    # -- span API ----------------------------------------------------------
    def span(self, name: str, parent: Optional[SpanContext] = None,
             attrs: Optional[dict] = None):
        """Start a span. `parent=None` consults the thread's current
        context, then starts a new (maybe-sampled) root. Returns a live
        span usable as a context manager (which also scopes the span's
        context for children), or a no-op span when there is provably
        nothing to record."""
        if parent is None:
            parent = current()
        if parent is None:
            if self.idle():
                return _NULL_SPAN
            parent = self.new_root()
            # a root HAS no parent span: record with an empty parent id
            ctx = parent
            return _Span(self, name, ctx, b"", attrs)
        if not parent.sampled and self.slow_s <= 0.0:
            return _NULL_SPAN
        return _Span(self, name, self.child_of(parent), parent.span_id,
                     attrs)

    def record(self, name: str, parent: Optional[SpanContext],
               t0: float, t1: Optional[float] = None,
               attrs: Optional[dict] = None) -> None:
        """Record an already-timed span (monotonic t0/t1) under `parent`.
        The workhorse for cross-thread stages that kept their own stamps
        (scheduler/PBFT/ingest). No-op when parent is None/unsampled and
        the duration is under the slow threshold."""
        if parent is None:
            return
        self._finish(name, self.child_of(parent), parent.span_id, t0,
                     t1 if t1 is not None else time.monotonic(), attrs)

    def observe_slow(self, name: str, duration_s: float,
                     attrs: Optional[dict] = None) -> None:
        """Slow-capture seam for paths with no context bound: retains a
        synthetic span iff it exceeds slow_ms (never enters the main
        ring — sample_rate=0 keeps it empty)."""
        if self.slow_s <= 0.0 or duration_s < self.slow_s:
            return
        now_m = time.monotonic()
        ctx = SpanContext(_rand_id(16), _rand_id(8), False)
        self._finish(name, ctx, b"", now_m - duration_s, now_m, attrs)

    # -- recording ---------------------------------------------------------
    def _finish(self, name: str, ctx: SpanContext, parent_id: bytes,
                t0: float, t1: float, attrs: Optional[dict]) -> None:
        dur = max(0.0, t1 - t0)
        slow = self.slow_s > 0.0 and dur >= self.slow_s
        if not ctx.sampled and not slow:
            return
        # wall-clock anchor derived once at record time (spans carry
        # monotonic stamps until here so cross-stage math never sees a
        # clock step)
        start_wall = time.time() - (time.monotonic() - t0)
        span = {
            "traceId": ctx.trace_id.hex(),
            "spanId": ctx.span_id.hex(),
            "parentSpanId": parent_id.hex() if parent_id else "",
            "name": name,
            "start_ms": round(start_wall * 1000.0, 3),
            "duration_ms": round(dur * 1000.0, 3),
            "attrs": dict(attrs) if attrs else {},
        }
        if slow:
            span["slow"] = True
        with self._lock:
            if ctx.sampled:
                if len(self._ring) == self._ring.maxlen:
                    self._dropped += 1
                self._ring.append(span)
            if slow:
                self._slow.append(span)
            self._recorded += 1
        if slow:
            REGISTRY.inc("bcos_trace_slow_spans_total")
            LOG.warning(badge("TRACE", "slow-span", name=name,
                              ms=span["duration_ms"],
                              trace=span["traceId"][:16]))
            for cb in list(self.on_slow):
                try:
                    cb(span)
                except Exception:  # noqa: BLE001 — observers must not
                    pass           # break span recording

    # -- queries (getTrace / listTraces / /trace) --------------------------
    def get_trace(self, trace_id: str) -> list[dict]:
        """Every retained span of `trace_id` (hex), start-ordered. Scans
        both rings (a slow span of an unsampled trace is findable by the
        id logged with it)."""
        tid = trace_id.lower().removeprefix("0x")
        with self._lock:
            spans = [s for s in self._ring if s["traceId"] == tid]
            seen = {s["spanId"] for s in spans}
            spans += [s for s in self._slow
                      if s["traceId"] == tid and s["spanId"] not in seen]
        return sorted(spans, key=lambda s: s["start_ms"])

    def list_traces(self, limit: int = 50, slow_only: bool = False) -> list:
        """Newest-first trace summaries: id, span count, names, wall
        bounds."""
        with self._lock:
            if slow_only:
                spans = list(self._slow)
            else:
                spans = list(self._ring)
                seen = {s["spanId"] for s in spans}
                spans += [s for s in self._slow
                          if s["spanId"] not in seen]
        by_trace: dict[str, list[dict]] = {}
        for s in spans:
            by_trace.setdefault(s["traceId"], []).append(s)
        out = []
        for tid, ss in by_trace.items():
            t0 = min(s["start_ms"] for s in ss)
            t1 = max(s["start_ms"] + s["duration_ms"] for s in ss)
            out.append({"traceId": tid, "spans": len(ss),
                        "names": sorted({s["name"] for s in ss}),
                        "start_ms": t0,
                        "duration_ms": round(t1 - t0, 3)})
        out.sort(key=lambda t: t["start_ms"], reverse=True)
        return out[:max(1, int(limit))]

    def stats(self) -> dict:
        with self._lock:
            return {
                "sample_rate": self.sample_rate,
                "slow_ms": round(self.slow_s * 1000.0, 1),
                "ring_size": self._ring.maxlen,
                "ring_spans": len(self._ring),
                "slow_spans": len(self._slow),
                "recorded_total": self._recorded,
                "dropped_total": self._dropped,
            }


# process-wide default tracer: OFF until a node's [trace] config (or a
# bench/test) turns sampling on — the hot path then costs one branch
TRACER = Tracer(sample_rate=0.0, ring_size=4096, slow_ms=0.0)


def configure(sample_rate: Optional[float] = None,
              ring_size: Optional[int] = None,
              slow_ms: Optional[float] = None) -> Tracer:
    """Apply [trace] config to the process tracer (init/node.py)."""
    TRACER.configure(sample_rate=sample_rate, ring_size=ring_size,
                     slow_ms=slow_ms)
    return TRACER


# -- stages ---------------------------------------------------------------
# one cohort's round trip through a node, from the request body in hand to
# the response handed to the socket; a name is the aggregate's key, the
# histogram's label and the profiler annotation at once, so `[a-z_]+`.
# `prime` is beside that chain, not in it: the commit observer's render of
# the block's fragments runs on the notifier thread while `rpc_respond`
# answers from them
STAGES = ("rpc_no_request", "rpc_decode", "lane_wait", "admit", "gossip",
          "crypto", "round_wait", "seal_wait", "consensus_pre", "fill",
          "execute", "roots", "consensus_wait", "commit", "notify",
          "rpc_respond", "prime",
          # inside `commit`: the changeset staged in the storage (a page
          # layer translates it first), then made durable and applied
          "storage_prepare", "storage_commit",
          # inside `execute`: the DAG planner, conflict keys included
          "dag_plan",
          # inside `roots`: the transactions' root, the receipts' encoding
          # and root, the ledger's rows of the block, the state root and
          # its index rows; what is left of `roots` is the header hash
          "txs_root", "receipts_root", "prewrite", "state_root")
# the stages that start on one thread and stop on another, or may: waits
# by nature (a queue's, a round's, the client's turn). They read no thread
# CPU clock and label no thread
CROSS_THREAD = frozenset(("rpc_no_request", "lane_wait", "round_wait",
                          "seal_wait", "consensus_pre", "consensus_wait"))
# what the edge counts beside its stages, per cohort and never per stamp:
# receipts a `sendTransaction` batch was answered, and those of them taken
# from the committed block's shared fragments; batches of `sendTransaction`
# received, and those the lane took as one piece (rpc/server.py). The
# executor's, once a block: blocks run through the DAG path, their waves and
# transactions (executor.py), and `dag_pooled_txs`, transactions run in a
# thread-pooled wave, which reads 0 since every wave runs serially; the
# block's SmallBank calls and those of them refused; the changeset rows the
# state root hashed (`state_root_with_leaves`); and once a frame, EVM frames
# and those of them the native interpreter ran (evm.py `_run`)
COUNTERS = ("cohort_receipts", "cohort_receipts_shared", "cohorts",
            "cohorts_whole", "dag_blocks", "dag_waves", "dag_txs",
            "dag_pooled_txs", "smallbank_calls", "smallbank_refused",
            "state_leaves", "evm_frames", "evm_native_frames")
STAGE_HISTOGRAM = "bcos_tx_stage_seconds"
# two series of the histogram are older than the stage names
_HISTOGRAM_LABEL = {"lane_wait": "ingest", "seal_wait": "queueing"}
# stage durations live between "instant" and "a slow block": the default
# time buckets bottom out too low and top out too high
_STAGE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                  0.25, 0.5, 1.0, 2.5, 5.0)

_annotation = None  # jax.profiler.TraceAnnotation; False: not importable


def _annotate(name: str):
    """A started TraceMe of `name`, or None where jax.profiler cannot be
    imported. Outside a profiler session a TraceMe is inert, so there is
    no switch; inside one it is written to the `/host:CPU` plane when it
    is stopped, on whatever thread stops it."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        except Exception:  # noqa: BLE001 — no jax, no annotation
            _annotation = False
    if not _annotation:
        return None
    ann = _annotation(name)
    ann.__enter__()
    return ann


# {thread ident: the names of the stages open on that thread, innermost
# last}: the sampling profiler's stage label (analysis/profiler.py). Only
# stages outside `CROSS_THREAD` enter it. A value is a tuple replaced
# whole, so a sampler on another thread never reads one half-written
THREAD_STAGES: dict[int, tuple] = {}


def _label(ident: int, name: str) -> None:
    THREAD_STAGES[ident] = THREAD_STAGES.get(ident, ()) + (name,)


def _unlabel(ident: int, name: str) -> None:
    names = list(THREAD_STAGES.get(ident, ()))
    if name in names:  # the innermost of that name: stages nest
        del names[len(names) - 1 - names[::-1].index(name)]
    if names:
        THREAD_STAGES[ident] = tuple(names)
    else:
        THREAD_STAGES.pop(ident, None)


class Stage:
    """One open stage. `stop()` closes it into the three sinks; as a
    context manager the scope is the stage. `t1` is the stop time, for
    the stage that starts where this one ended. A stage outside
    `CROSS_THREAD` reads its thread's CPU clock when it is made and,
    stopped on that thread, adds the CPU and the wall time between the
    two reads to its row; stopped elsewhere it adds neither."""

    __slots__ = ("_table", "_block", "name", "t0", "t1", "_ann", "_ident",
                 "_w0", "_cpu0")

    def __init__(self, table: "StageTable", name: str,
                 t0: Optional[float] = None, block=None):
        self._table = table
        self._block = block
        self.name = name
        self.t1 = None
        self._ann = _annotate(name)
        if name in CROSS_THREAD:
            self._ident = None
            self.t0 = time.monotonic() if t0 is None else t0
            return
        self._ident = threading.get_ident()
        _label(self._ident, name)
        # the wall clock first, the CPU clock inside it: the CPU read
        # never spans more than the wall read
        self._w0 = time.monotonic()
        self._cpu0 = time.thread_time()
        self.t0 = self._w0 if t0 is None else t0

    def stop(self, ctx: Optional[SpanContext] = None,
             attrs: Optional[dict] = None,
             t1: Optional[float] = None) -> float:
        """-> the stop time. `ctx`/`attrs` go to the span sink (a block's
        stage takes its block's, any other the stopping thread's current
        context); a second stop does nothing."""
        table = self._table
        if table is None:
            return self.t1
        self._table = None
        cpu = wall = None
        if self._ident == threading.get_ident():
            cpu = time.thread_time() - self._cpu0
        now = time.monotonic()
        if cpu is not None:
            wall = now - self._w0
        self.t1 = now if t1 is None else t1
        self._end_annotation()
        blk = self._block
        if blk is not None:
            ctx = ctx or blk.ctx
            attrs = {"number": blk.number, "node": table.owner,
                     **(attrs or {})}
        elif ctx is None:
            ctx = current()  # the stopping thread's, where it scopes one
        table._observe(self.name, self.t0, self.t1, ctx, attrs, cpu, wall)
        return self.t1

    def cancel(self) -> None:
        """Drop an open stage that turned out not to be one (an early
        return, a wait that ended with nothing to wait for)."""
        self._table = None
        self._end_annotation()

    def _end_annotation(self) -> None:
        """Close what an open stage holds: its TraceMe and its thread's
        label (taken off from whichever thread ends the stage)."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._ident is not None:
            _unlabel(self._ident, self.name)
            self._ident = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class BlockStages:
    """One block's stages on one node, and the span context bound to the
    block (sealer binds on the leader, the PBFT engine on replicas from
    the pre-prepare's envelope): stages stopped from here on are spans of
    that trace. `open`/`close` by name carry a stage from the handler
    that starts it to the one that ends it."""

    __slots__ = ("_table", "number", "ctx", "_open")

    def __init__(self, table: "StageTable", number: int):
        self._table = table
        self.number = number
        self.ctx: Optional[SpanContext] = None
        self._open: dict[str, Stage] = {}

    def bind(self, ctx: Optional[SpanContext]) -> None:
        if ctx is not None and ctx.sampled:
            self.ctx = ctx

    def stage(self, name: str, t0: Optional[float] = None) -> Stage:
        return Stage(self._table, name, t0, block=self)

    def open(self, name: str, t0: Optional[float] = None) -> None:
        """Start `name` and keep it (once: a re-entered handler keeps the
        first) until `close(name)`."""
        if name not in self._open:
            self._open[name] = self.stage(name, t0)

    def close(self, name: str, t1: Optional[float] = None) -> None:
        st = self._open.pop(name, None)
        if st is not None:
            st.stop(t1=t1)


class StageTable:
    """A node's always-on stage aggregate, {name: [count, seconds,
    cpu_seconds, cpu_wall_seconds]}, and its per-block holders. Keyed by
    the node's trace label (`stages`): the tracer is process-wide, and
    in-process clusters must not add their nodes together."""

    KEEP_BLOCKS = 64

    def __init__(self, owner: str = ""):
        self.owner = owner
        self._lock = threading.Lock()
        self._agg: dict[str, list] = {n: [0, 0.0, 0.0, 0.0] for n in STAGES}
        self._counts: dict[str, int] = dict.fromkeys(COUNTERS, 0)
        self._blocks: dict[int, BlockStages] = {}

    def stage(self, name: str, t0: Optional[float] = None) -> Stage:
        """A started stage: `with table.stage(name):`, or keep it and
        `.stop()` it where it ends."""
        return Stage(self, name, t0)

    def block(self, number: int) -> BlockStages:
        with self._lock:
            blk = self._blocks.get(number)
            if blk is None:
                blk = self._blocks[number] = BlockStages(self, number)
                for old in [n for n in self._blocks
                            if n < number - self.KEEP_BLOCKS]:
                    del self._blocks[old]
            return blk

    def drop_block(self, number: int) -> None:
        with self._lock:
            self._blocks.pop(number, None)

    def _observe(self, name: str, t0: float, t1: float,
                 ctx: Optional[SpanContext], attrs: Optional[dict],
                 cpu: Optional[float] = None,
                 wall: Optional[float] = None) -> None:
        dt = max(0.0, t1 - t0)
        with self._lock:
            row = self._agg.setdefault(name, [0, 0.0, 0.0, 0.0])
            row[0] += 1
            row[1] += dt
            if cpu is not None:
                row[2] += cpu
                row[3] += wall
        # the unlabeled registry on purpose: every stage lives in ONE
        # series family, or the dashboard's cross-stage shares skew
        REGISTRY.observe(STAGE_HISTOGRAM, dt,
                         {"stage": _HISTOGRAM_LABEL.get(name, name)},
                         buckets=_STAGE_BUCKETS)
        if ctx is not None and ctx.sampled:
            TRACER.record(f"stage.{name}", ctx, t0, t1, attrs=attrs)
        else:
            TRACER.observe_slow(f"stage.{name}", dt, attrs=attrs)

    def snapshot(self, names: Optional[tuple] = None) -> dict:
        """{name: {"count", "seconds", "cpu_seconds", "cpu_wall_seconds"}}:
        every stage of `STAGES` from the start, so a reader's delta never
        meets a missing key. The last two are summed over the stops on the
        stage's own thread alone: their ratio is the share of the stage's
        time its thread was on a core."""
        with self._lock:
            return {n: {"count": r[0], "seconds": r[1],
                        "cpu_seconds": r[2], "cpu_wall_seconds": r[3]}
                    for n, r in self._agg.items()
                    if names is None or n in names}

    def count(self, name: str, n: int) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def counters(self) -> dict:
        """{name: total}: every name of `COUNTERS` from the start."""
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            self._agg = {n: [0, 0.0, 0.0, 0.0] for n in STAGES}
            self._counts = dict.fromkeys(COUNTERS, 0)
            self._blocks.clear()


_tables: dict[str, StageTable] = {}
_tables_lock = threading.Lock()


def stages(owner: str = "") -> StageTable:
    """The stage table of the node labelled `owner` (one node per process
    stamps the same table everywhere; in-process clusters pass their node
    label so stamps don't collide)."""
    table = _tables.get(owner)
    if table is None:
        with _tables_lock:
            table = _tables.setdefault(owner, StageTable(owner))
    return table
