"""The one general load generator. A traffic mix is a data file under
`traffic/`: its `kind` picks the loop (`closed-batch` or `open-singles`)
and the rest are that loop's parameters. One process, few threads; the
work of a window is fixed by the file and the seed orders it.
"""

from __future__ import annotations

import math
import queue
import random
import threading
import time

from rpc import Rpc

# a signed transaction must reach a block below its block_limit, and the
# pool takes none whose limit lies more than [txpool] block_limit_range
# (600) above the current height
BLOCK_LIMIT_AHEAD = 500
DRAIN_SECONDS = 60.0


class Req:
    """One sendTransaction of the run. Times are time.monotonic()."""
    __slots__ = ("i", "hash", "move", "wire", "due", "sent", "admitted",
                 "done", "receipt", "error", "measured")

    def __init__(self, i: int, wire: str, tx_hash: str, move, measured: bool):
        self.i, self.wire, self.hash, self.move = i, wire, tx_hash, move
        self.measured = measured
        self.due = self.sent = self.admitted = self.done = None
        self.receipt = self.error = None


class Load:
    """Base of the two loops. `requests` holds every transaction this run
    sent, warm-up included: the replay needs them all."""

    def __init__(self, traffic: dict, cluster, maker, seed: int,
                 seconds: float):
        self.p, self.maker = traffic, maker
        self.seed, self.seconds = seed, float(seconds)
        self.group = cluster.group
        self.port = cluster.port(0)
        self.requests: list[Req] = []
        self.exhausted = False   # ran out of signed transactions
        # batches signed ahead for the window and those it took; a loop
        # that signs as it goes has neither
        self.window_signed = self.window_taken = None
        self.height = 0          # highest block number seen in a receipt
        self.t0 = self.t1 = 0.0  # the measured window
        self._lock = threading.Lock()

    def _sign(self, measured: bool) -> Req:
        """The next transaction of the seed; one thread signs."""
        i = len(self.requests)
        wire, h, mv = self.maker.make(i, self.height + BLOCK_LIMIT_AHEAD)
        r = Req(i, wire, h, mv, measured)
        self.requests.append(r)
        return r

    def _got(self, r: Req, receipt: dict, now: float) -> None:
        r.receipt, r.done = receipt, now
        n = receipt.get("blockNumber") or 0
        if n > self.height:
            self.height = n

    def presign(self) -> None:
        """Runs while node0 warms up."""

    def warm_up(self) -> None:
        """After node0 is ready; still set-up."""

    def run(self, t0: float) -> None:
        """The measured window: from t0 (a time.monotonic() a moment
        ahead) for `seconds`."""
        raise NotImplementedError

    def send_params(self, r: Req, wait: bool) -> list:
        return [self.group, "", r.wire, False, wait]


class ClosedBatch(Load):
    """`senders` threads, each: one JSON-RPC batch of `batch`
    sendTransaction with wait=true (the answer carries the receipts), the
    next batch when the last is answered."""

    def presign(self) -> None:
        p = self.p
        window = -(-int(p["presign_tx_per_s"] * self.seconds) // p["batch"])
        warm = p["senders"] * p["warmup_batches_per_sender"]
        for _ in range((window + warm) * p["batch"]):
            self._sign(False)
        self._cursor = 0
        self.window_signed, self.window_taken = window, 0

    def _take(self, measured: bool) -> list[Req] | None:
        with self._lock:
            o = self._cursor
            if o + self.p["batch"] > len(self.requests):
                return None
            self._cursor = o + self.p["batch"]
            self.window_taken += measured
        reqs = self.requests[o:o + self.p["batch"]]
        for r in reqs:
            r.measured = measured
        return reqs

    def _send(self, cli: Rpc, reqs: list[Req]) -> None:
        t = time.monotonic()
        for r in reqs:
            r.due = r.sent = t
        try:
            out = cli.batch([("sendTransaction", self.send_params(r, True))
                             for r in reqs])
        except Exception as exc:  # noqa: BLE001 — counted as failed requests
            for r in reqs:
                r.error = f"{type(exc).__name__}: {exc}"
            return
        now = time.monotonic()
        for r, ans in zip(reqs, out):
            if "error" in ans or not ans.get("result"):
                r.error = str(ans.get("error"))
            else:
                self._got(r, ans["result"], now)

    def _loop(self, measured: bool, until, rounds: int | None) -> None:
        cli = Rpc(self.port, self.p["request_timeout_s"])
        try:
            n = 0
            while (rounds is None or n < rounds) and not until():
                reqs = self._take(measured)
                if reqs is None:
                    self.exhausted = True
                    return
                self._send(cli, reqs)
                n += 1
        finally:
            cli.close()

    def _threads(self, measured: bool, until, rounds: int | None) -> None:
        ts = [threading.Thread(target=self._loop,
                               args=(measured, until, rounds))
              for _ in range(self.p["senders"])]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    def warm_up(self) -> None:
        self._threads(False, lambda: False,
                      self.p["warmup_batches_per_sender"])

    def run(self, t0: float) -> None:
        time.sleep(max(0.0, t0 - time.monotonic()))
        self.t0, self.t1 = t0, t0 + self.seconds
        # a batch in flight at the close is waited for: its requests were
        # due in the window and their latency counts the wait
        self._threads(True, lambda: time.monotonic() >= self.t1, None)


def poisson_schedule(rate: float, seconds: float, seed: int) -> list[float]:
    """Due times in [0, seconds) of round(rate * seconds) arrivals whose
    gaps are the stratified quantiles of the exponential distribution,
    shuffled by the seed: every seed has the same set of gaps, so the same
    count and the same burstiness, in another order."""
    n = max(1, round(rate * seconds))
    gaps = [-math.log(1.0 - (k + 0.5) / n) for k in range(n)]
    random.Random(seed).shuffle(gaps)
    scale = seconds / sum(gaps)
    out, t = [], 0.0
    for g in gaps:
        out.append(t)
        t += g * scale
    return out


class OpenSingles(Load):
    """Open loop: one sendTransaction per HTTP request at the due times of
    `poisson_schedule`, over `connections` keep-alive connections. Door
    `wait`: the answer is the receipt. Door `nowait-poll`: the answer is
    the hash, and one poller asks for all outstanding receipts in one
    batch every `poll_ms`."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._q: queue.Queue = queue.Queue()
        self._out: dict[str, Req] = {}
        self._stop = threading.Event()
        self._workers: list[threading.Thread] = []
        self._pollers: list[threading.Thread] = []

    def _worker(self, wait: bool) -> None:
        cli = Rpc(self.port, self.p["request_timeout_s"])
        try:
            while True:
                r = self._q.get()
                if r is None:
                    return
                r.sent = time.monotonic()
                try:
                    res = cli.call("sendTransaction",
                                   self.send_params(r, wait))
                except Exception as exc:  # noqa: BLE001 — a failed request
                    r.error = f"{type(exc).__name__}: {exc}"
                    continue
                r.admitted = time.monotonic()
                if wait:
                    self._got(r, res, r.admitted)
                else:
                    with self._lock:
                        self._out[r.hash] = r
        finally:
            cli.close()

    def _poller(self) -> None:
        cli = Rpc(self.port, self.p["request_timeout_s"])
        period = self.p["poll_ms"] / 1000.0
        try:
            while True:
                t = time.monotonic()
                with self._lock:
                    todo = list(self._out.values())
                if todo:
                    got = cli.results([("getTransactionReceipt",
                                        [self.group, "", r.hash])
                                       for r in todo])
                    now = time.monotonic()
                    for r, rc in zip(todo, got):
                        if rc:
                            self._got(r, rc, now)
                            with self._lock:
                                del self._out[r.hash]
                if self._stop.is_set() and (
                        not self._out
                        or time.monotonic() > self._drain_until):
                    return
                time.sleep(max(0.0, period - (time.monotonic() - t)))
        finally:
            cli.close()

    def start(self, door: str | None = None) -> None:
        door = door or self.p["door"]
        if door not in ("wait", "nowait-poll"):
            raise ValueError(f"traffic door {door!r}")
        wait = door == "wait"
        self._stop.clear()
        self._drain_until = float("inf")
        self._workers = [threading.Thread(target=self._worker, args=(wait,))
                         for _ in range(self.p["connections"])]
        self._pollers = [] if wait else [
            threading.Thread(target=self._poller)]
        for t in self._workers + self._pollers:
            t.start()

    def finish(self) -> None:
        """Wait for what is outstanding, DRAIN_SECONDS at the most, and
        stop the threads."""
        for _ in self._workers:
            self._q.put(None)
        for t in self._workers:
            t.join()
        self._drain_until = time.monotonic() + DRAIN_SECONDS
        self._stop.set()
        for t in self._pollers:
            t.join()
        self._workers = self._pollers = []

    def offer(self, rate: float, seconds: float, measured: bool,
              tag: int = 0, t0: float | None = None) -> tuple[float, float]:
        """Offer `rate` for `seconds` from now -> (start, end) of the
        phase. Each transaction is signed just before it is due, under a
        block limit read from the newest receipt: at a dozen small blocks
        a second a window outlives any one limit."""
        sched = poisson_schedule(rate, seconds, self.seed * 1009 + tag)
        if t0 is None:
            t0 = time.monotonic() + 0.05
        for due in sched:
            r = self._sign(measured)
            r.due = t0 + due
            delay = r.due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            self._q.put(r)
        return t0, t0 + seconds

    def outstanding(self) -> int:
        return sum(1 for r in self.requests
                   if r.sent is not None and r.done is None
                   and r.error is None) + self._q.qsize()

    def settle(self, timeout: float) -> None:
        """Until nothing is outstanding (between phases)."""
        deadline = time.monotonic() + timeout
        while self.outstanding() and time.monotonic() < deadline:
            time.sleep(0.05)

    def warm_up(self) -> None:
        self.start()
        self.offer(self.p["rate_tx_per_s"], self.p["warmup_s"], False, 1)
        self.settle(30.0)

    def run(self, t0: float) -> None:
        self.t0, self.t1 = self.offer(self.p["rate_tx_per_s"],
                                      self.seconds, True, t0=t0)
        self.finish()


KINDS = {"closed-batch": ClosedBatch, "open-singles": OpenSingles}


def make_load(traffic: dict, cluster, maker, seed: int,
              seconds: float) -> Load:
    if traffic.get("kind") not in KINDS:
        raise ValueError(f"traffic {traffic.get('name')!r}: kind "
                         f"{traffic.get('kind')!r} is not one of "
                         f"{sorted(KINDS)}")
    return KINDS[traffic["kind"]](traffic, cluster, maker, seed, seconds)
