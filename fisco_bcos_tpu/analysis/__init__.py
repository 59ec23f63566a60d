"""Concurrency-correctness plane — machine-checked lock discipline.

Two layers guard the repo's 70+ lock sites (the hand-review archaeology
that found the PR-9 pin lost-update, the PR-11 `committing=True` strand
and the PR-12 admission-LRU self-eviction, made permanent and automatic):

  * **lockcheck** (this package, runtime): instrumented drop-in wrappers
    for `threading.Lock/RLock/Condition` behind a `BCOS_LOCKCHECK=1` env
    gate. Armed, they record per-thread acquisition stacks into a
    process-wide lock-order graph (cycle = potential deadlock), flag
    blocking calls (fsync / socket send / `suite.*_batch` / subprocess
    waits) executed while a registered HOT lock is held, and publish
    `bcos_lock_*` hold/wait histograms. Disarmed (production), the
    factories return plain `threading` primitives — zero steady-state
    cost beyond one module-flag branch at each blocking marker.
  * **bcoslint** (tools/bcoslint.py, static): ~10 AST passes encoding
    repo-specific invariants (canonical lock order violated lexically,
    swallowed worker-loop exceptions, wall-clock deadlines, fsync edges
    missing failpoints, raw lock construction in hot modules, metrics
    label-cardinality hazards, ...) gating CI against a committed
    baseline (`tools/bcoslint_baseline.txt`).

The canonical lock-ordering declarations both layers check against live
in `analysis/lockorder.py`.

The package also hosts the **continuous-profiling plane** (ISSUE 15):

  * **profiler** — always-on low-hz sampling profiler (folded stacks by
    thread role + otrace stage, per-thread GIL-held CPU attribution
    from /proc, the process's CPU by thread role from each thread's own
    clock, slow-span burst captures linked to trace ids, the
    zero-dependency flamegraph renderer behind `GET /profile`);
  * **hostweather** — the PSI/steal/spin-score stamp every bench row
    carries, consumed by `tools/perf_gate.py`'s noise-aware bands.

Both are imported lazily by their call sites (Node construction, the
ops routes, chain_bench) via `from ..analysis import profiler` — not
eagerly here, so `import analysis` keeps zero side effects for the
lint/lockcheck consumers; they are intentionally absent from __all__
for the same reason.
"""

from . import lockcheck, lockorder  # noqa: F401

__all__ = ["lockcheck", "lockorder"]
