#!/usr/bin/env python3
"""perf_gate — noise-aware perf-regression gate over the bench trajectory.

The problem (PERF rounds 9/10/13, verbatim complaint): A/B medians on the
CI host flip sign inside a 1.45–1.6x run-to-run swing while /proc/loadavg
reads 0.00 — so a naive "candidate < last run ⇒ regression" gate would be
red half the time and trusted never. This gate makes the comparison the
way the repo's own PERF methodology demands:

  * the REFERENCE for each metric is the median of the recorded
    trajectory (`BENCH_r*.json` `parsed` lines; with none recorded every
    metric is reported as new);
  * the TOLERANCE BAND per metric is derived from the recorded run
    SPREAD of that very metric across the trajectory — a metric that
    historically swings 1.4x gets a wide band, a stable one gets the
    floor band — capped so a true 2x regression can never hide;
  * the HOST-WEATHER stamp (analysis/hostweather.py) on the candidate
    row, and a fresh sample taken by the gate itself, WIDEN the band on
    a noisy host instead of silently failing honest runs;
  * MULTIPLE candidate files are reduced to per-metric medians
    (interleaved A/B runs), and metrics with fewer than `--min-runs`
    recorded observations are ADVISORY (reported, never fatal).

Exit 0 = no enforced regression (or --report-only). Exit 1 = at least one
enforced metric fell below its band. Exit 2 = usage/input error.

Usage:
  tools/perf_gate.py --candidate BENCH_NEW.json [--candidate ...]
  bench.py ... | tools/perf_gate.py --candidate - --report-only
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# band parameters (fractions of the reference median)
MIN_BAND = 0.12    # floor: even a historically flat metric gets this
SPREAD_K = 0.75    # band contribution per unit of relative spread
MAX_BAND = 0.45    # cap: a 2x regression (cand = 0.5*ref) must ALWAYS trip
NOISE_MARGIN = 0.10  # extra width when the host weather says "co-tenant"
# a drop past this ratio is fatal even for advisory (<min-runs) metrics:
# with MAX_BAND at 0.45, 0.52 keeps an injected 2x regression (ratio 0.5)
# caught no matter how thin the metric's recorded history is
CATASTROPHIC = 0.52

# metric-name heuristics: which numeric fields of a bench line are
# comparable performance numbers, and in which direction
_HIGHER_SUFFIXES = ("_tps", "_qps", "_per_sec", "_speedup", "_share")
_HIGHER_EXACT = {"value", "vs_baseline", "recover_vs_baseline",
                 "chain_tps_4node_host", "pipeline_tps", "rpc_ingest_tps",
                 "rpc_read_qps", "groups_scaling_2x", "groups_tps_median",
                 "recover_sigs_per_sec", "native_host_floor_sigs_per_sec",
                 "replay_blocks_per_sec", "poseidon_hashes_per_sec",
                 "rpc_read_cache_hit_rate",
                 # columnar wire ingest vs the object path (adjacent-pair
                 # ratio median) and the exec pools' busy fraction over
                 # the timed window — both shrink when the substrate or
                 # the worker seam regresses
                 "columnar_vs_object", "exec_worker_occupancy"}
_LOWER_SUFFIXES = ("_ms", "_seconds", "_mb", "_cost_pct", "_ns")
# lower-is-better fields whose names don't carry a _LOWER suffix: the
# sealer's idle threading-wait share of attributed CPU (the event-driven
# sealer's acceptance number — PR 16 measured 15.4% under the 0.02 s poll)
_LOWER_EXACT = {"seal_wait_share_pct",
                # push-plane acceptance numbers (PR 20): commit->client
                # notify tail (also caught by the _ms suffix — pinned
                # here so a rename can't silently un-gate it) and the
                # fan-out CPU burned per delivered notification (the
                # zero-extra-render contract: flat as subscribers grow)
                "sub_notify_p99_ms", "sub_cpu_us_per_notify"}
_SKIP = {"cpu_cores", "rpc_ingest_clients", "rpc_read_clients",
         "sub_subscribers",
         "poseidon_batch", "overload_rate_limited", "live_value",
         "cpu_baseline_sigs_per_sec", "spin_score", "sampled_at",
         "measured_at",
         # run-size / config-dependent absolutes: these scale with the
         # run's CLI args (-n, client counts, memtable knobs), so pooling
         # them across runs would gate the CONFIG, not the code — a
         # doubled -n must never read as a catastrophic wall_seconds
         # regression
         "wall_seconds", "submit_seconds", "episode_seconds",
         "join_seconds", "cross_shard_drain_seconds",
         "dataset_mb", "disk_dataset_mb", "memtable_mb",
         "peak_rss_mb", "peak_rss_disk_mb", "peak_rss_memory_mb",
         "storage_peak_rss_disk_mb",
         "cpu_seconds", "attributed_cpu_seconds", "profiler_cpu_seconds",
         # counts that scale with the run's -n / worker config, and the
         # fallback counter whose healthy median is exactly 0 (ratio
         # banding around zero is meaningless; workers_smoke asserts the
         # fallback/respawn contract directly)
         "exec_worker_pool_blocks", "exec_worker_fallbacks", "workers",
         "pool_blocks", "exec_fallbacks",
         # commit-seal carriage observability (--seal-bench /
         # --trace-profile summary): these pool across seal_mode and
         # roster size under one name, so a cert-mode run would gate
         # against an aggregate-mode median (239 vs 95 bytes is config,
         # not code). tests/test_qc.py pins the cert<multi<aggregate byte
         # ordering deterministically; the gated consensus numbers are
         # consensus_pre_ms / consensus_wait_ms on the summary row
         "seal_bytes_per_block", "vs_multi", "span_verify_ms",
         "sealers", "quorum"}


def direction(metric: str):
    """'higher' | 'lower' | None (not gated). Accepts both bare field
    names and metric-qualified ones (`<metric>.<field>`)."""
    base = metric.rsplit(".", 1)[-1]
    if base in _SKIP or base.startswith("host_weather"):
        return None
    if base in _HIGHER_EXACT or base.endswith(_HIGHER_SUFFIXES):
        return "higher"
    if base in _LOWER_EXACT or base.endswith(_LOWER_SUFFIXES):
        return "lower"
    return None


# fields whose MEANING depends on the row's `metric` identity (the
# headline `value` is sigs/sec at whatever batch size that run used —
# pooling value@1024 with value@65536 would make the reference median
# nonsense, which the recorded trajectory actually demonstrates:
# r02=47194 @16k, r03=50.9 @1k-CPU-fallback, r04=95022 @64k)
_METRIC_SCOPED = {"value", "vs_baseline", "recover_vs_baseline"}


def flatten(line: dict) -> dict[str, float]:
    """Bench line -> {metric: float} for every gateable numeric field.
    Generic fields are qualified by the row's `metric` name so only
    like-for-like observations ever share a reference."""
    out = {}
    ident = str(line.get("metric", ""))
    for k, v in line.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        if direction(k) is None:
            continue
        out[f"{ident}.{k}" if k in _METRIC_SCOPED and ident else k] = \
            float(v)
    return out


def load_history(pattern: str) -> tuple[list[dict], list[int]]:
    """-> (parsed bench lines oldest-first, best spin_scores seen)."""
    lines, spins = [], []
    for path in sorted(glob.glob(pattern)):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = rec.get("parsed") if isinstance(rec, dict) else None
        if isinstance(rec, dict) and parsed is None and "metric" in rec:
            parsed = rec  # a bare bench line is also accepted as history
        if isinstance(parsed, dict):
            lines.append(parsed)
            spin = (parsed.get("host_weather") or {}).get("spin_score")
            if isinstance(spin, (int, float)):
                spins.append(int(spin))
    return lines, spins


def load_candidates(paths: list[str]) -> list[dict]:
    cands = []
    for p in paths:
        try:
            text = sys.stdin.read() if p == "-" else open(p).read()
        except OSError as exc:
            raise SystemExit(f"perf_gate: cannot read candidate {p}: {exc}")
        # a whole-file JSON document: a bare bench line, a BENCH_rNN
        # wrapper ({.., "parsed": line}), or a list of lines
        try:
            doc = json.loads(text)
        except ValueError:
            doc = None
        if isinstance(doc, dict):
            parsed = doc.get("parsed", doc)
            if isinstance(parsed, dict) and "metric" in parsed:
                cands.append(parsed)
                continue
        if isinstance(doc, list):
            cands.extend(d for d in doc
                         if isinstance(d, dict) and "metric" in d)
            continue
        # else: a bench.py stdout stream — one JSON line per row
        for ln in text.splitlines():
            ln = ln.strip()
            if not ln.startswith("{"):
                continue
            try:
                row = json.loads(ln)
            except ValueError:
                continue
            if isinstance(row, dict) and "metric" in row:
                cands.append(row)
    if not cands:
        raise SystemExit("perf_gate: no parseable bench line in candidates")
    return cands


def gate(candidates: list[dict], history: list[dict],
         min_runs: int = 3, weather_now: dict | None = None,
         best_spin: int | None = None) -> dict:
    """Pure comparison (importable for tests): -> report dict with
    per-metric verdicts and an overall `ok`."""
    from fisco_bcos_tpu.analysis import hostweather

    # candidate medians across (interleaved) runs
    cand_vals: dict[str, list[float]] = {}
    for line in candidates:
        for m, v in flatten(line).items():
            cand_vals.setdefault(m, []).append(v)
    cand = {m: statistics.median(vs) for m, vs in cand_vals.items()}

    hist_vals: dict[str, list[float]] = {}
    for line in history:
        for m, v in flatten(line).items():
            hist_vals.setdefault(m, []).append(v)

    # host weather: candidate stamps + the gate's own fresh sample
    noisy_reasons = []
    for line in candidates:
        is_noisy, why = hostweather.noisy(line.get("host_weather"),
                                          best_spin)
        if is_noisy:
            noisy_reasons.append(f"candidate: {why}")
            break
    if weather_now is not None:
        is_noisy, why = hostweather.noisy(weather_now, best_spin)
        if is_noisy:
            noisy_reasons.append(f"gate-time: {why}")
    margin = NOISE_MARGIN if noisy_reasons else 0.0

    rows = []
    failed = []
    for m, cv in sorted(cand.items()):
        hv = hist_vals.get(m, [])
        if not hv:
            rows.append({"metric": m, "candidate": cv, "verdict": "new",
                         "note": "no recorded reference"})
            continue
        ref = statistics.median(hv)
        if ref == 0:
            continue
        spread = (max(hv) - min(hv)) / abs(ref) if len(hv) >= 2 else 0.0
        band = min(MAX_BAND, max(MIN_BAND, SPREAD_K * spread) + margin)
        d = direction(m)
        ratio = cv / ref
        if d == "higher":
            bad = ratio < (1.0 - band)
            good = ratio > (1.0 + band)
        else:
            bad = ratio > (1.0 + band)
            good = ratio < (1.0 - band)
        advisory = len(hv) < min_runs
        # catastrophic drops are fatal regardless of history depth: noise
        # tolerance exists for marginal calls, not for a halved metric
        catastrophic = (ratio <= CATASTROPHIC if d == "higher"
                        else ratio >= 1.0 / CATASTROPHIC)
        verdict = ("regression" if bad else
                   "improved" if good else "ok")
        if bad and (not advisory or catastrophic):
            failed.append(m)
            advisory = advisory and not catastrophic
        rows.append({
            "metric": m, "direction": d,
            "candidate": cv, "reference": round(ref, 3),
            "ratio": round(ratio, 3), "band": round(band, 3),
            "runs_recorded": len(hv), "advisory": advisory,
            "verdict": verdict + ("(advisory)" if advisory and bad else ""),
        })
    return {
        "ok": not failed,
        "failed": failed,
        "noisy": noisy_reasons,
        "band_margin": margin,
        "candidate_runs": len(candidates),
        "rows": rows,
    }


def print_report(rep: dict, out=sys.stdout) -> None:
    w = max([len(r["metric"]) for r in rep["rows"]] + [8])
    print(f"perf_gate: {rep['candidate_runs']} candidate run(s), "
          f"band margin +{rep['band_margin']:.0%} "
          f"({'; '.join(rep['noisy']) or 'host quiet'})", file=out)
    for r in rep["rows"]:
        if r["verdict"] == "new":
            print(f"  {r['metric']:<{w}}  {r['candidate']:>12}  NEW "
                  f"(no reference)", file=out)
            continue
        mark = {"ok": " ", "improved": "+",
                "regression": "!"}.get(r["verdict"].split("(")[0], "?")
        print(f"{mark} {r['metric']:<{w}}  {r['candidate']:>12} vs "
              f"{r['reference']:>12}  x{r['ratio']:<6} "
              f"band ±{r['band']:.0%} ({r['runs_recorded']} runs"
              f"{', advisory' if r['advisory'] else ''})  {r['verdict']}",
              file=out)
    print(f"perf_gate: {'PASS' if rep['ok'] else 'FAIL'}"
          + (f" — regressions: {', '.join(rep['failed'])}"
             if rep["failed"] else ""), file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--candidate", action="append", required=True,
                    metavar="FILE", help="bench line JSON (repeatable; "
                    "'-' reads stdin; files may hold several lines — "
                    "medians are taken per metric)")
    ap.add_argument("--history", default=os.path.join(_REPO,
                                                      "BENCH_r*.json"),
                    help="trajectory glob (default: repo BENCH_r*.json)")
    ap.add_argument("--min-runs", type=int, default=3,
                    help="recorded observations below this make a metric "
                         "advisory (reported, never fatal)")
    ap.add_argument("--report-only", action="store_true",
                    help="always exit 0 (the trajectory-watch mode)")
    ap.add_argument("--no-weather", action="store_true",
                    help="skip the gate-time host-weather sample")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON document")
    args = ap.parse_args(argv)

    from fisco_bcos_tpu.analysis import hostweather

    candidates = load_candidates(args.candidate)
    history, spins = load_history(args.history)
    weather_now = None if args.no_weather else hostweather.sample()
    rep = gate(candidates, history, min_runs=args.min_runs,
               weather_now=weather_now, best_spin=max(spins, default=None))
    if args.json:
        print(json.dumps(rep, indent=1))
    else:
        print_report(rep)
    if args.report_only:
        return 0
    return 0 if rep["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
