#!/usr/bin/env python3
"""Summarize or diff lightgbm_tpu telemetry runs (stdlib only).

A telemetry run directory (written by lightgbm_tpu/telemetry.py when
`telemetry_dir` / $LGBM_TPU_TELEMETRY is set) holds:

    events.jsonl   one JSON object per line; the final `session_end` record
                   carries the per-label timer totals, work counters, and
                   watcher summaries this tool reads
    trace.json     Chrome trace-event JSON (Perfetto / chrome://tracing)

Usage:

    python tools/teldiff.py summarize RUN_DIR
    python tools/teldiff.py diff BASE_DIR CAND_DIR [--threshold PCT]
    python tools/teldiff.py --self-check RUN_DIR

`diff` prints per-label time and counter deltas and exits nonzero when any
tracked figure regresses by more than --threshold percent (default 10) —
the machine check "bench before/after" needs. Gating is direction-aware
(COUNTER_DIRECTIONS): time and byte figures regress UPWARD, while counters
like committed splits or predict-cache hits regress DOWNWARD — a symmetric
threshold cannot tell an optimization from a regression. `summarize` also
prints per-label span-duration p50/p99 recovered from trace.json.
`--self-check` validates a
run's artifacts (parseable JSONL, required event types, monotonic trace
timestamps, matched B/E span pairs) and exits nonzero on any violation —
CI runs it on the smoke-train artifact. It also accepts the flight-
recorder dump format (lightgbm_tpu/tracing.py): pass a `flight-*.json`
file directly, or a run dir — any flight dumps sitting in the dir are
validated alongside the event stream.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

EVENTS_FILE = "events.jsonl"
TRACE_FILE = "trace.json"
# per-counter DIRECTION for --threshold gating: "lower" means a higher
# value is a regression (bytes moved, compiles, speculation waste);
# "higher" means a DROP is the regression (work the optimizer is supposed
# to keep, e.g. committed splits or predict-cache hits falling means the
# fast path stopped engaging). Counters not listed are reported but never
# gate the exit code.
COUNTER_DIRECTIONS: Dict[str, str] = {
    "jit_compiles": "lower",
    "kernel_compiles": "lower",
    "hbm_high_water_bytes": "lower",
    "device_hist_rows": "lower",
    "device_ici_bytes_per_wave": "lower",
    "wave_splits_speculated": "lower",
    "device_waves": "lower",
    "wave_splits_committed": "higher",
    "predict_pack_hits": "higher",
}


def _read_events(run_dir: str) -> List[Dict[str, Any]]:
    path = os.path.join(run_dir, EVENTS_FILE)
    if not os.path.isfile(path):
        sys.exit(f"teldiff: no {EVENTS_FILE} in {run_dir}")
    events = []
    with open(path, "r", encoding="utf-8") as fh:
        for ln, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError as e:
                sys.exit(f"teldiff: {path}:{ln}: invalid JSON ({e})")
    if not events:
        sys.exit(f"teldiff: {path} is empty")
    return events


def _session_end(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    for ev in reversed(events):
        if ev.get("ev") == "session_end":
            return ev
    sys.exit("teldiff: no session_end record — run did not close cleanly")


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.1f} GiB"


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile on an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(round(
        q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _span_durations(run_dir: str) -> Dict[str, List[float]]:
    """Per-label span durations (ms) from trace.json's B/E pairs. Labels
    never self-nest (one tid per label — telemetry.build_chrome_trace), so
    a simple per-track open-stack recovers every duration."""
    path = os.path.join(run_dir, TRACE_FILE)
    if not os.path.isfile(path):
        return {}
    try:
        with open(path, "r", encoding="utf-8") as fh:
            trace = json.load(fh)
    except json.JSONDecodeError:
        return {}
    open_ts: Dict[Tuple[int, int], List[int]] = {}
    durations: Dict[str, List[float]] = {}
    for ev in trace.get("traceEvents", []):
        ph = ev.get("ph")
        if ph not in ("B", "E"):
            continue
        key = (ev.get("pid", 0), ev.get("tid", 0))
        if ph == "B":
            open_ts.setdefault(key, []).append(int(ev.get("ts", 0)))
        else:
            stack = open_ts.get(key)
            if stack:
                t0 = stack.pop()
                durations.setdefault(str(ev.get("name", "?")), []).append(
                    (int(ev.get("ts", 0)) - t0) / 1000.0)
    return durations


def _print_span_percentiles(run_dir: str) -> None:
    durations = _span_durations(run_dir)
    if not durations:
        return
    print("span durations (ms):")
    print(f"  {'label':<24} {'n':>6} {'p50':>10} {'p99':>10} {'max':>10}")
    for label in sorted(durations,
                        key=lambda k: -sum(durations[k])):
        vals = sorted(durations[label])
        print(f"  {label:<24} {len(vals):>6} "
              f"{_percentile(vals, 50):>10.3f} "
              f"{_percentile(vals, 99):>10.3f} {vals[-1]:>10.3f}")


def summarize(run_dir: str) -> int:
    events = _read_events(run_dir)
    end = _session_end(events)
    iters = [e for e in events if e.get("ev") == "iteration"]
    print(f"run: {run_dir}")
    print(f"label: {end.get('label')}  duration: {end.get('duration_s')}s  "
          f"events: {sum(end.get('events', {}).values())}  "
          f"iterations: {len(iters)}")
    if end.get("compile_count"):
        print(f"jit compiles: {end['compile_count']}")
    if end.get("hbm_high_water_bytes"):
        print("hbm high water: "
              f"{_fmt_bytes(end['hbm_high_water_bytes'])}")
    totals = end.get("timer_totals", {})
    counts = end.get("timer_counts", {})
    if totals:
        print("timer totals:")
        for label in sorted(totals, key=lambda k: (-totals[k], k)):
            print(f"  {label:<24} {totals[label]:>10.3f}s "
                  f"({counts.get(label, 0)} calls)")
    counters = end.get("counters", {})
    if counters:
        print("counters:")
        for label in sorted(counters):
            print(f"  {label:<32} {counters[label]}")
    if iters:
        walls = sorted(e.get("wall_s", 0.0) for e in iters)
        mid = walls[len(walls) // 2]
        print(f"per-iteration wall: median {mid:.4f}s  "
              f"min {walls[0]:.4f}s  max {walls[-1]:.4f}s")
    _print_span_percentiles(run_dir)
    return 0


def _pct(base: float, cand: float) -> Optional[float]:
    if base == 0:
        return None if cand == 0 else float("inf")
    return (cand - base) / abs(base) * 100.0


def diff(base_dir: str, cand_dir: str, threshold: float) -> int:
    base = _session_end(_read_events(base_dir))
    cand = _session_end(_read_events(cand_dir))
    regressions: List[str] = []

    def _section(name: str, b: Dict[str, Any], c: Dict[str, Any],
                 directions: Any, unit: str) -> None:
        # directions: "lower" applied to every key, or a per-key map —
        # a +15% in committed splits must not gate like +15% in bytes
        keys = sorted(set(b) | set(c))
        if not keys:
            return
        print(f"{name}:")
        for k in keys:
            bv, cv = float(b.get(k, 0)), float(c.get(k, 0))
            p = _pct(bv, cv)
            ptxt = "   (new)" if p == float("inf") else (
                "" if p is None else f" {p:+8.1f}%")
            direction = directions if isinstance(directions, str) \
                else directions.get(k)
            dtxt = f"  [{direction}-is-better]" if direction else ""
            print(f"  {k:<32} {bv:>12g} -> {cv:>12g}{unit}{ptxt}{dtxt}")
            if direction is None or p is None:
                continue
            bad_pct = p if direction == "lower" else -p
            if bad_pct > threshold:
                regressions.append(
                    f"{k}: {bv:g} -> {cv:g} ({p:+.1f}%, "
                    f"{direction}-is-better)")

    _section("timer totals (s)", base.get("timer_totals", {}),
             cand.get("timer_totals", {}), "lower", "s")
    _section("counters", base.get("counters", {}),
             cand.get("counters", {}), COUNTER_DIRECTIONS, "")
    for scalar in ("compile_count", "hbm_high_water_bytes", "duration_s"):
        bv, cv = float(base.get(scalar, 0)), float(cand.get(scalar, 0))
        if bv or cv:
            p = _pct(bv, cv)
            ptxt = "" if p is None else (
                " (new)" if p == float("inf") else f" ({p:+.1f}%)")
            print(f"{scalar}: {bv:g} -> {cv:g}{ptxt}")
            if scalar != "duration_s" and p is not None and p > threshold:
                regressions.append(f"{scalar}: {bv:g} -> {cv:g}")
    if regressions:
        print(f"\nREGRESSIONS past {threshold:g}% threshold:",
              file=sys.stderr)
        for r in regressions:
            print(f"  {r}", file=sys.stderr)
        return 1
    print(f"\nno regressions past {threshold:g}% threshold")
    return 0


FLIGHT_FORMAT = "lgbm-flight"


def check_flight_dump(path: str) -> List[str]:
    """Validate one flight-recorder dump: format/version header, events
    as a list of seq-ordered records with numeric timestamps, counter
    map, drop accounting. Returns problems ([] = valid)."""
    problems: List[str] = []
    try:
        with open(path, "r", encoding="utf-8") as fh:
            dump = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable flight dump ({e})"]
    if not isinstance(dump, dict) or dump.get("format") != FLIGHT_FORMAT:
        return [f"{path}: not a {FLIGHT_FORMAT} dump"]
    if not isinstance(dump.get("version"), int):
        problems.append(f"{path}: missing integer version")
    if not dump.get("reason"):
        problems.append(f"{path}: missing reason")
    events = dump.get("events")
    if not isinstance(events, list):
        problems.append(f"{path}: events is not a list")
        events = []
    last_seq = -1
    for ev in events:
        if not isinstance(ev, dict) or not isinstance(ev.get("seq"), int) \
                or not isinstance(ev.get("t"), (int, float)) \
                or not ev.get("kind"):
            problems.append(f"{path}: malformed ring record: {ev}")
            break
        if ev["seq"] <= last_seq:
            problems.append(
                f"{path}: ring seq not strictly increasing at {ev['seq']}")
            break
        last_seq = ev["seq"]
    if not isinstance(dump.get("counters"), dict):
        problems.append(f"{path}: missing counters map")
    dropped = dump.get("dropped")
    total = dump.get("total_records")
    if not isinstance(dropped, int) or not isinstance(total, int) \
            or dropped < 0 or dropped > total:
        problems.append(f"{path}: inconsistent drop accounting "
                        f"(dropped={dropped} total={total})")
    return problems


def self_check(run_dir: str) -> int:
    """Artifact validity: parseable JSONL with the required event types,
    trace.json with monotonic timestamps and matched B/E span pairs.
    A `flight-*.json` path validates as a flight dump instead; a run dir
    containing flight dumps validates those too."""
    if os.path.isfile(run_dir):
        problems = check_flight_dump(run_dir)
        if problems:
            for p in problems:
                print(f"self-check FAIL: {p}", file=sys.stderr)
            return 1
        print(f"self-check OK: {run_dir} (flight dump)")
        return 0
    problems: List[str] = []
    events = _read_events(run_dir)  # exits on parse failure
    types = {e.get("ev") for e in events}
    for required in ("session_start", "session_end"):
        if required not in types:
            problems.append(f"events.jsonl: missing {required} event")
    for e in events:
        if not isinstance(e.get("t"), (int, float)):
            problems.append(f"events.jsonl: event without numeric t: {e}")
            break
    trace_path = os.path.join(run_dir, TRACE_FILE)
    if not os.path.isfile(trace_path):
        problems.append(f"missing {TRACE_FILE}")
    else:
        try:
            with open(trace_path, "r", encoding="utf-8") as fh:
                trace = json.load(fh)
        except json.JSONDecodeError as e:
            problems.append(f"{TRACE_FILE}: invalid JSON ({e})")
            trace = None
        if trace is not None:
            tev = trace.get("traceEvents")
            if not isinstance(tev, list):
                problems.append(f"{TRACE_FILE}: no traceEvents list")
                tev = []
            last_ts = -1
            depth: Dict[Tuple[int, int], int] = {}
            for ev in tev:
                ph = ev.get("ph")
                if ph == "M":
                    continue
                ts = ev.get("ts")
                if not isinstance(ts, int) or ts < 0:
                    problems.append(f"{TRACE_FILE}: bad ts in {ev}")
                    break
                if ts < last_ts:
                    problems.append(
                        f"{TRACE_FILE}: ts not monotonic at {ev}")
                    break
                last_ts = ts
                key = (ev.get("pid", 0), ev.get("tid", 0))
                if ph == "B":
                    depth[key] = depth.get(key, 0) + 1
                elif ph == "E":
                    depth[key] = depth.get(key, 0) - 1
                    if depth[key] < 0:
                        problems.append(
                            f"{TRACE_FILE}: E without B on track {key}")
                        break
            for key, d in depth.items():
                if d != 0:
                    problems.append(
                        f"{TRACE_FILE}: {d} unmatched B event(s) on "
                        f"track {key}")
    try:
        flight_dumps = sorted(
            f for f in os.listdir(run_dir)
            if f.startswith("flight-") and f.endswith(".json"))
    except OSError:
        flight_dumps = []
    for name in flight_dumps:
        problems.extend(check_flight_dump(os.path.join(run_dir, name)))
    if problems:
        for p in problems:
            print(f"self-check FAIL: {p}", file=sys.stderr)
        return 1
    print(f"self-check OK: {run_dir} ({len(events)} events)")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="teldiff", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--self-check", metavar="RUN_DIR_OR_DUMP",
                    help="validate a run's artifacts (or a flight-*.json "
                         "dump) and exit")
    sub = ap.add_subparsers(dest="cmd")
    p_sum = sub.add_parser("summarize", help="print one run's summary")
    p_sum.add_argument("run_dir")
    p_diff = sub.add_parser("diff", help="compare two runs")
    p_diff.add_argument("base_dir")
    p_diff.add_argument("cand_dir")
    p_diff.add_argument("--threshold", type=float, default=10.0,
                        help="regression threshold in percent (default 10)")
    args = ap.parse_args(argv)
    if args.self_check:
        return self_check(args.self_check)
    if args.cmd == "summarize":
        return summarize(args.run_dir)
    if args.cmd == "diff":
        return diff(args.base_dir, args.cand_dir, args.threshold)
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
