"""Structured telemetry: event stream, Chrome-trace export, watchers.

The reference's observability story is a timer table printed at exit under
-DUSE_TIMETAG (include/LightGBM/utils/common.h:979-1063). A TPU-native stack
needs machine-readable, per-iteration data because XLA adds failure modes the
reference never had — shape-driven recompile churn, HBM high-water blowups,
host<->device sync stalls — and a before/after comparison needs more than
one end-of-run text dump. This module is the event bus:

  * In-process aggregator — always on while a session is active: every event
    type counted, every `global_timer.scope` span captured via `span_hook`.
  * JSONL file sink — one self-describing object per line in
    `<dir>/events.jsonl`, written with checkpoint.py's atomic
    temp+fsync+os.replace writer so a crash never leaves a torn file.
  * Chrome trace-event exporter — `<dir>/trace.json` loadable in Perfetto /
    chrome://tracing: B/E span pairs on per-phase tracks (one tid per timer
    label), "C" counter tracks for per-device HBM samples.

Two watchers with no reference counterpart:

  * Recompile watcher — a logging.Handler on jax's pxla logger (enabled via
    `jax_log_compiles`) counting jit cache misses per (function, input
    shapes); warns once per function past a churn threshold. The hook is
    logging-only: it cannot change compilation or numerics.
  * HBM gauge — samples `device.memory_stats()` per device, tracks the
    high-water mark, publishes `hbm_high_water_bytes` and per-device "C"
    trace counter events. Degrades to a no-op where the backend reports no
    memory stats (CPU).

Enable with the `telemetry_dir` param, $LGBM_TPU_TELEMETRY, or the CLI;
`start(None)` runs an aggregate-only session (no files: a caller reads
compile/HBM figures without touching disk). Emission is a single
module-global None-check when no session is active, so the disabled path
costs <1% (asserted by tests/test_telemetry.py) and changes no model output.
Hot-path call sites must guard `emit()` behind `telemetry.enabled()` —
enforced by graftlint R9 (telemetry-hygiene).

Offline analysis: tools/teldiff.py summarizes one run or diffs two.
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
import time
from collections import Counter
from typing import Any, Dict, Iterator, List, Optional, Tuple

from .utils.log import Log
from .utils.timer import global_timer

ENV_VAR = "LGBM_TPU_TELEMETRY"
EVENTS_FILE = "events.jsonl"
TRACE_FILE = "trace.json"
# rewrite the JSONL sink every this-many events (plus once at close); the
# whole-file atomic rewrite keeps the on-disk stream crash-consistent
FLUSH_EVERY = 256
# warn when one jitted function compiles this many times in a session (low
# enough to catch per-iteration churn, high enough to pass over the normal
# warm-up of generic helpers like convert_element_type)
RECOMPILE_WARN_THRESHOLD = 8
_PXLA_LOGGER = "jax._src.interpreters.pxla"

_session: Optional["TelemetrySession"] = None

# --- kernel-compile classification -----------------------------------------
# Pallas/Mosaic kernel wrappers register their jitted entry names here at
# import; the recompile watcher splits their cache misses into the separate
# `kernel_compiles` counter so a change of a kernel's signature shows its
# compile cost apart from ordinary XLA jit churn. The substring
# markers back up the registry for names we never saw registered.
_KERNEL_FN_MARKERS = ("pallas", "mosaic")
_kernel_fns: set = set()


def register_kernel_fn(name: str) -> None:
    """Mark a jitted entry point as a Pallas/Mosaic kernel wrapper (called
    at import time by ops/hist_pallas.py and friends)."""
    _kernel_fns.add(str(name))


def is_kernel_fn(fn: str) -> bool:
    if fn in _kernel_fns:
        return True
    low = fn.lower()
    return any(m in low for m in _KERNEL_FN_MARKERS)


def enabled() -> bool:
    """True while a session is recording. Hot paths MUST check this before
    building event payloads (graftlint R9)."""
    return _session is not None


def session() -> Optional["TelemetrySession"]:
    return _session


def emit(ev: str, **fields: Any) -> None:
    """Record one structured event; single None-check no-op when disabled."""
    s = _session
    if s is not None:
        s.emit(ev, **fields)


def sample_hbm() -> int:
    """Sample per-device memory stats into the active session (no-op when
    disabled or when the backend reports none). Returns the high-water."""
    s = _session
    return s.hbm.sample() if s is not None else 0


def signals() -> Dict[str, int]:
    """Cheap watcher snapshot for adaptive consumers — the serving circuit
    breaker polls this between batches to detect compile churn and HBM
    pressure without owning the watchers. Ints read from the active
    session (zeros when no session is recording): total jit cache misses
    seen by the recompile watcher, the Pallas/Mosaic-kernel subset of
    those, and the per-device HBM high-water. exposition.py renders the
    same snapshot as Prometheus text."""
    s = _session
    if s is None:
        return {"compiles": 0, "kernel_compiles": 0,
                "hbm_high_water_bytes": 0}
    return s.signal_snapshot()


def resolve_dir(params: Optional[Dict[str, Any]]) -> str:
    """Output dir from the `telemetry_dir` param, else $LGBM_TPU_TELEMETRY."""
    return str((params or {}).get("telemetry_dir") or ""
               ) or os.environ.get(ENV_VAR, "")


def start(out_dir: Optional[str], **kwargs: Any) -> "TelemetrySession":
    """Begin a session. `out_dir=None` -> aggregate-only (no files). At most
    one session is active per process; a second start() keeps the first."""
    global _session
    if _session is not None:
        Log.warning("Telemetry session already active; keeping it")
        return _session
    _session = TelemetrySession(out_dir, **kwargs)
    return _session


def stop() -> Optional[Dict[str, Any]]:
    """Close the active session (flush sinks, restore hooks); returns its
    summary dict, or None if no session was active."""
    global _session
    s, _session = _session, None
    return s.close() if s is not None else None


@contextlib.contextmanager
def capture(out_dir: Optional[str], **kwargs: Any
            ) -> Iterator["TelemetrySession"]:
    """Session as a context manager (closes even when the body raises)."""
    s = start(out_dir, **kwargs)
    try:
        yield s
    finally:
        if _session is s:
            stop()


class _RecompileWatcher(logging.Handler):
    """Counts jit cache misses per (function, input shapes) by listening to
    jax's `jax_log_compiles` log line; warns once per function on churn.

    The pxla logger emits "Compiling jit(<fn>) with global shapes and types
    [...]. Argument mapping: ..." per cache miss — the only public hook that
    carries function identity (jax._src.monitoring events do not)."""

    def __init__(self, sess: "TelemetrySession") -> None:
        super().__init__(level=logging.DEBUG)
        self._sess = sess
        self.per_key: Counter = Counter()  # (fn, shapes) -> compiles
        self.per_fn: Counter = Counter()
        self.kernel_total = 0  # Pallas/Mosaic subset of the per_fn total
        self._warned: set = set()
        self._logger = logging.getLogger(_PXLA_LOGGER)
        self._dispatch_logger = logging.getLogger("jax._src.dispatch")
        self._prev_flag: Optional[bool] = None
        self._prev_propagate = True
        self._prev_dispatch_level = logging.NOTSET

    def install(self) -> None:
        try:
            import jax
            self._prev_flag = bool(jax.config.jax_log_compiles)
            jax.config.update("jax_log_compiles", True)
        except Exception:  # pragma: no cover - jax unavailable/changed
            self._prev_flag = None
        # the flag makes jax log compile chatter at WARNING; keep it out of
        # the user's stderr (handlers on the logger itself still fire with
        # propagate off) — both settings restored at uninstall
        self._prev_propagate = self._logger.propagate
        self._logger.propagate = False
        self._prev_dispatch_level = self._dispatch_logger.level
        self._dispatch_logger.setLevel(logging.ERROR)
        self._logger.addHandler(self)

    def uninstall(self) -> None:
        self._logger.removeHandler(self)
        self._logger.propagate = self._prev_propagate
        self._dispatch_logger.setLevel(self._prev_dispatch_level)
        if self._prev_flag is not None:
            try:
                import jax
                jax.config.update("jax_log_compiles", self._prev_flag)
            except Exception:  # pragma: no cover
                pass

    def emit(self, record: logging.LogRecord) -> None:  # logging.Handler API
        try:
            msg = record.getMessage()
        except Exception:  # pragma: no cover - malformed record
            return
        if not msg.startswith("Compiling "):
            return
        head, _, rest = msg[len("Compiling "):].partition(
            " with global shapes and types ")
        fn = head.strip() or "<unknown>"
        if fn.startswith("jit(") and fn.endswith(")"):
            # jax logs the lowered module's name, "jit(<fn>)"; the registry
            # and the churn warning key on the function's own name
            fn = fn[4:-1]
        shapes = rest.split(". Argument mapping", 1)[0].strip()
        self.per_key[(fn, shapes)] += 1
        self.per_fn[fn] += 1
        global_timer.add_count("jit_compiles", 1)
        kernel = is_kernel_fn(fn)
        if kernel:
            self.kernel_total += 1
            global_timer.add_count("kernel_compiles", 1)
        self._sess.emit("compile", fn=fn, shapes=shapes[:400],
                        n_for_fn=self.per_fn[fn], kernel=kernel)
        if (self.per_fn[fn] >= self._sess.recompile_warn
                and fn not in self._warned):
            self._warned.add(fn)
            n_shapes = sum(1 for k in self.per_key if k[0] == fn)
            Log.warning(
                "Recompile churn: %r compiled %d times (%d distinct input "
                "shapes) — shape-unstable inputs defeat the jit cache; pad "
                "to stable buckets", fn, self.per_fn[fn], n_shapes)

    @property
    def total(self) -> int:
        return int(sum(self.per_fn.values()))


class _HbmGauge:
    """Per-device memory high-water from `device.memory_stats()`.

    `devices` is injectable for tests (fakes with a memory_stats() method);
    defaults to jax.local_devices(). Backends without stats (CPU) -> 0."""

    def __init__(self, sess: "TelemetrySession", devices=None) -> None:
        self._sess = sess
        self._devices = devices
        self.high_water: Dict[str, int] = {}

    def _device_list(self):
        if self._devices is not None:
            return self._devices
        try:
            import jax
            return jax.local_devices()
        except Exception:  # pragma: no cover - jax unavailable
            return []

    def sample(self) -> int:
        for d in self._device_list():
            stats_fn = getattr(d, "memory_stats", None)
            if stats_fn is None:
                continue
            try:
                stats = stats_fn()
            except Exception:  # backend without stats support
                stats = None
            if not stats:
                continue
            used = int(stats.get("peak_bytes_in_use",
                                 stats.get("bytes_in_use", 0)) or 0)
            name = str(d)
            if used > self.high_water.get(name, -1):
                self.high_water[name] = used
            self._sess.counter_sample(f"hbm:{name}", used)
        top = max(self.high_water.values(), default=0)
        if top:
            global_timer.set_count("hbm_high_water_bytes", top)
        return top


class TelemetrySession:
    """One recording window: event list + aggregate counts + timer spans,
    flushed to JSONL + Chrome trace at close when `out_dir` is set."""

    def __init__(self, out_dir: Optional[str] = None, label: str = "train",
                 flush_every: int = FLUSH_EVERY,
                 recompile_warn: int = RECOMPILE_WARN_THRESHOLD,
                 devices=None, watch_compiles: bool = True) -> None:
        self.out_dir = out_dir or None
        self.label = label
        self.flush_every = max(1, int(flush_every))
        self.recompile_warn = int(recompile_warn)
        self.t0 = time.perf_counter()
        self.events: List[Dict[str, Any]] = []
        self.aggregate: Counter = Counter()  # event type -> count
        self.spans: List[Tuple[str, float, float]] = []  # (label, t0, t1) rel
        self._counter_samples: List[Tuple[str, float, int]] = []
        self._counters0 = dict(global_timer.counters)
        self._closed = False
        self._summary: Dict[str, Any] = {}
        if self.out_dir:
            os.makedirs(self.out_dir, exist_ok=True)
        # force timer scopes on for the session (they feed the trace) and
        # chain any pre-existing hook; both restored at close
        self._prev_timer_enabled = global_timer.enabled
        self._prev_span_hook = global_timer.span_hook
        global_timer.enabled = True
        global_timer.span_hook = self._on_span
        self.hbm = _HbmGauge(self, devices)
        self.recompiles = _RecompileWatcher(self) if watch_compiles else None
        if self.recompiles is not None:
            self.recompiles.install()
        self.emit("session_start", label=label, wall_time=time.time(),
                  timer_epoch=global_timer.epoch, pid=os.getpid())

    def _now(self) -> float:
        return time.perf_counter() - self.t0

    def emit(self, ev: str, **fields: Any) -> None:
        rec: Dict[str, Any] = {"ev": ev, "t": round(self._now(), 6)}
        rec.update(fields)
        self.events.append(rec)
        self.aggregate[ev] += 1
        if self.out_dir and len(self.events) % self.flush_every == 0:
            self._flush_jsonl()

    def _on_span(self, label: str, start: float, end: float) -> None:
        self.spans.append((label, start - self.t0, end - self.t0))
        if self._prev_span_hook is not None:
            self._prev_span_hook(label, start, end)

    def add_span(self, label: str, start: float, end: float) -> None:
        """Record an externally-timed span (perf_counter seconds) — the
        tracing module feeds finished request/iteration stage spans here
        so the Chrome-trace export is one unified timeline. Clamped at
        the session start so a span opened pre-session can't produce a
        negative trace timestamp."""
        t0 = max(0.0, start - self.t0)
        t1 = max(t0, end - self.t0)
        self.spans.append((label, t0, t1))

    def counter_sample(self, name: str, value: int) -> None:
        """Timestamped gauge sample (becomes a "C" counter trace track)."""
        self._counter_samples.append((name, self._now(), int(value)))

    def counter_deltas(self) -> Dict[str, int]:
        """Session-scoped view of global_timer counters: accumulators as
        the delta since session start (counters are process-cumulative —
        see timer.py), gauges at their absolute level."""
        out: Dict[str, int] = {}
        for k, v in global_timer.counters.items():
            if k in global_timer.gauges:
                out[k] = int(v)
            else:
                d = int(v) - int(self._counters0.get(k, 0))
                if d:
                    out[k] = d
        return out

    def signal_snapshot(self) -> Dict[str, int]:
        """This session's watcher figures (the signals() payload) — callable
        even after stop() has already detached the module global, so the
        close-time metrics.prom snapshot reports the session's real totals
        instead of the no-session zeros."""
        return {
            "compiles": (self.recompiles.total
                         if self.recompiles is not None else 0),
            "kernel_compiles": (self.recompiles.kernel_total
                                if self.recompiles is not None else 0),
            "hbm_high_water_bytes": max(self.hbm.high_water.values(),
                                        default=0),
        }

    def close(self) -> Dict[str, Any]:
        if self._closed:
            return self._summary
        self._closed = True
        self.hbm.sample()
        summary: Dict[str, Any] = {
            "label": self.label,
            "duration_s": round(self._now(), 6),
            "events": {k: int(v) for k, v in sorted(self.aggregate.items())},
            "n_spans": len(self.spans),
            "compile_count": (self.recompiles.total
                              if self.recompiles is not None else 0),
            "kernel_compile_count": (self.recompiles.kernel_total
                                     if self.recompiles is not None else 0),
            "hbm_high_water_bytes": max(self.hbm.high_water.values(),
                                        default=0),
            "timer_totals": {k: round(global_timer.totals[k], 6)
                             for k in sorted(global_timer.totals)},
            "timer_counts": {k: int(global_timer.counts[k])
                             for k in sorted(global_timer.counts)},
            "counters": dict(sorted(self.counter_deltas().items())),
        }
        self.emit("session_end", **summary)
        if self.recompiles is not None:
            self.recompiles.uninstall()
        global_timer.span_hook = self._prev_span_hook
        global_timer.enabled = self._prev_timer_enabled
        if self.out_dir:
            self._flush_jsonl()
            self._write_trace()
            Log.info("Telemetry written to %s (%d events, %d spans)",
                     self.out_dir, len(self.events), len(self.spans))
        self._summary = summary
        return summary

    # --- sinks -----------------------------------------------------------
    def _flush_jsonl(self) -> None:
        # lazy: checkpoint.py imports this module at top level for event
        # emission, so the reverse import must happen at call time
        from .checkpoint import atomic_write_text
        text = "".join(json.dumps(e, sort_keys=True, default=_jsonable) + "\n"
                       for e in self.events)
        atomic_write_text(os.path.join(self.out_dir, EVENTS_FILE), text)
        # same cadence: a Prometheus textfile snapshot of the live counter
        # namespace, so a node-exporter collector scrapes a running train
        # exactly like the serving /metrics endpoint (exposition.py)
        try:
            from .exposition import SNAPSHOT_FILE, write_snapshot
            write_snapshot(os.path.join(self.out_dir, SNAPSHOT_FILE),
                           signals=self.signal_snapshot())
        except Exception:  # a scrape failure must never kill a train
            pass

    def _write_trace(self) -> None:
        from .checkpoint import atomic_write_text
        trace = build_chrome_trace(self.spans, self._counter_samples,
                                   label=self.label)
        atomic_write_text(os.path.join(self.out_dir, TRACE_FILE),
                          json.dumps(trace, default=_jsonable))


def _jsonable(obj: Any) -> Any:
    """JSON fallback for numpy/jax scalars and arrays in event payloads."""
    for attr in ("item", "tolist"):
        fn = getattr(obj, attr, None)
        if fn is not None:
            try:
                return fn()
            except Exception:
                pass
    return str(obj)


def build_chrome_trace(spans: List[Tuple[str, float, float]],
                       counter_samples: List[Tuple[str, float, int]],
                       label: str = "train") -> Dict[str, Any]:
    """Trace-event JSON: B/E pairs on one track (tid) per span label —
    labels never self-nest, so per-label tracks need no nesting bookkeeping
    — plus "C" counter events per gauge name. ts is µs from session start;
    the list is sorted ts-ascending with E-before-B at ties so Perfetto's
    importer never sees a child close after its parent."""
    labels = sorted({s[0] for s in spans})
    tid_of = {lbl: i + 1 for i, lbl in enumerate(labels)}
    events: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
        "args": {"name": f"lightgbm_tpu:{label}"},
    }]
    for lbl, tid in tid_of.items():
        events.append({"name": "thread_name", "ph": "M", "pid": 0,
                       "tid": tid, "args": {"name": lbl}})
    timed: List[Tuple[int, int, int, Dict[str, Any]]] = []
    for lbl, t0, t1 in spans:
        b = int(round(t0 * 1e6))
        e = max(int(round(t1 * 1e6)), b)
        dur = e - b
        tid = tid_of[lbl]
        # sort key: ts, then E(0) before B(1); longer spans open first and
        # close last at identical timestamps so nesting stays well-formed
        timed.append((b, 1, -dur, {"name": lbl, "ph": "B", "pid": 0,
                                   "tid": tid, "ts": b}))
        timed.append((e, 0, dur, {"name": lbl, "ph": "E", "pid": 0,
                                  "tid": tid, "ts": e}))
    for name, t, value in counter_samples:
        ts = int(round(t * 1e6))
        timed.append((ts, 2, 0, {"name": name, "ph": "C", "pid": 0, "tid": 0,
                                 "ts": ts, "args": {"bytes": value}}))
    timed.sort(key=lambda x: x[:3])
    events.extend(ev for _, _, _, ev in timed)
    return {"traceEvents": events, "displayTimeUnit": "ms"}
