"""Cost-model-attributed profiling: expected vs measured, per stage.

docs/PERF_NOTES.md derives an analytic model for every hot stage of the
wave learner — carry bytes dragged through HBM per wave, rows-in-leaf
histogram traffic, the gain-scan read volume, the ICI merge — but until
now the model lived only in prose, and the telemetry stack (telemetry.py)
recorded only measured walls. This module connects the two so a bench
capture can say *which* stage is eating the gap to the reference baseline
instead of just restating the end-to-end number:

  * **Formulas as code** — `carry_bytes_per_wave`, `hist_bytes_per_row`,
    `scan_bytes_per_wave`, `ici_bytes_per_wave` are the executable form of
    the PERF_NOTES models. The device/sharded learners publish their
    gauges through these functions (one source of truth; the doc
    cross-links here), and `attribution()` reads them back from
    `global_timer` counters.
  * **Static compile-time costs** — `note_dispatch()` captures the jitted
    callable plus abstract arg shapes the first time each instrumented
    stage dispatches (growth, compaction, scan, predict);
    `static_costs()` later AOT-lowers each capture and reads XLA's own
    `cost_analysis()` / `memory_analysis()` — flops, bytes accessed, peak
    temp bytes — for the actual compiled program, no estimate drift.
  * **Attribution** — `attribution()` merges measured per-stage walls
    (timer totals, captured by any telemetry session), the analytic byte
    model, and a per-device-kind peak-bandwidth table into a report:
    stage fraction of the covered wall (fractions sum to 1, the residual
    is an explicit "other" stage), model-implied seconds, model-vs-
    measured drift, and the roofline fraction actually achieved.

bench.py embeds the report in every capture record (the ledger schema in
docs/OBSERVABILITY.md); `tools/perfreport.py` renders it for humans.

Hot-path cost: `note_dispatch` is a dict-membership check after the first
capture of a stage, and call sites guard on `telemetry.enabled()` — the
disabled path stays a no-op (graftlint R9 polices this file's scope too).
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping, NamedTuple, Optional, Tuple

# ---------------------------------------------------------------------------
# Analytic formulas — the executable docs/PERF_NOTES.md model
# ---------------------------------------------------------------------------

# per-wave loop-carry payload: gh channels + position + leaf id, one f32
# row of the [8, Np] carry each (the rows pad to the 8-sublane tile)
PAYLOAD_COLS = 5
# packed best-split record length ([2K, F_pad, REC] all_gather, f32)
REC_FIELDS = 14


def padded_rows(n_rows: int, unit: int) -> int:
    """Rows padded to the wave tile unit (compaction/histogram grids)."""
    return -(-int(n_rows) // int(unit)) * int(unit)


def plane_groups_padded(n_groups: int, plane_bytes: int) -> int:
    """Bin-plane group dim after Mosaic tile padding: uint8 planes pad to
    the (32, 128) tile's 32 sublanes, int32 planes to 8."""
    g = int(n_groups)
    return -(-g // 32) * 32 if int(plane_bytes) == 1 else -(-g // 8) * 8


def carry_bytes_per_wave(n_rows: int, n_groups: int, plane_bytes: int,
                         unit: int) -> int:
    """HBM bytes of the wave loop carry (PERF_NOTES round-5):
    ``Gp * Np * plane_bytes + Np * payload_rows * 4``, the payload's
    PAYLOAD_COLS channels carried as rows padded to a multiple of 8."""
    np_rows = padded_rows(n_rows, unit)
    gp = plane_groups_padded(n_groups, plane_bytes)
    payload_rows = -(-PAYLOAD_COLS // 8) * 8
    return gp * np_rows * int(plane_bytes) + np_rows * payload_rows * 4


def hist_bytes_per_row(n_groups: int, plane_bytes: int, ch: int = 3) -> int:
    """Bytes the ragged histogram kernel streams per histogrammed row: the
    row's bin-plane column plus its gh payload channels."""
    gp = plane_groups_padded(n_groups, plane_bytes)
    return gp * int(plane_bytes) + int(ch) * 4


def stream_block_bytes(block_rows: int, n_groups: int, plane_bytes: int) -> int:
    """H2D bytes of one streamed bin block (streaming/learner.py): the raw
    [G, block_rows] slab — ``G * block_rows * plane_bytes``. Transfers copy
    the unpadded host slab; Mosaic tile padding applies only once the block
    feeds a kernel, so the G here is the true group count, not
    plane_groups_padded."""
    return int(n_groups) * int(block_rows) * int(plane_bytes)


def scan_bytes_per_wave(wave_width: int, f_pad: int, max_bins: int,
                        ch: int = 3, pool_bytes: int = 4) -> int:
    """Gain-scan traffic per wave (PERF_NOTES round-4 step 5, round-8):
    the XLA scan reads the [K, F_pad, Bmax, CH] histogram pool block,
    writes the [2K, F_pad, REC] best-record store, and materializes the
    two per-lane gain tensors ([K, F_pad, 2*Bmax] f32, written then
    re-read by the argmax) through HBM."""
    k = int(wave_width)
    return (k * int(f_pad) * int(max_bins) * int(ch) * int(pool_bytes)
            + 2 * k * int(f_pad) * REC_FIELDS * 4
            + 2 * k * int(f_pad) * 2 * int(max_bins) * 4)


def ici_bytes_per_wave(wave_width: int, f_pad: int, max_bins: int,
                       ch: int = 3, pool_bytes: int = 4) -> int:
    """Cross-device bytes per wave for the data-parallel learner
    (PERF_NOTES round-6): one psum_scatter of the raw [K, F_pad, Bmax, CH]
    histograms plus the [2K, F_pad, REC] best-record all_gather."""
    k = int(wave_width)
    return (k * int(f_pad) * int(max_bins) * int(ch) * int(pool_bytes)
            + 2 * k * int(f_pad) * REC_FIELDS * 4)


def voting_ici_bytes_per_wave(wave_width: int, k_local: int, k_global: int,
                              max_bins: int, n_shards: int, ch: int = 3,
                              pool_bytes: int = 4) -> int:
    """Cross-device bytes per wave for the voting-parallel learner
    (PERF_NOTES round-9, PV-Tree): the [2K, D*k_local] nomination
    all_gather plus the psum of the [2K, k_global, Bmax, CH] ELECTED
    histogram slices. No term scales with the feature count — that is the
    whole point of the vote."""
    k = int(wave_width)
    return (2 * k * int(n_shards) * int(k_local) * 4
            + 2 * k * int(k_global) * int(max_bins) * int(ch)
            * int(pool_bytes))


def feature_ici_bytes_per_wave(wave_width: int, n_shards: int) -> int:
    """Cross-device bytes per wave for the feature-parallel learner
    (PERF_NOTES round-9): rows are replicated and every histogram stays
    local, so the only traffic is the [2K, D, REC] best-record all_gather
    — independent of the row count AND the feature count."""
    return 2 * int(wave_width) * int(n_shards) * REC_FIELDS * 4


def serve_wire_bytes_per_request(n_rows: int, n_cols: int,
                                 binary: bool = True,
                                 name_len: int = 8,
                                 json_chars_per_value: int = 20) -> int:
    """Request-body bytes on the serving wire (PERF_NOTES round-10).

    Binary (serving/wire.py): a fixed 24-byte header + the model name +
    the raw f32 row block — 4 bytes per value, parsed by one zero-copy
    frombuffer. JSON: each f64 value prints as up to ~20 characters
    (sign, 17 significant digits, exponent, comma), so the same rows cost
    ~5x the bytes AND a per-value float parse. The ratio is the static
    half of the measured serve_wire_binary_rows_per_sec /
    serve_rows_per_sec speedup; the dynamic half is the per-request
    allocation count (one view vs a parsed list-of-lists)."""
    if binary:
        return 24 + int(name_len) + 4 * int(n_rows) * int(n_cols)
    # {"model": ..., "rows": [[...]]} framing plus per-value text
    return (24 + int(name_len)
            + int(n_rows) * int(n_cols) * int(json_chars_per_value)
            + 2 * int(n_rows))


def serve_cold_start_ms(n_buckets: int, compile_ms_per_bucket: float,
                        deserialize_ms_per_bucket: float = 7.0,
                        aot: bool = True) -> float:
    """Replica cold-start model (PERF_NOTES round-10): time from model
    load to the first bucket-shaped answer. Without an AOT bundle every
    warmup bucket pays one XLA compile (O(100ms) each, serialized on the
    main thread); with one (ops/predict.aot_serialize_bundle persisted by
    checkpoint.write_aot_sidecar) each bucket pays only executable
    deserialization, measured at ~7ms on CPU — a ~25x per-bucket ratio
    that the serve_cold_start_ms ledger metric tracks end to end."""
    per = (float(deserialize_ms_per_bucket) if aot
           else float(compile_ms_per_bucket))
    return float(n_buckets) * per


def serve_replica_scaling_efficiency(t1_rows_per_sec: float,
                                     tn_rows_per_sec: float,
                                     n_replicas: int) -> float:
    """Fleet dispatch efficiency: measured N-replica throughput over N x
    the single-replica figure. Below 1.0 the replicas are contending (one
    device queue, GIL-held decode, shared breaker lock); the ledger metric
    of the same name records the 2-replica figure on the smoke bench."""
    if t1_rows_per_sec <= 0 or n_replicas <= 0:
        return 0.0
    return round(float(tn_rows_per_sec)
                 / (float(n_replicas) * float(t1_rows_per_sec)), 4)


def ici_overlap_pct(overlapped_bytes: int, total_bytes: int) -> float:
    """Share of a wave's ICI traffic dispatched while independent local
    compute is still pending (double-buffered dispatch, PERF_NOTES
    round-9) — the fraction of the transfer XLA's async collectives can
    hide behind the Pallas kernels. Byte accounting, so the gauge is
    deterministic; the wall-clock benefit shows up in the tree_device
    stage attribution instead."""
    if int(total_bytes) <= 0:
        return 0.0
    return round(100.0 * int(overlapped_bytes) / int(total_bytes), 2)


# Peak HBM bandwidth per chip by device kind (bytes/s). Matched by
# substring against jax's `device_kind` string; used for the roofline
# fraction in attribution reports. Override with LGBM_TPU_PEAK_BW_GBPS.
PEAK_HBM_BYTES_PER_S: Tuple[Tuple[str, float], ...] = (
    ("v5 lite", 819e9), ("v5e", 819e9), ("v5p", 2765e9),
    ("v4", 1228e9), ("v3", 900e9), ("v2", 700e9),
)


# VMEM capacity per core by device kind (bytes), substring-matched like
# the bandwidth table. graftlint R14 reads this file's AST (no import) to
# bound every pallas_call's worst-case footprint; nothing reads them at
# run time. The DEFAULT is the lint's floor every kernel must fit: the
# smallest VMEM of any device the kernels are expected to run on (see /opt
# guide numbers cited in docs/PERF_NOTES.md).
PALLAS_VMEM_BYTES: Tuple[Tuple[str, int], ...] = (
    ("v5 lite", 134217728), ("v5e", 134217728),   # 128 MiB
    ("v7x", 67108864),                            # 64 MiB
)
PALLAS_VMEM_DEFAULT_BYTES: int = 16777216          # 16 MiB conservative floor

# Lint-time worst-case caps for BlockSpec dimensions that are runtime
# values (static argnums, shape components). R14 substitutes these when a
# dimension does not resolve to a literal/module constant; raising a cap
# here widens the envelope the kernels are certified for, so keep each in
# sync with the call sites' actual maxima.
PALLAS_DIM_BOUNDS: Tuple[Tuple[str, int], ...] = (
    ("num_bins", 256), ("n_bins", 256),   # histogram bins cap (uint8 codes)
    ("tile_rows", 2048),                  # row tiles (hist/predict)
    ("GB", 64), ("CH", 8), ("SC", 64),    # hist group block / channels / slots
    ("Gp", 512), ("tile", 1024), ("rc", 16),  # compact planes / row tile / cols
    ("F", 1024), ("C", 32),               # predict feature row / tree outputs
)


def peak_bandwidth_bytes_per_s(device_kind: str = "") -> Optional[float]:
    """Peak HBM bytes/s for a device kind, or None when unknown (CPU and
    unrecognized backends report no roofline). $LGBM_TPU_PEAK_BW_GBPS
    overrides — the knob for calibrating against a measured STREAM."""
    import os

    env = os.environ.get("LGBM_TPU_PEAK_BW_GBPS", "")
    if env:
        try:
            return float(env) * 1e9
        except ValueError:
            pass
    kind = (device_kind or "").lower()
    for marker, bw in PEAK_HBM_BYTES_PER_S:
        if marker in kind:
            return bw
    return None


# ---------------------------------------------------------------------------
# Dispatch capture — static flops/bytes from XLA's own cost analysis
# ---------------------------------------------------------------------------


class _Dispatch(NamedTuple):
    fn: Any                     # the jitted callable (has .lower)
    args: Tuple[Any, ...]       # ShapeDtypeStructs / static literals
    kwargs: Dict[str, Any]


_dispatches: Dict[str, _Dispatch] = {}
_static_cache: Dict[str, Dict[str, Any]] = {}


def _abstractify(x: Any) -> Any:
    """Array-like (incl. tracers mid-trace) -> ShapeDtypeStruct; anything
    else (static ints, bools, None) passes through for the AOT re-lower."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return x
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def note_dispatch(stage: str, fn: Any, *args: Any, **kwargs: Any) -> None:
    """Record one instrumented stage's dispatch signature (first one wins).

    Called from the stage's real call site — eagerly (grow, scan, predict)
    or at trace time (the compaction pallas_call inside the fused growth
    jit): tracers carry shape/dtype, which is all the AOT lower needs.
    After the first capture this is a dict-membership check, so per-tree /
    per-predict call sites stay O(1)."""
    if stage in _dispatches:
        return
    try:
        import jax

        spec_args = tuple(jax.tree_util.tree_map(_abstractify, a)
                          for a in args)
        spec_kwargs = {k: jax.tree_util.tree_map(_abstractify, v)
                       for k, v in kwargs.items()}
    except Exception:  # never let instrumentation break a dispatch
        return
    _dispatches[stage] = _Dispatch(fn, spec_args, spec_kwargs)
    _static_cache.pop(stage, None)


def captured_stages() -> List[str]:
    return sorted(_dispatches)


def reset_dispatches() -> None:
    """Test hook: forget captured dispatches (and their cached analyses)."""
    _dispatches.clear()
    _static_cache.clear()


def static_costs(stages: Optional[List[str]] = None) -> Dict[str, Dict[str, Any]]:
    """AOT-lower every captured dispatch and read the compiled program's
    own cost figures. Per stage: ``flops``, ``bytes_accessed`` (from
    ``cost_analysis()``), ``argument_bytes`` / ``output_bytes`` /
    ``temp_bytes`` (from ``memory_analysis()``). A stage that fails to
    lower degrades to an ``error`` entry — never an exception (a capture
    run must not die on an analysis)."""
    out: Dict[str, Dict[str, Any]] = {}
    for stage in (stages or captured_stages()):
        if stage in _static_cache:
            out[stage] = _static_cache[stage]
            continue
        d = _dispatches.get(stage)
        if d is None:
            continue
        try:
            compiled = d.fn.lower(*d.args, **d.kwargs).compile()
            entry = _read_compiled(compiled)
        except Exception as e:  # noqa: BLE001 - structured degradation
            entry = {"error": repr(e)[:300]}
        _static_cache[stage] = entry
        out[stage] = entry
    return out


def _read_compiled(compiled: Any) -> Dict[str, Any]:
    entry: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    # jax returns one properties dict per computation on this version
    # (older/newer return the dict directly) — normalize both shapes
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, Mapping):
        entry["flops"] = float(ca.get("flops", 0.0))
        entry["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        for key, attr in (("argument_bytes", "argument_size_in_bytes"),
                          ("output_bytes", "output_size_in_bytes"),
                          ("temp_bytes", "temp_size_in_bytes"),
                          ("code_bytes", "generated_code_size_in_bytes")):
            val = getattr(ma, attr, None)
            if val is not None:
                entry[key] = int(val)
    if not entry:
        entry["error"] = "backend reported no cost/memory analysis"
    return entry


# ---------------------------------------------------------------------------
# Attribution — measured walls x analytic bytes x roofline
# ---------------------------------------------------------------------------

# stage name -> timer labels whose totals it owns. These are the LEAF
# scopes of the training loop (never a scope that nests another listed
# one, so stage walls are disjoint and the fractions can sum to 1).
STAGE_LABELS: Dict[str, Tuple[str, ...]] = {
    "grow_fused": ("tree_device",),
    "histogram": ("hist_root", "hist_children", "hist_recompute"),
    "scan": ("find_best_split",),
    "partition": ("partition",),
    "replay": ("tree_replay",),
    "score_update": ("update_score",),
    "bagging": ("bagging",),
    "linear_fit": ("linear_fit",),
}

ATTRIBUTION_SCHEMA_VERSION = 1


def model_bytes_from_counters(counters: Mapping[str, int]) -> Dict[str, int]:
    """Total analytic HBM/ICI bytes per stage for one run, reconstructed
    from the gauges/counters the learners publish (PERF_NOTES models):

      compaction  2 x carry x waves   (the kernel reads AND writes the carry)
      histogram   hist_rows x bytes/row  (the rows-in-leaf ragged kernel)
      scan        scan_bytes x waves
      ici         ici_bytes x waves

    Missing counters contribute nothing — a serial-learner run (no device
    gauges) yields an empty model and the attribution falls back to pure
    measured fractions."""
    waves = int(counters.get("device_waves", 0))
    out: Dict[str, int] = {}
    carry = int(counters.get("device_carry_bytes_per_wave", 0))
    if carry and waves:
        out["compact"] = 2 * carry * waves
    hist_rows = int(counters.get("device_hist_rows", 0))
    row_bytes = int(counters.get("device_hist_bytes_per_row", 0))
    if hist_rows and row_bytes:
        out["histogram"] = hist_rows * row_bytes
    scan = int(counters.get("device_scan_bytes_per_wave", 0))
    if scan and waves:
        out["scan"] = scan * waves
    ici = int(counters.get("device_ici_bytes_per_wave", 0))
    if ici and waves:
        out["ici"] = ici * waves
    # out-of-core H2D traffic: the block cache counts every upload's bytes
    # directly (blocks x stream_block_bytes + per-split group rows), so the
    # counter IS the model — no waves multiplier
    h2d = int(counters.get("stream_h2d_bytes", 0))
    if h2d:
        out["stream_h2d"] = h2d
    return out


def attribution(totals: Mapping[str, float], counters: Mapping[str, int],
                total_s: Optional[float] = None,
                device_kind: str = "",
                include_static: bool = False) -> Dict[str, Any]:
    """Per-stage attribution report.

    totals:   timer label -> accumulated seconds (global_timer.totals or a
              snapshot / a telemetry session_end's ``timer_totals``)
    counters: global_timer counters (for the analytic byte model)
    total_s:  the wall to attribute against; defaults to the ``boosting``
              scope total (the whole training loop)
    Returns ``{"stages": {name: {...}}, "fractions_sum": ~1.0, ...}``;
    every stage carries ``wall_s`` and ``fraction``, device stages add
    ``model_bytes`` / ``model_s`` / ``drift_pct`` / ``roofline_frac``
    when the analytic model and bandwidth table cover them."""
    if total_s is None:
        total_s = float(totals.get("boosting", 0.0))
    walls: Dict[str, float] = {}
    for stage, labels in STAGE_LABELS.items():
        w = sum(float(totals.get(lbl, 0.0)) for lbl in labels)
        if w > 0.0:
            walls[stage] = w
    covered = sum(walls.values())
    if total_s <= 0.0:
        total_s = covered
    # nested scopes cannot overflow their parent, but when no parent scope
    # ran (direct learner drives in tests) covered IS the total
    if covered > total_s:
        total_s = covered
    model = model_bytes_from_counters(counters)
    bw = peak_bandwidth_bytes_per_s(device_kind)
    stages: Dict[str, Dict[str, Any]] = {}
    for stage, wall in sorted(walls.items(), key=lambda kv: -kv[1]):
        entry: Dict[str, Any] = {
            "wall_s": round(wall, 6),
            "fraction": round(wall / total_s, 6) if total_s else 0.0,
        }
        # the fused device stage owns every analytic component; host-driven
        # stages map 1:1 by name
        if stage == "grow_fused":
            comp = dict(model)
            comp.pop("stream_h2d", None)  # H2D is its own (overlapped) stage
            if comp:
                entry["model_components_bytes"] = comp
                m_bytes = sum(comp.values())
                entry["model_bytes"] = m_bytes
                _add_model_seconds(entry, m_bytes, wall, bw)
        elif stage in model:
            entry["model_bytes"] = model[stage]
            _add_model_seconds(entry, model[stage], wall, bw)
        stages[stage] = entry
    other = max(total_s - covered, 0.0)
    if total_s > 0.0 and other > 0.0:
        stages["other"] = {"wall_s": round(other, 6),
                           "fraction": round(other / total_s, 6)}
    if "stream_h2d" in model:
        # out-of-core block transfer: dispatched behind histogram compute
        # (streaming/learner.py double buffer), so its wall rides inside
        # stages already counted — fraction stays 0 and the stage is
        # excluded from the ~1.0 closure by construction
        h2d_wall = float(counters.get("stream_h2d_us", 0)) / 1e6
        entry = {"wall_s": round(h2d_wall, 6), "fraction": 0.0,
                 "overlapped": True, "model_bytes": model["stream_h2d"]}
        _add_model_seconds(entry, model["stream_h2d"], h2d_wall, bw)
        stages["stream_h2d"] = entry
    report: Dict[str, Any] = {
        "schema_version": ATTRIBUTION_SCHEMA_VERSION,
        "total_s": round(total_s, 6),
        "covered_s": round(covered, 6),
        "stages": stages,
        "fractions_sum": round(sum(s["fraction"] for s in stages.values()),
                               6) if stages else 0.0,
    }
    if bw is not None:
        report["peak_bw_bytes_per_s"] = bw
    if include_static:
        static = static_costs()
        if static:
            report["static"] = static
    return report


def _add_model_seconds(entry: Dict[str, Any], model_bytes: int,
                       wall_s: float, bw: Optional[float]) -> None:
    """Model-implied seconds at peak bandwidth, measured-vs-model drift,
    and the roofline fraction the stage actually achieved."""
    if not bw or model_bytes <= 0:
        return
    model_s = model_bytes / bw
    entry["model_s"] = round(model_s, 6)
    if wall_s > 0.0:
        entry["drift_pct"] = round((wall_s / model_s - 1.0) * 100.0, 1)
        entry["roofline_frac"] = round(model_s / wall_s, 4)
