"""Performance-observatory suite: perfmodel formulas, dispatch capture +
XLA static cost analysis, attribution structure, the environment
fingerprint + bench ledger, benchdiff direction/threshold gating, and the
Prometheus exposition (render, parse, /metrics endpoint, snapshot file).
"""
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import exposition, fingerprint, perfmodel, telemetry
from lightgbm_tpu.engine import train
from lightgbm_tpu.utils.timer import global_timer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHDIFF = os.path.join(_REPO, "tools", "benchdiff.py")

BASE = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.1,
        "verbosity": -1, "min_data_in_leaf": 5}


def _data(n=400, f=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.standard_normal(n) * 0.5 > 0)
    return X, y.astype(np.float64)


@pytest.fixture(autouse=True)
def _clean_capture_state():
    perfmodel.reset_dispatches()
    yield
    perfmodel.reset_dispatches()
    assert telemetry.session() is None, "test leaked a telemetry session"


# -- analytic formulas ----------------------------------------------------

def test_carry_formula_matches_bench_expectation():
    # the bench smoke's locked figure: 28 features -> Gp=32 uint8 groups,
    # 20000 rows pad to the 1024-row wave unit, payload 5 channels carried
    # as 8 rows x 4 B (the [8, Np] carry pads to the sublane tile)
    n_pad = -(-20000 // 1024) * 1024
    assert perfmodel.carry_bytes_per_wave(20000, 28, 1, 1024) \
        == n_pad * (32 * 1 + 8 * 4)
    # int32 planes pad the group dim to 8: ceil(28/8)*8 = 32 groups still
    assert perfmodel.carry_bytes_per_wave(20000, 28, 4, 1024) \
        == n_pad * (32 * 4 + 8 * 4)
    assert perfmodel.plane_groups_padded(17, 4) == 24


def test_ici_formula_matches_parallel_learner():
    # parallel/learners.py _record_ici_bytes: K*F_pad*Bmax*CH*pool_bytes
    # + 2K*F_pad*REC*4 — perfmodel is the single source of truth now
    k, f_pad, bmax = 21, 32, 256
    expected = k * f_pad * bmax * 3 * 4 + 2 * k * f_pad * 14 * 4
    assert perfmodel.ici_bytes_per_wave(k, f_pad, bmax) == expected
    # narrow (int16) histogram pool halves the first term only
    narrow = k * f_pad * bmax * 3 * 2 + 2 * k * f_pad * 14 * 4
    assert perfmodel.ici_bytes_per_wave(k, f_pad, bmax,
                                        pool_bytes=2) == narrow


def test_peak_bandwidth_table_and_override(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_PEAK_BW_GBPS", raising=False)
    assert perfmodel.peak_bandwidth_bytes_per_s("TPU v5 lite") == 819e9
    assert perfmodel.peak_bandwidth_bytes_per_s("cpu") is None
    monkeypatch.setenv("LGBM_TPU_PEAK_BW_GBPS", "100")
    assert perfmodel.peak_bandwidth_bytes_per_s("cpu") == 100e9


# -- dispatch capture + static cost analysis ------------------------------

def test_capture_and_cost_analysis_keys_for_instrumented_fns(tmp_path):
    """A telemetry-on CPU train + predict must capture the serial-learner
    scan and histogram dispatches and the fused predict, and XLA's
    cost_analysis must report flops/bytes for each."""
    X, y = _data()
    with telemetry.capture(None, label="perfmodel-test"):
        bst = train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=3)
        bst.predict(X[:64], raw_score=True)
        captured = perfmodel.captured_stages()
        assert "scan" in captured, captured
        assert "histogram" in captured, captured
        assert "predict" in captured, captured
        static = perfmodel.static_costs()
    for stage in ("scan", "histogram", "predict"):
        entry = static[stage]
        assert "error" not in entry, (stage, entry)
        assert entry["flops"] > 0, (stage, entry)
        assert entry["bytes_accessed"] > 0, (stage, entry)
        assert entry["argument_bytes"] > 0, (stage, entry)
    # repeat lowering hits the cache, not a recompute
    assert perfmodel.static_costs() == static


def test_capture_is_noop_without_session():
    X, y = _data(n=120)
    train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=1)
    assert perfmodel.captured_stages() == []


# -- attribution ----------------------------------------------------------

def test_attribution_fractions_sum_to_one_on_real_train():
    X, y = _data()
    with telemetry.capture(None, label="attr-test"):
        train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=3)
        report = perfmodel.attribution(dict(global_timer.totals),
                                       dict(global_timer.counters))
    assert report["stages"], report
    assert abs(report["fractions_sum"] - 1.0) <= 0.05, report
    for st in report["stages"].values():
        assert 0.0 <= st["fraction"] <= 1.0
        assert st["wall_s"] >= 0.0


def test_attribution_model_and_roofline(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_PEAK_BW_GBPS", "1")  # 1e9 B/s
    totals = {"boosting": 2.0, "tree_device": 1.0, "update_score": 0.4}
    counters = {"device_waves": 10,
                "device_carry_bytes_per_wave": 10_000_000,
                "device_hist_rows": 1_000_000,
                "device_hist_bytes_per_row": 52,
                "device_scan_bytes_per_wave": 2_000_000,
                "device_ici_bytes_per_wave": 500_000}
    rep = perfmodel.attribution(totals, counters, device_kind="whatever")
    grow = rep["stages"]["grow_fused"]
    comp = grow["model_components_bytes"]
    assert comp["compact"] == 2 * 10_000_000 * 10
    assert comp["histogram"] == 1_000_000 * 52
    assert comp["scan"] == 2_000_000 * 10
    assert comp["ici"] == 500_000 * 10
    assert grow["model_bytes"] == sum(comp.values())
    # model seconds at 1e9 B/s; drift + roofline derived from it
    assert grow["model_s"] == pytest.approx(grow["model_bytes"] / 1e9)
    assert "drift_pct" in grow and "roofline_frac" in grow
    # the uncovered wall shows up as an explicit "other" stage and the
    # fractions still close to 1
    assert "other" in rep["stages"]
    assert abs(rep["fractions_sum"] - 1.0) <= 0.05


# -- fingerprint + ledger -------------------------------------------------

def test_fingerprint_keys():
    fp = fingerprint.fingerprint()
    assert fp["schema_version"] == fingerprint.LEDGER_SCHEMA_VERSION
    assert fp["git_sha"] and fp["git_sha"] != "unknown"
    assert fp["jax_version"] != "unknown"
    assert fp["device_count"] >= 1
    assert isinstance(fp["flags"], dict)


def test_ledger_append_and_disable(tmp_path, monkeypatch):
    path = str(tmp_path / "ledger.jsonl")
    assert fingerprint.append_ledger({"value": 1}, path=path) == path
    assert fingerprint.append_ledger({"value": 2}, path=path) == path
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert [r["value"] for r in lines] == [1, 2]
    monkeypatch.setenv("BENCH_LEDGER", "off")
    assert fingerprint.ledger_path() is None
    assert fingerprint.append_ledger({"value": 3}) is None


# -- benchdiff gating -----------------------------------------------------

def _record(**over):
    rec = {"metric": "train_row_iters_per_sec", "value": 10_000.0,
           "unit": "row_iters/s", "platform": "cpu", "rows": 20000,
           "iters": 2, "auc": 0.85, "est_carried_bytes_per_wave": 1064960,
           "predict_chunk_rows": 8192, "device_hist_rows": 0,
           "serve_p99_ms": 4.0, "schema_version": 1,
           "fingerprint": {"git_sha": "aaa", "schema_version": 1},
           "attribution": {"fractions_sum": 1.0}}
    rec.update(over)
    return rec


def _run_benchdiff(tmp_path, old, new, *extra):
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(json.dumps(old) + "\n" + json.dumps(new) + "\n")
    out = subprocess.run(
        [sys.executable, BENCHDIFF, str(ledger), "--gate", *extra],
        capture_output=True, text=True, timeout=60)
    return out


def test_benchdiff_exits_1_on_seeded_throughput_regression(tmp_path):
    out = _run_benchdiff(tmp_path, _record(), _record(value=5_000.0))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "REGRESSION" in out.stdout and "value" in out.stdout


def test_benchdiff_exits_0_on_noise_within_threshold(tmp_path):
    out = _run_benchdiff(tmp_path, _record(), _record(value=10_400.0,
                                                      serve_p99_ms=4.2))
    assert out.returncode == 0, out.stdout + out.stderr


def test_benchdiff_direction_lower_is_better(tmp_path):
    # serve_p99_ms doubling IS a regression; halving is an improvement
    out = _run_benchdiff(tmp_path, _record(), _record(serve_p99_ms=20.0))
    assert out.returncode == 1, out.stdout
    out = _run_benchdiff(tmp_path, _record(), _record(serve_p99_ms=1.0,
                                                      value=20_000.0))
    assert out.returncode == 0, out.stdout
    assert "improved" in out.stdout


def test_benchdiff_exact_metric_change_gates(tmp_path):
    out = _run_benchdiff(tmp_path, _record(),
                         _record(est_carried_bytes_per_wave=999))
    assert out.returncode == 1, out.stdout


def test_benchdiff_deterministic_only_skips_perf(tmp_path):
    out = _run_benchdiff(tmp_path, _record(), _record(value=5_000.0),
                         "--deterministic-only")
    assert out.returncode == 0, out.stdout + out.stderr


def test_benchdiff_bad_attribution_gates(tmp_path):
    bad = _record(attribution={"fractions_sum": 0.5})
    out = _run_benchdiff(tmp_path, _record(), bad)
    assert out.returncode == 1, out.stdout


def test_benchdiff_incomparable_records_skip_not_fail(tmp_path):
    out = _run_benchdiff(tmp_path, _record(rows=40000),
                         _record(value=5_000.0))
    assert out.returncode == 0, out.stdout
    assert "not comparable" in out.stdout
    out = _run_benchdiff(tmp_path, _record(rows=40000),
                         _record(value=5_000.0), "--strict")
    assert out.returncode == 1, out.stdout


def test_benchdiff_gates_stream_sharded_metrics(tmp_path):
    """Pod-streaming SPEC entries: throughput gates as perf (skipped in
    CI's deterministic-only mode); the overlap/merge pair gates
    everywhere inside wide deterministic tolerances."""
    old = _record(stream_sharded_rows_per_sec=1000.0,
                  stream_h2d_overlap_pct=80.0, stream_sketch_merge_ms=10.0)
    # throughput halves: a perf regression ...
    out = _run_benchdiff(tmp_path, old,
                         _record(stream_sharded_rows_per_sec=400.0,
                                 stream_h2d_overlap_pct=80.0,
                                 stream_sketch_merge_ms=10.0))
    assert out.returncode == 1, out.stdout
    assert "stream_sharded_rows_per_sec" in out.stdout
    # ... that deterministic-only CI mode does NOT gate on
    out = _run_benchdiff(tmp_path, old,
                         _record(stream_sharded_rows_per_sec=400.0,
                                 stream_h2d_overlap_pct=80.0,
                                 stream_sketch_merge_ms=10.0),
                         "--deterministic-only")
    assert out.returncode == 0, out.stdout
    # overlap collapsing past the 25-point allowance gates even there
    out = _run_benchdiff(tmp_path, old,
                         _record(stream_sharded_rows_per_sec=1000.0,
                                 stream_h2d_overlap_pct=20.0,
                                 stream_sketch_merge_ms=10.0),
                         "--deterministic-only")
    assert out.returncode == 1, out.stdout
    assert "stream_h2d_overlap_pct" in out.stdout
    # a merge wall blowing through the 250ms allowance gates too
    out = _run_benchdiff(tmp_path, old,
                         _record(stream_sharded_rows_per_sec=1000.0,
                                 stream_h2d_overlap_pct=80.0,
                                 stream_sketch_merge_ms=700.0),
                         "--deterministic-only")
    assert out.returncode == 1, out.stdout
    assert "stream_sketch_merge_ms" in out.stdout


def test_benchdiff_gates_against_committed_baseline():
    """The committed CPU baseline must self-gate clean (the CI invocation)."""
    baseline = os.path.join(_REPO, "BENCH_BASELINE_CPU.json")
    out = subprocess.run(
        [sys.executable, BENCHDIFF, baseline, baseline,
         "--gate", "--deterministic-only"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr


# -- exposition -----------------------------------------------------------

def test_render_metrics_matches_signals_and_parses():
    with telemetry.capture(None, label="expo-test"):
        X, y = _data(n=137, f=11)
        train(dict(BASE), lgb.Dataset(X, label=y), num_boost_round=1)
        sig = telemetry.signals()
        text = exposition.render_metrics(extra={"serve_p50_ms": 1.25})
    parsed = exposition.parse_exposition(text)
    assert parsed[("lgbm_tpu_compiles_total", ())] == float(sig["compiles"])
    assert sig["compiles"] > 0
    assert parsed[("lgbm_tpu_kernel_compiles_total", ())] \
        == float(sig["kernel_compiles"])
    assert parsed[("lgbm_tpu_hbm_high_water_bytes", ())] \
        == float(sig["hbm_high_water_bytes"])
    assert parsed[("lgbm_tpu_telemetry_enabled", ())] == 1.0
    assert parsed[("lgbm_tpu_serve_p50_ms", ())] == 1.25
    # per-stage timer totals carry the stage label
    stage_samples = [k for k in parsed
                     if k[0] == "lgbm_tpu_stage_seconds_total"]
    assert stage_samples, sorted(parsed)
    assert all(dict(labels).get("stage") for _, labels in stage_samples)


def test_parse_exposition_rejects_malformed():
    with pytest.raises(ValueError):
        exposition.parse_exposition("this is { not a metric line\n")


def test_telemetry_dir_gets_metrics_snapshot(tmp_path):
    X, y = _data(n=150)
    train(dict(BASE, telemetry_dir=str(tmp_path)), lgb.Dataset(X, label=y),
          num_boost_round=2)
    snap = tmp_path / exposition.SNAPSHOT_FILE
    assert snap.is_file()
    parsed = exposition.parse_exposition(snap.read_text())
    # the close-time snapshot must carry the SESSION's compile total, not
    # the no-session zeros (stop() detaches the module global before close)
    assert parsed[("lgbm_tpu_compiles_total", ())] > 0
    assert parsed[("lgbm_tpu_telemetry_enabled", ())] == 0.0


def test_metrics_endpoint_prometheus_text():
    from lightgbm_tpu.serving import PredictionService
    from lightgbm_tpu.serving.http import serve

    rng = np.random.RandomState(42)
    X = rng.rand(300, 10)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float64)
    bst = lgb.train(dict(BASE, num_leaves=15), lgb.Dataset(X, label=y),
                    num_boost_round=4)
    svc = PredictionService(max_batch_rows=512, batch_window_s=0.0)
    server = None
    try:
        svc.load_model("m", booster=bst)
        svc.predict("m", X[:32], raw_score=True)
        server, _ = serve(svc, port=0)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=10) as r:
            assert r.status == 200
            ctype = r.headers.get("Content-Type", "")
            body = r.read().decode("utf-8")
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        parsed = exposition.parse_exposition(body)
        sig = telemetry.signals()
        assert parsed[("lgbm_tpu_compiles_total", ())] \
            == float(sig["compiles"])
        assert parsed[("lgbm_tpu_hbm_high_water_bytes", ())] \
            == float(sig["hbm_high_water_bytes"])
        # the flattened /statz figures ride along as serve_* gauges
        assert parsed[("lgbm_tpu_serve_batcher_batches", ())] >= 1.0
        assert ("lgbm_tpu_serve_breaker_failures", ()) in parsed \
            or ("lgbm_tpu_serve_swaps", ()) in parsed
    finally:
        if server is not None:
            server.shutdown()
        svc.close()
