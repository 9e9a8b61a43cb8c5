"""The package's remaining performance figures and the operator's view of
them: the ICI byte formula the sharded learners publish through, the
environment fingerprint, and the Prometheus exposition (render, parse,
/metrics endpoint, snapshot file).
"""
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import exposition, fingerprint, perfmodel, telemetry
from lightgbm_tpu.engine import train

BASE = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.1,
        "verbosity": -1, "min_data_in_leaf": 5}


def _data(n=400, f=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.standard_normal(n) * 0.5 > 0)
    return X, y.astype(np.float64)


@pytest.fixture(autouse=True)
def _no_leaked_session():
    yield
    assert telemetry.session() is None, "test leaked a telemetry session"


# -- ICI formula ----------------------------------------------------------

def test_ici_formula_matches_parallel_learner():
    # parallel/learners.py _record_ici_bytes publishes through this:
    # K*F_pad*Bmax*CH*pool_bytes + 2K*F_pad*REC*4
    k, f_pad, bmax = 21, 32, 256
    expected = k * f_pad * bmax * 3 * 4 + 2 * k * f_pad * 14 * 4
    assert perfmodel.ici_bytes_per_wave(k, f_pad, bmax) == expected
    # narrow (int16) histogram pool halves the first term only
    narrow = k * f_pad * bmax * 3 * 2 + 2 * k * f_pad * 14 * 4
    assert perfmodel.ici_bytes_per_wave(k, f_pad, bmax,
                                        pool_bytes=2) == narrow


# -- fingerprint ----------------------------------------------------------

def test_fingerprint_keys():
    fp = fingerprint.fingerprint()
    assert set(fp) == {"git_sha", "flags", "jax_version", "jaxlib_version",
                       "device_kind", "device_count", "backend"}
    assert fp["git_sha"] and fp["git_sha"] != "unknown"
    assert fp["jax_version"] != "unknown"
    assert fp["device_count"] >= 1
    assert isinstance(fp["flags"], dict)


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
