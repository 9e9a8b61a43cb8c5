"""bench.py smoke test: the benchmark entrypoint must emit its ONE JSON
record with a real throughput number on a small CPU run — catching drift
between the bench harness and the library surface before a capture round
burns a TPU window on it."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_smoke_cpu(tmp_path):
    ledger = tmp_path / "ledger.jsonl"
    env = dict(os.environ)
    env.update({
        "BENCH_ROWS": "20000",
        "BENCH_ITERS": "2",
        "BENCH_PLATFORM": "cpu",  # skip the accelerator probe entirely
        "BENCH_QUANTIZED": "0",   # primary metric only: keep the smoke fast
        "JAX_PLATFORMS": "cpu",
        "BENCH_LEDGER": str(ledger),  # don't dirty the repo ledger
    })
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        capture_output=True, text=True, timeout=900, env=env, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    # last stdout line is the structured record
    record = json.loads(out.stdout.strip().splitlines()[-1])
    assert record["metric"] == "train_row_iters_per_sec"
    assert record["platform"] == "cpu"
    assert "error" not in record, record
    assert record["value"] > 0
    assert record["rows"] == 20000
    assert 0.5 <= record["auc"] <= 1.0
    # wave-traffic instrumentation: both fields present on EVERY record
    # (CPU benches run the serial learner, so the row counter may be 0 but
    # the carry estimate still comes from the dataset shape formula)
    assert record["device_hist_rows"] >= 0
    assert record["est_carried_bytes_per_wave"] > 0
    # 28 features -> Gp=32 groups; rows pad to the 1024-row wave unit.
    # uint8 plane: carry = np_rows * (32*1 + 32), the payload's 5 channels
    # carried as 8 f32 rows; the int32 figure would be np_rows * (32*4 + 32)
    # — assert we sit in the narrow-plane regime.
    n_pad = -(-20000 // 1024) * 1024
    assert record["est_carried_bytes_per_wave"] == n_pad * (32 + 32)
    # kernel instrumentation: both microlatency fields are real timed
    # dispatches (the XLA split scan and the device GOSS select both run
    # on any backend); the wave fields are 0 on CPU benches (serial
    # learner — no waves dispatched) but must exist
    assert "scan_kernel_error" not in record, record
    assert "goss_kernel_error" not in record, record
    assert record["scan_kernel_ms"] > 0
    assert record["goss_device_gather_ms"] > 0
    assert 0.0 <= record["wave_commit_rate"] <= 1.0
    assert record["adaptive_k_final"] >= 0
    # inference metric: chunked streaming predict must have run and timed.
    # 20000 rows -> chunk = bucket_size(5000, 1024) = 8192 (3 chunks).
    assert record["predict_rows_per_sec"] > 0
    assert record["predict_chunk_rows"] == 8192
    # robustness-layer cost tracking: a real timed checkpoint write and a
    # measured guardrail train-loop delta (can be negative on noisy hosts)
    assert record["checkpoint_write_ms"] > 0
    assert isinstance(record["guardrail_overhead_pct"], float)
    # elastic-layer cost tracking: the heartbeat train-loop delta is
    # measured every capture (single-device smoke degrades the psum token
    # to the watchdog beat, so the delta is noise around zero — the field
    # must still be a real measurement), and one stub-gang recovery cycle
    # timed the supervisor's detect -> reap -> respawn loop
    assert isinstance(record["heartbeat_overhead_pct"], float)
    assert "gang_error" not in record, record
    assert record["gang_recovery_ms"] > 0
    # telemetry attribution fields: the aggregate-only session counted real
    # compiles; HBM is 0 on CPU (no memory_stats) but the field is present;
    # the overhead delta is measured every capture (noisy hosts -> negative)
    assert record["compile_count"] > 0
    assert record["hbm_high_water_bytes"] >= 0
    assert isinstance(record["telemetry_overhead_pct"], float)
    # serving-layer metrics: the open-loop generator drove the hardened
    # prediction service and every request was micro-batched and answered
    assert record["serve_rows_per_sec"] > 0
    assert record["serve_p50_ms"] > 0
    assert record["serve_p99_ms"] >= record["serve_p50_ms"]
    assert record["serve_batches"] > 0
    # request-path decomposition (tracing stage histograms, fed by the
    # HTTP-driven open loop): the serving gap now has named parts, and the
    # stages a real request must traverse carry real time
    for field in ("serve_parse_ms_p99", "serve_queue_ms_p99",
                  "serve_assembly_ms_p99", "serve_device_ms_p99",
                  "serve_d2h_ms_p99", "serve_serialize_ms_p99"):
        assert record[field] >= 0, field
    assert record["serve_queue_ms_p99"] > 0
    assert record["serve_device_ms_p99"] > 0
    assert record["serve_serialize_ms_p99"] > 0
    # out-of-core streaming capture: chunked ingest + a 2-blocks-of-8
    # budget train must both have run and timed; the starved budget means
    # the resident fraction sits strictly inside (0, 1) and the overlap
    # percentage is a real ratio (prefetch hits can be 0 on tiny runs)
    assert "stream_error" not in record, record
    assert record["stream_ingest_rows_per_sec"] > 0
    assert record["stream_train_rows_per_sec"] > 0
    assert 0.0 < record["hbm_resident_fraction"] < 1.0
    assert 0.0 <= record["stream_h2d_overlap_pct"] <= 100.0
    # gang-sharded streaming capture: the sketch-merged fit and the
    # sharded (tree_learner=data) streamed train both ran and timed; the
    # single-device smoke degenerates to one shard but the merge gauge is
    # a real measurement and the overlap ratio stays a real percentage
    assert "stream_sharded_error" not in record, record
    assert record["stream_sharded_rows_per_sec"] > 0
    assert record["stream_sketch_merge_ms"] >= 0
    assert record["stream_gang_shards"] >= 1
    # drift-layer cost tracking (docs/STREAMING.md "Drift and generation
    # safety"): the sketch+occupancy ingest delta is measured every capture
    # (noisy hosts -> negative is fine), and one forced bin-mapper refresh
    # plus one holdout gate evaluation both ran and timed
    assert isinstance(record["drift_check_overhead_pct"], float)
    assert record["bin_refresh_ms"] > 0
    assert record["gate_eval_ms"] > 0
    # provenance: every record carries the environment fingerprint and the
    # ledger schema version (benchdiff refuses cross-schema comparisons)
    assert record["schema_version"] == 1
    fp = record["fingerprint"]
    assert fp["git_sha"] not in ("", None)
    assert fp["jax_version"] not in ("unknown", "", None)
    assert fp["backend"] == "cpu"
    assert fp["flags"].get("JAX_PLATFORMS") == "cpu"
    # cost-model attribution: per-stage fractions of the training wall must
    # close to ~1 (the ISSUE acceptance bound benchdiff also gates on)
    attr = record["attribution"]
    assert attr["stages"], attr
    assert abs(attr["fractions_sum"] - 1.0) <= 0.05, attr
    assert all(s["wall_s"] >= 0 for s in attr["stages"].values())
    # XLA static cost analysis captured for the instrumented dispatches
    static = attr.get("static") or {}
    assert "scan" in static and "predict" in static, sorted(static)
    assert static["scan"].get("flops", 0) > 0, static["scan"]
    # the same record was appended to the ledger (atomic rewrite path)
    led = [json.loads(ln) for ln in
           ledger.read_text().splitlines() if ln.strip()]
    assert len(led) == 1
    assert led[0]["value"] == record["value"]
