"""Telemetry stack: JSONL event stream round-trip, Chrome-trace validity,
recompile watcher, HBM gauge, counter semantics, and the two contract
claims — bit-identical model output with telemetry on, and a disabled
path cheap enough for the <1% overhead budget.
"""
import json
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.checkpoint import save_checkpoint
from lightgbm_tpu.engine import train
from lightgbm_tpu.telemetry import EVENTS_FILE, TRACE_FILE, build_chrome_trace
from lightgbm_tpu.utils.timer import global_timer

BASE = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.1,
        "verbosity": -1, "min_data_in_leaf": 5}


def _data(n=400, f=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.standard_normal(n) * 0.5 > 0)
    return X, y.astype(np.float64)


def _train(params, X, y, rounds=4, **kw):
    return train(dict(BASE, **params), lgb.Dataset(X, label=y),
                 num_boost_round=rounds, **kw)


def _read_events(run_dir):
    with open(os.path.join(run_dir, EVENTS_FILE)) as fh:
        return [json.loads(line) for line in fh if line.strip()]


@pytest.fixture(autouse=True)
def _no_leaked_session():
    assert telemetry.session() is None
    yield
    # a test that leaks a session would silently disturb every later test
    assert telemetry.session() is None, "test leaked a telemetry session"


# -- end-to-end: enabled training run ------------------------------------

def test_enabled_run_writes_event_stream_and_trace(tmp_path):
    X, y = _data()
    rounds = 4
    _train({"telemetry_dir": str(tmp_path)}, X, y, rounds=rounds)

    events = _read_events(tmp_path)
    by_type = {}
    for e in events:
        assert isinstance(e["t"], (int, float)) and e["t"] >= 0
        by_type.setdefault(e["ev"], []).append(e)
    # one record per iteration plus the session/loop framing events
    assert by_type["session_start"][0]["label"] == "train"
    assert len(by_type["iteration"]) == rounds
    assert len(by_type["session_end"]) == 1
    assert by_type["train_begin"][0]["end_iteration"] == rounds
    assert len(by_type["compile"]) > 0  # the watcher saw jit cache misses
    for i, rec in enumerate(by_type["iteration"]):
        assert rec["iteration"] == i
        assert rec["wall_s"] > 0
        assert rec["num_trees"] == i + 1
        assert rec["tree_leaves"] > 0
    end = by_type["session_end"][0]
    assert end["compile_count"] == len(by_type["compile"])
    assert end["events"]["iteration"] == rounds
    assert end["n_spans"] > 0

    # the trace must be loadable and structurally valid Perfetto input
    trace = json.load(open(os.path.join(tmp_path, TRACE_FILE)))
    evs = trace["traceEvents"]
    assert isinstance(evs, list) and evs
    last_ts = 0
    depth = {}
    for ev in evs:
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], int) and ev["ts"] >= last_ts
        last_ts = ev["ts"]
        if ev["ph"] in "BE":
            key = (ev["pid"], ev["tid"])
            depth[key] = depth.get(key, 0) + (1 if ev["ph"] == "B" else -1)
            assert depth[key] >= 0, "E without matching B on track %s" % (key,)
    assert all(d == 0 for d in depth.values()), "unclosed spans: %s" % depth
    # the thread-name metadata names the timer phases feeding the tracks
    names = {ev["args"]["name"] for ev in evs
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert "tree_train" in names


def test_checkpoint_events_ride_the_stream(tmp_path):
    X, y = _data()
    run_dir = tmp_path / "tel"
    with telemetry.capture(str(run_dir)):
        bst = _train({}, X, y, rounds=2)
        save_checkpoint(bst, str(tmp_path / "snap.txt"))
    events = _read_events(run_dir)
    ck = [e for e in events if e["ev"] == "checkpoint"]
    assert len(ck) == 1 and ck[0]["iteration"] == 2
    assert ck[0]["model_only"] is False and ck[0]["sidecar_bytes"] > 0


def test_env_var_enables_telemetry(tmp_path, monkeypatch):
    monkeypatch.setenv(telemetry.ENV_VAR, str(tmp_path))
    X, y = _data(n=200)
    _train({}, X, y, rounds=2)
    assert {e["ev"] for e in _read_events(tmp_path)} >= {
        "session_start", "iteration", "session_end"}


def test_telemetry_on_is_bit_identical_to_off(tmp_path):
    X, y = _data()
    base = _train({}, X, y, rounds=4)
    with telemetry.capture(str(tmp_path)):
        instrumented = _train({}, X, y, rounds=4)
    assert base.model_to_string() == instrumented.model_to_string()
    np.testing.assert_array_equal(base.predict(X, raw_score=True),
                                  instrumented.predict(X, raw_score=True))


def test_device_learner_emits_tree_wave_events(tmp_path):
    # the factory only picks DeviceTreeLearner on accelerators; instantiate
    # directly (the test_device_learner.py pattern) to cover the
    # committed-vs-speculated event off-TPU
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset as CoreDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.treelearner.device import DeviceTreeLearner

    X, y = _data(n=800)
    cfg = Config(dict(BASE))
    ds = CoreDataset.from_matrix(np.asarray(X, np.float64), label=y,
                                 config=cfg)
    bst = GBDT(cfg, ds, create_objective(cfg.objective, cfg))
    bst.tree_learner = DeviceTreeLearner(cfg, ds)
    with telemetry.capture(str(tmp_path), watch_compiles=False) as s:
        for _ in range(3):
            bst.train_one_iter()
        deltas = s.counter_deltas()
    waves = [e for e in _read_events(tmp_path) if e["ev"] == "tree_wave"]
    assert waves, "device learner finalize emitted no tree_wave events"
    for w in waves:
        assert w["waves"] >= 1
        assert 0 < w["committed"] <= w["speculated"]
        assert w["speculated"] == w["waves"] * w["wave_width"]
    assert deltas["device_waves"] >= len(waves)
    assert deltas["wave_splits_committed"] == sum(
        w["committed"] for w in waves)


# -- watchers -------------------------------------------------------------

def test_recompile_watcher_counts_forced_shape_changes():
    @jax.jit
    def poly(v):
        return (v * 2.0).sum()

    with telemetry.capture(None, label="shapes") as s:
        before = s.recompiles.total
        for n in (8, 16, 32):  # three distinct shapes -> three cache misses
            poly(jnp.ones((n,), jnp.float32)).block_until_ready()
        fn_counts = {fn: c for fn, c in s.recompiles.per_fn.items()
                     if "poly" in fn}
        assert sum(fn_counts.values()) == 3
        assert s.recompiles.total >= before + 3
        compiles = [e for e in s.events if e["ev"] == "compile"
                    and "poly" in e["fn"]]
        assert len(compiles) == 3
        shapes = {e["shapes"] for e in compiles}
        assert len(shapes) == 3  # distinct input shapes recorded
    summary = s.close()
    assert summary["compile_count"] >= 3


def test_recompile_watcher_warns_on_churn(capsys):
    @jax.jit
    def churny(v):
        return v + 1.0

    with telemetry.capture(None, label="churn", recompile_warn=2):
        for n in (3, 5):
            churny(jnp.ones((n,), jnp.float32)).block_until_ready()
    out = capsys.readouterr()
    assert "Recompile churn: 'churny' compiled 2 times" in out.out + out.err


def test_recompile_watcher_restores_logging_state():
    pxla = logging.getLogger("jax._src.interpreters.pxla")
    prev_propagate = pxla.propagate
    prev_flag = bool(jax.config.jax_log_compiles)
    with telemetry.capture(None, label="restore"):
        assert pxla.propagate is False
        assert bool(jax.config.jax_log_compiles) is True
    assert pxla.propagate == prev_propagate
    assert bool(jax.config.jax_log_compiles) == prev_flag


def test_kernel_fn_registry_and_markers():
    telemetry.register_kernel_fn("my_custom_kernel_entry")
    assert telemetry.is_kernel_fn("my_custom_kernel_entry")
    # the pallas wrappers register at import; substring markers back them up
    assert telemetry.is_kernel_fn("_pallas_compact_call")
    assert telemetry.is_kernel_fn("some_mosaic_lowered_fn")
    assert not telemetry.is_kernel_fn("find_best_split")


def test_recompile_watcher_splits_kernel_compiles():
    pxla = logging.getLogger("jax._src.interpreters.pxla")
    with telemetry.capture(None, label="kernel") as s:
        base = telemetry.signals()
        # synthetic compile-log lines in jax's exact format: one Pallas
        # kernel wrapper, one ordinary jit function
        pxla.warning("Compiling pallas_histogram with global shapes and "
                     "types (f32[128,8],). Argument mapping: ().")
        pxla.warning("Compiling update_score with global shapes and "
                     "types (f32[128],). Argument mapping: ().")
        sig = telemetry.signals()
        assert sig["compiles"] == base["compiles"] + 2
        assert sig["kernel_compiles"] == base["kernel_compiles"] + 1
        flags = {e["fn"]: e["kernel"] for e in s.events
                 if e["ev"] == "compile"
                 and e["fn"] in ("pallas_histogram", "update_score")}
        assert flags == {"pallas_histogram": True, "update_score": False}
    summary = s.close()
    assert summary["kernel_compile_count"] == 1
    assert summary["compile_count"] >= 2


class _FakeDevice:
    def __init__(self, name, peak):
        self._name, self._peak = name, peak

    def memory_stats(self):
        return {"peak_bytes_in_use": self._peak, "bytes_in_use": 1}

    def __str__(self):
        return self._name


def test_hbm_gauge_tracks_high_water_and_counter_track(tmp_path):
    devs = [_FakeDevice("tpu:0", 1000), _FakeDevice("tpu:1", 3000)]
    with telemetry.capture(str(tmp_path), label="hbm", devices=devs,
                           watch_compiles=False) as s:
        s.hbm.sample()
        devs[0]._peak = 5000  # later sample raises the high-water
        summary_peak = telemetry.sample_hbm()
    assert summary_peak == 5000
    assert s.close()["hbm_high_water_bytes"] == 5000
    assert global_timer.counters["hbm_high_water_bytes"] == 5000
    assert "hbm_high_water_bytes" in global_timer.gauges
    trace = json.load(open(os.path.join(tmp_path, TRACE_FILE)))
    counters = [e for e in trace["traceEvents"] if e["ph"] == "C"]
    assert {e["name"] for e in counters} == {"hbm:tpu:0", "hbm:tpu:1"}
    assert max(e["args"]["bytes"] for e in counters) == 5000


# -- session mechanics ----------------------------------------------------

def test_counter_deltas_scope_accumulators_to_the_session():
    global_timer.add_count("test_accum", 10)  # pre-session noise
    with telemetry.capture(None, label="deltas",
                           watch_compiles=False) as s:
        global_timer.add_count("test_accum", 7)
        global_timer.set_count("test_gauge", 42)
        deltas = s.counter_deltas()
    assert deltas["test_accum"] == 7     # delta, not the cumulative 17
    assert deltas["test_gauge"] == 42    # gauges read absolute


def test_second_start_keeps_first_session(tmp_path):
    s1 = telemetry.start(None, label="first", watch_compiles=False)
    try:
        s2 = telemetry.start(str(tmp_path), label="second")
        assert s2 is s1
    finally:
        assert telemetry.stop()["label"] == "first"
    assert telemetry.stop() is None  # idempotent when nothing is active


def test_capture_closes_on_exception(tmp_path):
    with pytest.raises(RuntimeError):
        with telemetry.capture(str(tmp_path), watch_compiles=False):
            telemetry.emit("custom", detail="before the failure")
            raise RuntimeError("boom")
    assert telemetry.session() is None
    evs = _read_events(tmp_path)
    assert [e["ev"] for e in evs][0] == "session_start"
    assert any(e["ev"] == "custom" for e in evs)
    assert evs[-1]["ev"] == "session_end"  # close flushed despite the raise


def test_session_restores_timer_hooks():
    prev_enabled = global_timer.enabled
    prev_hook = global_timer.span_hook
    with telemetry.capture(None, watch_compiles=False):
        assert global_timer.enabled is True
        assert global_timer.span_hook is not None
    assert global_timer.enabled == prev_enabled
    assert global_timer.span_hook == prev_hook


def test_jsonl_flush_cadence(tmp_path):
    with telemetry.capture(str(tmp_path), flush_every=4,
                           watch_compiles=False):
        for i in range(6):
            telemetry.emit("tick", i=i)
        # 7 events so far (session_start + 6) -> one mid-run flush at 4
        assert len(_read_events(tmp_path)) == 4
    assert len(_read_events(tmp_path)) == 8  # close flushes the rest


def test_event_payloads_jsonable_for_device_scalars(tmp_path):
    with telemetry.capture(str(tmp_path), watch_compiles=False):
        telemetry.emit("device_vals", scalar=jnp.float32(1.5),
                       vec=jnp.arange(3), np_int=np.int64(7))
    ev = [e for e in _read_events(tmp_path) if e["ev"] == "device_vals"][0]
    assert ev["scalar"] == 1.5 and ev["vec"] == [0, 1, 2] and ev["np_int"] == 7


# -- trace builder unit ---------------------------------------------------

def test_chrome_trace_orders_ties_and_nests_containment():
    # outer contains inner; a zero-length span and an exact tie stress the
    # E-before-B ordering the Perfetto importer requires
    spans = [("outer", 0.0, 0.010), ("inner", 0.002, 0.004),
             ("inner", 0.004, 0.004), ("outer", 0.010, 0.020)]
    trace = build_chrome_trace(spans, [("hbm:dev", 0.001, 5)], label="unit")
    evs = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    assert all(b["ts"] <= a["ts"] for b, a in zip(evs, evs[1:]))
    at_10ms = [(e["ph"], e["name"]) for e in evs if e["ts"] == 10000]
    assert at_10ms.index(("E", "outer")) < at_10ms.index(("B", "outer"))
    c = [e for e in evs if e["ph"] == "C"]
    assert len(c) == 1 and c[0]["args"]["bytes"] == 5


# -- the overhead budget --------------------------------------------------

# generous stand-in for the real count of enabled()/emit() call sites hit
# per boosting iteration (engine loop + per-wave + per-chunk guards)
_CALL_SITES_PER_ITER = 2000


@pytest.mark.slow
def test_disabled_overhead_under_one_percent():
    assert not telemetry.enabled()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        telemetry.emit("hot", a=1)
    emit_cost = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        telemetry.enabled()
    guard_cost = (time.perf_counter() - t0) / n

    # per-iteration wall from a real (telemetry-off) training run
    X, y = _data(n=2000, f=20)
    _train({}, X, y, rounds=2)  # warm the jit caches out of the measurement
    rounds = 10
    t0 = time.perf_counter()
    _train({}, X, y, rounds=rounds)
    iter_wall = (time.perf_counter() - t0) / rounds

    worst_site = max(emit_cost, guard_cost)
    modeled_pct = 100.0 * _CALL_SITES_PER_ITER * worst_site / iter_wall
    assert modeled_pct < 1.0, (
        "disabled telemetry path too hot: %.3f%% modeled overhead "
        "(%.0f ns/site x %d sites vs %.1f ms/iter)" % (
            modeled_pct, worst_site * 1e9, _CALL_SITES_PER_ITER,
            iter_wall * 1e3))
