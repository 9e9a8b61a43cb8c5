"""The two metrics of the dense predict program, read through the metric
files the benchmark ships and the readers it already had:
`predict_batch.path_match_ns_per_row` (trace_scope) on a trace recorded on
the chip with `lgbm.path_match` in it (recorded/predict_dense_small.xplane.pb.gz:
four `Booster.predict` calls of 4,096 x 28 rows through 20 trees of 31
leaves inside the window span; PERF.md says how it was taken), and
`predict_batch.dense_call_share` (flight_notes) on hand-made notes."""
import gzip
import os
import shutil
import types

import pytest

import harness
import trace as tr
import xplane
from conftest import BENCH

HERE = os.path.dirname(os.path.abspath(__file__))
DENSE_SMALL_GZ = os.path.join(HERE, "recorded",
                              "predict_dense_small.xplane.pb.gz")
GATHER_SMALL = os.path.join(HERE, "recorded", "predict_small.xplane.pb")
ROWS = 4 * 4096


def spec_of(metric: str) -> dict:
    return harness.load_json(os.path.join(BENCH, "metrics", metric + ".json"))


def reader_of(spec: dict):
    return harness.load_module("readers", spec["reader"], [BENCH])


@pytest.fixture(scope="module")
def dense_small(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("recorded") / "predict_dense_small.xplane.pb"
    with gzip.open(DENSE_SMALL_GZ, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def traced_ctx(path: str, **counts):
    raw = xplane.load(path)
    summary = tr.reduce(path)
    ctx = types.SimpleNamespace(counts=dict(counts), _xplane=raw)
    ctx.trace_summary = lambda: summary
    return ctx


def test_path_match_reads_the_recorded_dense_trace(dense_small):
    spec = spec_of("predict_batch.path_match_ns_per_row")
    ctx = traced_ctx(dense_small, window_rows=ROWS)
    value = reader_of(spec).read(ctx, spec)
    ts = harness.load_module("readers", "trace_scope", [BENCH])
    lo, hi = tr.window_of(ctx._xplane["host"])
    sec = ts.scope_seconds(ctx._xplane["devices"], lo, hi)
    assert sec["lgbm.path_match"] > 0
    assert value == pytest.approx(1e9 * sec["lgbm.path_match"] / ROWS)
    # the dense program: no node table is gathered, the scopes partition busy
    assert "lgbm.node_gather" not in sec
    assert sec["lgbm.feature_gather"] > 0
    assert sum(sec.values()) == pytest.approx(ctx.trace_summary().busy_s,
                                              rel=0.01)


def test_node_gather_reads_nothing_from_the_dense_trace(dense_small):
    """The accepted metric of the traversal's node tables falls silent
    where the dense program ran; it is left out of the line, not 0."""
    spec = spec_of("predict_batch.node_gather_ns_per_row")
    ctx = traced_ctx(dense_small, window_rows=ROWS)
    assert reader_of(spec).read(ctx, spec) is None
    spec = spec_of("predict_batch.feature_gather_ns_per_row")
    assert reader_of(spec).read(ctx, spec) > 0


def test_path_match_reads_nothing_from_a_traversal_trace():
    """The parent's program (a trace from before the scopes, and no
    path_match in it either way): the metric stays out of the line."""
    spec = spec_of("predict_batch.path_match_ns_per_row")
    ctx = traced_ctx(GATHER_SMALL, window_rows=4096)
    assert reader_of(spec).read(ctx, spec) is None


NOTES = [{"kind": "predict_traverse", "t": 90.0, "dense": 1, "rows": 8,
          "trees": 4},                                       # the warm-up call
         {"kind": "predict_traverse", "t": 101.0, "dense": 1, "rows": 8,
          "trees": 4},
         {"kind": "compile", "t": 102.0, "cache_hit": True, "seconds": 1.0},
         {"kind": "predict_traverse", "t": 103.0, "dense": 0, "rows": 8,
          "trees": 4},
         {"kind": "predict_traverse", "t": 104.0, "dense": 1, "rows": 8,
          "trees": 4},
         {"kind": "predict_traverse", "t": 105.0, "dense": 1, "rows": 8,
          "trees": 4},
         {"kind": "predict_traverse", "t": 111.0, "dense": 0, "rows": 8,
          "trees": 4}]                                       # after the close


@pytest.mark.parametrize("notes, want", [
    (NOTES, 0.75),
    ([n for n in NOTES if n.get("dense") != 0], 1.0),
    ([n for n in NOTES if n["kind"] != "predict_traverse"], None)])
def test_dense_call_share_of_the_windows_calls(monkeypatch, notes, want):
    from lightgbm_tpu import tracing

    fake = types.SimpleNamespace(dropped=0, snapshot=lambda: list(notes))
    monkeypatch.setattr(tracing, "recorder", lambda: fake)
    spec = spec_of("predict_batch.dense_call_share")
    calls = sum(1 for n in notes if n["kind"] == "predict_traverse"
                and 100.0 <= n["t"] < 110.0)
    ctx = types.SimpleNamespace(window_open_at=100.0,
                                counts={"window_s": 10.0,
                                        "window_calls": calls})
    got = reader_of(spec).read(ctx, spec)
    assert got == (pytest.approx(want) if want is not None else None)
