"""The readers that put device time under the program's own names: the
`.xplane.pb` walked on the wire (xplane.py) against jax's ProfileData on
the recorded traces, and `trace_scope`, `trace_idle_in_span` and
`flight_notes` on hand-made events and notes, then through the harness on
a training trace recorded on the chip with the scopes in
(recorded/train_small.xplane.pb.gz: PERF.md says how it was taken)."""
import gzip
import json
import os
import shutil
import types

import pytest

import harness
import trace as tr
import xplane
from conftest import BENCH, FIXTURES

HERE = os.path.dirname(os.path.abspath(__file__))
PREDICT_SMALL = os.path.join(HERE, "recorded", "predict_small.xplane.pb")
TRAIN_SMALL_GZ = os.path.join(HERE, "recorded", "train_small.xplane.pb.gz")


def reader(name: str):
    return harness.load_module("readers", name, [BENCH])


@pytest.fixture(scope="module")
def train_small(tmp_path_factory) -> str:
    """The training trace as the profiler wrote it (it is kept gzipped:
    4.3 MB, most of it the programs' HLO in the `/host:metadata` plane)."""
    path = tmp_path_factory.mktemp("recorded") / "train_small.xplane.pb"
    with gzip.open(TRAIN_SMALL_GZ, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


# ----------------------------------------------------------- the wire format


def varint(value: int) -> bytes:
    out = bytearray()
    while True:
        byte, value = value & 0x7F, value >> 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(key: int, message: bytes) -> bytes:
    return field(1, key) + field(2, message)


def plane(name: str, line_name: str, stamp_ns: int, events: list,
          metadata: dict) -> bytes:
    """events: [(metadata id, offset_ps, duration_ps)];
    metadata: {id: (event name, name stack or None)}"""
    line = field(2, line_name) + field(3, stamp_ns) + b"".join(
        field(4, field(1, i) + field(2, off) + field(3, dur))
        for i, off, dur in events)
    out = field(2, name) + field(3, line)
    for ident, (event_name, stack) in metadata.items():
        meta = field(1, ident) + field(2, event_name)
        meta += field(5, field(1, 7) + field(3, 99))          # another stat
        if stack is not None:
            meta += field(5, field(1, 9) + field(5, stack))   # tf_op
        out += field(4, entry(ident, meta))
    out += field(5, entry(9, field(1, 9) + field(2, xplane.NAME_STACK_STAT)))
    out += field(5, entry(7, field(1, 7) + field(2, "flops")))
    return out


def test_wire_walk_of_a_hand_made_file(tmp_path):
    device = plane("/device:TPU:0", tr.OPS_LINE, 1000, [
        (1, 0, 5_000_000), (2, 1_000_000, 2_000_000)], {
        1: ("%while.3 = (s32[]) while(...)", None),
        2: ("%fusion.9 = f32[8]{0} fusion(f32[8] %p), kind=kLoop, "
            "calls=%fused_computation.4",
            "jit(f)/while/body/lgbm.route/select_n:")})
    other = plane("/device:TPU:0 ignored", "Async XLA Ops", 0, [(1, 0, 9)],
                  {1: ("x", None)})
    host = plane("/host:CPU", "thread", 500, [(1, 0, 9_000_000),
                                              (2, 0, 0)],
                 {1: (tr.WINDOW_SPAN, None), 2: ("instant", None)})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, device) + field(1, host) + field(1, other)
                     + field(3, "hostname"))
    raw = xplane.load(str(path))
    assert raw["host"] == [(tr.WINDOW_SPAN, 500.0, 9000.0)]
    assert raw["devices"]["/device:TPU:0"] == [
        ("while.3", 1000.0, 5000.0, ""),
        ("fusion.9:fused_computation.4", 2000.0, 2000.0,
         "jit(f)/while/body/lgbm.route/select_n:")]
    assert list(raw["devices"]) == ["/device:TPU:0"]  # no other ops line


@pytest.mark.parametrize("which", ["predict_small", "train_small"])
def test_wire_walk_agrees_with_profile_data(which, train_small):
    """Same events, same names, times within the nanosecond ProfileData
    truncates to; and the recorded file carries name stacks."""
    path = PREDICT_SMALL if which == "predict_small" else train_small
    want, got = tr.load(path), xplane.load(path)
    assert sorted(want["devices"]) == sorted(got["devices"])
    for name, events in want["devices"].items():
        assert len(events) == len(got["devices"][name])
        for a, b in zip(events, got["devices"][name]):
            assert a[0] == b[0]
            assert abs(a[1] - b[1]) < 1.0 and abs(a[2] - b[2]) < 1.0
    assert len(want["host"]) == len(got["host"])
    for a, b in zip(sorted(want["host"]), sorted(got["host"])):
        assert a[0] == b[0] and abs(a[1] - b[1]) < 1.0
    stacks = [e[3] for events in got["devices"].values() for e in events]
    assert sum(1 for s in stacks if s.startswith("jit(")) > len(stacks) // 2


# ------------------------------------------------------------- trace_scope

# ns; the window is [1000, 11000). The while holds the three below it.
HOST = [(tr.WINDOW_SPAN, 1000.0, 10000.0),
        ("iteration", 1000.0, 9000.0), ("tree_replay", 4500.0, 2000.0)]
STACK = "jit(grow)/while/body/"
DEVICE = [
    ("while.1", 2000.0, 2000.0, ""),
    ("fusion.1", 2100.0, 500.0, STACK + "lgbm.route/select_n:"),
    ("kernel.2", 2700.0, 1000.0,
     STACK + "lgbm.hist/jit(k)/lgbm.allreduce/psum:"),       # innermost
    ("fusion.3", 3750.0, 200.0, ""),                          # no metadata
    ("fusion.1", 6000.0, 1000.0, STACK + "lgbm.route/select_n:"),
    ("copy.4", 7000.0, 500.0, "jit(grow)/copy:"),             # no scope
    ("fusion.5", 8000.0, 250.0, STACK + "vmap(lgbm.scan)/gather:"),
    ("early.6", 0.0, 500.0, STACK + "lgbm.route/x:")]         # before it


def test_scope_of_takes_the_innermost_of_the_programs_components():
    ts = reader("trace_scope")
    assert ts.scope_of("jit(f)/lgbm.hist/jit(k)/lgbm.allreduce/psum:") \
        == "lgbm.allreduce"
    assert ts.scope_of("jit(f)/while/body/vmap(lgbm.scan)/gather:") \
        == "lgbm.scan"
    assert ts.scope_of("jit(f)/while/body/closed_call/gather:") \
        == ts.UNSCOPED
    assert ts.scope_of("") == ts.UNSCOPED


def test_scope_seconds_add_up_to_busy_and_unscoped_is_never_dropped():
    ts = reader("trace_scope")
    sec = ts.scope_seconds({"/device:TPU:0": DEVICE}, 1000.0, 11000.0)
    assert sec["lgbm.route"] == pytest.approx(1500e-9)
    assert sec["lgbm.allreduce"] == pytest.approx(1000e-9)
    assert sec["lgbm.scan"] == pytest.approx(250e-9)
    assert "lgbm.hist" not in sec
    # the while's own 300 ns, the fusion without metadata, the copy
    assert sec[ts.UNSCOPED] == pytest.approx((300 + 200 + 500) * 1e-9)
    summary = tr.reduce_events(
        {"/device:TPU:0": [e[:3] for e in DEVICE]}, HOST)
    assert sum(sec.values()) == pytest.approx(summary.busy_s)
    assert ts.unscoped_ops({"/device:TPU:0": DEVICE}, 1000.0, 11000.0) == [
        ("copy.4", pytest.approx(500e-9)),
        ("while.1", pytest.approx(300e-9)),
        ("fusion.3", pytest.approx(200e-9))]


def test_scope_seconds_are_the_mean_over_the_devices():
    ts = reader("trace_scope")
    sec = ts.scope_seconds({"/device:TPU:0": DEVICE, "/device:TPU:1": [
        ("fusion.1", 2000.0, 500.0, STACK + "lgbm.route/select_n:")]},
        1000.0, 11000.0)
    assert sec["lgbm.route"] == pytest.approx((1500e-9 + 500e-9) / 2)


def fake_ctx(devices, host=HOST, **counts):
    summary = tr.reduce_events(
        {k: [e[:3] for e in v] for k, v in devices.items()}, host)
    ctx = types.SimpleNamespace(
        counts=dict(counts), window_open_at=100.0,
        _xplane={"devices": devices, "host": host})
    ctx.trace_summary = lambda: summary
    return ctx


def test_trace_scope_reads_a_pattern_per_a_count_and_the_unscoped_share():
    ts = reader("trace_scope")
    ctx = fake_ctx({"/device:TPU:0": DEVICE}, window_trees=2)
    spec = {"scope": r"^lgbm\.(route|scan)$", "per": "window_trees",
            "scale": 1e9}
    assert ts.read(ctx, spec) == pytest.approx((1500 + 250) / 2)
    assert ts.read(ctx, {"unscoped": True}) == pytest.approx(
        100.0 * 1000 / 3750)
    assert ts.read(ctx, {"scope": r"^lgbm\.finish$"}) is None
    assert ts.read(ctx, {"scope": r"^lgbm\.route$", "per": "absent"}) is None


def test_trace_scope_reads_nothing_from_a_program_without_scopes():
    """The parent commit's trace: the share would say 100 %, which is no
    reading of this metric."""
    ts = reader("trace_scope")
    bare = [(n, s, d, "jit(grow)/while/body/select_n:" if st else "")
            for n, s, d, st in DEVICE]
    ctx = fake_ctx({"/device:TPU:0": bare}, window_trees=2)
    assert ts.read(ctx, {"unscoped": True}) is None
    assert ts.read(ctx, {"scope": r"^lgbm\.route$"}) is None
    untraced = types.SimpleNamespace(trace_summary=lambda: None, counts={})
    assert ts.read(untraced, {"unscoped": True}) is None


# ------------------------------------------------------ trace_idle_in_span


def test_idle_inside_a_span_counts_every_gap_whole():
    """Busy is [2000,4000) [6000,7500) [8000,8250); tree_replay is
    [4500,6500): idle inside it is [4500,6000)."""
    ti = reader("trace_idle_in_span")
    ctx = fake_ctx({"/device:TPU:0": DEVICE})
    assert ti.read(ctx, {"span": "tree_replay"}) == pytest.approx(
        100.0 * 1500 / 10000)
    # iteration [1000,10000): idle 1000 + 2000 + 500 + 1750
    assert ti.read(ctx, {"span": "iteration"}) == pytest.approx(
        100.0 * 5250 / 10000)
    assert ti.read(ctx, {"span": "no_such_span"}) is None


# ------------------------------------------------------------ flight_notes

NOTES = [{"kind": "compile", "t": 40.0, "cache_hit": False, "seconds": 2.5},
         {"kind": "compile", "t": 90.0, "cache_hit": False, "seconds": 0.5},
         {"kind": "tree_wave", "t": 95.0, "waves": 7},      # warm-up tree
         {"kind": "tree_wave", "t": 101.0, "waves": 19},
         {"kind": "tree_wave", "t": 105.0, "waves": 21},
         {"kind": "compile", "t": 106.0, "cache_hit": False, "seconds": 9.0},
         {"kind": "tree_wave", "t": 111.0, "waves": 5}]     # after the close


@pytest.fixture
def recorder(monkeypatch):
    from lightgbm_tpu import tracing

    fake = types.SimpleNamespace(dropped=0, snapshot=lambda: list(NOTES))
    monkeypatch.setattr(tracing, "recorder", lambda: fake)
    return fake


def test_flight_notes_inside_the_window_and_before_it(recorder):
    fn = reader("flight_notes")
    ctx = types.SimpleNamespace(window_open_at=100.0,
                                counts={"window_s": 10.0, "window_trees": 2})
    waves = {"kind": "tree_wave", "field": "waves", "per": "window_trees"}
    assert fn.read(ctx, waves) == pytest.approx(20.0)
    setup = {"kind": "compile", "field": "seconds", "when": "setup"}
    assert fn.read(ctx, setup) == pytest.approx(3.0)
    assert fn.read(ctx, {"kind": "compile", "field": "seconds"}) \
        == pytest.approx(9.0)
    assert fn.read(ctx, {"kind": "no_such", "field": "x"}) is None
    assert fn.read(ctx, {"kind": "tree_wave", "field": "no_field"}) is None


def test_flight_notes_reads_nothing_from_a_ring_that_dropped(recorder):
    fn = reader("flight_notes")
    ctx = types.SimpleNamespace(window_open_at=100.0,
                                counts={"window_s": 10.0})
    recorder.dropped = 3
    assert fn.read(ctx, {"kind": "tree_wave", "field": "waves"}) is None
    recorder.dropped = 0
    ctx.window_open_at = None
    assert fn.read(ctx, {"kind": "tree_wave", "field": "waves"}) is None


# ------------------------------------------- through the harness, recorded


@pytest.fixture
def scoped_root(tmp_path):
    """The fixture benchmark (an existing file, left alone) copied, with
    three scope metrics and their files beside its own."""
    root = tmp_path / "root"
    shutil.copytree(FIXTURES, root)
    with open(root / "BENCHMARK.json") as f:
        index = json.load(f)
    for name, spec in {
            "train.route_ms_per_tree": {
                "reader": "trace_scope", "scope": r"^lgbm\.route$",
                "per": "window_trees", "scale": 1000.0},
            "train.unscoped_device_pct": {
                "reader": "trace_scope", "unscoped": True},
            "train.idle_in_tree_replay_pct": {
                "reader": "trace_idle_in_span", "span": "tree_replay"}}.items():
        index["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "tree learner",
            "moves": "train_s_per_tree", "workloads": ["tiny.train"]})
        with open(root / "bench" / "metrics" / (name + ".json"), "w") as f:
            json.dump(spec, f)
    index["per_layer"] = [m for m in index["per_layer"]
                          if m["name"].startswith("train.")
                          and "tiny.train" in m["workloads"]][-3:]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(index, f)
    return str(root)


def test_per_layer_metrics_from_the_recorded_training_trace(
        scoped_root, train_small, monkeypatch):
    """`harness.per_layer_metrics` over a Context of a traced run whose
    trace file is the one recorded on the chip: three trees in the window,
    every phase of the whole-tree program under its scope."""
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: train_small)
    args = types.SimpleNamespace(workload="tiny.train", seed=1, seconds=1,
                                 trace=1)
    ctx = harness.Context(args, scoped_root, 0.0)
    ctx.counts["window_trees"] = 3
    ctx.e2e["train_s_per_tree"] = 1.0
    got = harness.per_layer_metrics(ctx)
    assert set(got) == {"train.route_ms_per_tree",
                        "train.unscoped_device_pct",
                        "train.idle_in_tree_replay_pct"}
    assert got["train.route_ms_per_tree"]["value"] > 0
    assert 0 < got["train.unscoped_device_pct"]["value"] < 100
    assert 0 <= got["train.idle_in_tree_replay_pct"]["value"] < 100

    ts = reader("trace_scope")
    raw = xplane.load(train_small)
    lo, hi = tr.window_of(raw["host"])
    sec = ts.scope_seconds(raw["devices"], lo, hi)
    for scope in ("tree_setup", "select", "route", "compact", "hist", "scan",
                  "replay", "commit", "finish", "gradients", "update_score"):
        assert sec.get("lgbm." + scope, 0.0) > 0.0, scope
    assert sum(sec.values()) == pytest.approx(ctx.trace_summary().busy_s,
                                              rel=0.01)
    names = {e[0] for events in raw["devices"].values() for e in events}
    assert any(n.startswith("pallas_histogram_slots_ragged") for n in names)
    assert any(n.startswith("_pallas_compact_call") for n in names)
    spans = {e[0] for e in raw["host"]}
    assert {"iteration", "tree_train", "tree_replay", "update_score"} <= spans
