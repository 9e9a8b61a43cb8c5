"""What PR 36 added: `ops_per_note` (a kernel's device time per unit of the
work the program counted for it) on hand-made events and notes and on the
training trace recorded on the chip, and every new metric file listed in
`BENCHMARK.json` beside a reader that exists. By hand, as the rest of
benchmark/tests."""
import gzip
import json
import os
import shutil
import types

import pytest

import harness
import trace as tr
from conftest import BENCH, FIXTURES, REPO

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN_SMALL_GZ = os.path.join(HERE, "recorded", "train_small.xplane.pb.gz")
TRAINING_CELLS = ["higgs_binary.train", "higgs_full.train_4chip",
                  "higgs_binary_quant.train", "mslr_lambdarank.train"]
# metric -> (reader, the cells that report it)
NEW_METRICS = {
    "train.compact_pairs_per_tree": ("flight_notes", TRAINING_CELLS),
    "train.compact_permute_pair_share": ("notes_ratio", TRAINING_CELLS),
    "train.compact_grid_fill_share": ("notes_ratio", TRAINING_CELLS),
    "train.compact_kernel_us_per_pair": ("ops_per_note", TRAINING_CELLS),
    "train.hist_tile_visits_per_tree": ("flight_notes", TRAINING_CELLS),
    "train.hist_grid_fill_share": ("notes_ratio", TRAINING_CELLS),
    "train.hist_kernel_us_per_visit": ("ops_per_note", TRAINING_CELLS),
    "train.score_update_ms_per_tree": ("trace_scope", TRAINING_CELLS),
    "train.idle_in_gradients_pct": ("trace_idle_in_span", TRAINING_CELLS),
    "train.idle_in_update_score_pct": ("trace_idle_in_span", TRAINING_CELLS),
    "train_4chip.idle_in_gather_leaf_ids_pct": (
        "trace_idle_in_span", ["higgs_full.train_4chip"]),
    "predict_batch.idle_in_upload_pct": (
        "trace_idle_in_span", ["forest500x255.predict_batch"]),
}
STACK = "jit(grow_tree_on_device)/while/body/"
# (name, start_ns, duration_ns): two compaction calls, a root and a wave
# histogram call, something else; one device
DEVICE = [("_pallas_compact_call.7", 2000.0, 3000.0),
          ("pallas_histogram_slots_ragged.12", 5000.0, 500.0),
          ("pallas_histogram_slots_ragged.13", 6000.0, 1500.0),
          ("_pallas_compact_call.7", 8000.0, 1000.0),
          ("fusion.3", 9500.0, 250.0)]
HOST = [(tr.WINDOW_SPAN, 1000.0, 10000.0)]
NOTES = [{"kind": "tree_wave", "t": 95.0, "compact_pairs": 999,
          "hist_tile_visits": 999},                      # a warm-up tree
         {"kind": "tree_wave", "t": 101.0, "compact_pairs": 30,
          "hist_tile_visits": 12, "compact_grid_steps": 100},
         {"kind": "tree_wave", "t": 105.0, "compact_pairs": 10,
          "hist_tile_visits": 8, "compact_grid_steps": 100},
         {"kind": "tree_wave", "t": 111.0, "compact_pairs": 999}]  # after


def reader(name: str):
    return harness.load_module("readers", name, [BENCH])


@pytest.fixture
def recorder(monkeypatch):
    from lightgbm_tpu import tracing

    fake = types.SimpleNamespace(dropped=0, snapshot=lambda: list(NOTES))
    monkeypatch.setattr(tracing, "recorder", lambda: fake)
    return fake


def fake_ctx(devices):
    summary = tr.reduce_events(devices, HOST)
    ctx = types.SimpleNamespace(counts={"window_s": 10.0, "window_trees": 2},
                                window_open_at=100.0, roots=[BENCH])
    ctx.trace_summary = lambda: summary
    return ctx


COMPACT = {"pattern": "^_pallas_compact_call", "kind": "tree_wave",
           "field": "compact_pairs", "scale": 1e9}
HIST = {"pattern": "^pallas_histogram", "kind": "tree_wave",
        "field": "hist_tile_visits", "scale": 1e9}


def test_ops_per_note_is_the_kernels_time_over_the_windows_count(recorder):
    opn = reader("ops_per_note")
    ctx = fake_ctx({"/device:TPU:0": DEVICE})
    assert opn.read(ctx, COMPACT) == pytest.approx((3000 + 1000) / 40)
    assert opn.read(ctx, HIST) == pytest.approx((500 + 1500) / 20)
    # what the two readers it is made of say per tree
    per_tree = reader("trace_ops").read(ctx, {
        "pattern": COMPACT["pattern"], "per": "window_trees", "scale": 1e9})
    pairs = reader("flight_notes").read(ctx, {
        "kind": "tree_wave", "field": "compact_pairs", "per": "window_trees"})
    assert opn.read(ctx, COMPACT) == pytest.approx(per_tree / pairs)


def test_ops_per_note_sums_both_sides_over_the_chips(recorder):
    """`trace_ops` sums the devices' events and the program `psum`s its
    counts, so four chips of equal work read one chip's time a unit."""
    opn = reader("ops_per_note")
    one = opn.read(fake_ctx({"/device:TPU:0": DEVICE}), COMPACT)
    notes = [dict(n, compact_pairs=4 * n["compact_pairs"]) for n in NOTES]
    recorder.snapshot = lambda: notes
    four = opn.read(fake_ctx({f"/device:TPU:{i}": DEVICE for i in range(4)}),
                    COMPACT)
    assert four == pytest.approx(one)


def test_ops_per_note_reads_nothing_where_either_side_is_missing(recorder):
    opn = reader("ops_per_note")
    ctx = fake_ctx({"/device:TPU:0": DEVICE})
    assert opn.read(ctx, dict(COMPACT, pattern="^no_such_kernel")) is None
    # the parent commit's notes: no such field
    assert opn.read(ctx, dict(COMPACT, field="compact_copy_pairs")) is None
    recorder.snapshot = lambda: [dict(n, compact_pairs=0) for n in NOTES]
    assert opn.read(ctx, COMPACT) is None
    recorder.snapshot = lambda: list(NOTES)
    recorder.dropped = 1
    assert opn.read(ctx, COMPACT) is None
    recorder.dropped = 0
    untraced = types.SimpleNamespace(
        trace_summary=lambda: None, counts={"window_s": 10.0},
        window_open_at=100.0, roots=[BENCH])
    assert opn.read(untraced, COMPACT) is None
    assert opn.per_unit(None, 3.0) is None and opn.per_unit(2.0, 0) is None


def test_notes_ratio_reads_the_grid_fill_from_the_same_notes(recorder):
    ratio = reader("notes_ratio")
    ctx = fake_ctx({"/device:TPU:0": DEVICE})
    assert ratio.read(ctx, {"kind": "tree_wave", "field": "compact_pairs",
                            "over": "compact_grid_steps"}) \
        == pytest.approx(40 / 200)


# ------------------------------------------- through the harness, recorded


@pytest.fixture
def train_small(tmp_path) -> str:
    path = tmp_path / "train_small.xplane.pb"
    with gzip.open(TRAIN_SMALL_GZ, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture
def work_root(tmp_path):
    """The fixture benchmark copied, its per-layer list replaced by this
    PR's two time-per-unit metrics and the update's scope metric, their
    files taken from benchmark/metrics as committed."""
    root = tmp_path / "root"
    shutil.copytree(FIXTURES, root)
    with open(root / "BENCHMARK.json") as f:
        index = json.load(f)
    index["per_layer"] = []
    for name in ("train.compact_kernel_us_per_pair",
                 "train.hist_kernel_us_per_visit",
                 "train.score_update_ms_per_tree"):
        index["per_layer"].append({
            "name": name, "unit": "x", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "train_s_per_tree", "workloads": ["tiny.train"]})
        shutil.copy(os.path.join(BENCH, "metrics", name + ".json"),
                    root / "bench" / "metrics" / (name + ".json"))
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(index, f)
    return str(root)


def test_time_per_unit_from_the_recorded_training_trace(
        work_root, train_small, recorder, monkeypatch):
    """The trace recorded on the chip (three trees in the window) beside
    hand-made notes: the kernels' events are found by the committed
    patterns, and the recorded program's async update is under
    lgbm.update_score."""
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: train_small)
    args = types.SimpleNamespace(workload="tiny.train", seed=1, seconds=1,
                                 trace=1)
    ctx = harness.Context(args, work_root, 0.0)
    ctx.counts.update(window_trees=3, window_s=10.0)
    ctx.window_open_at = 100.0
    ctx.e2e["train_s_per_tree"] = 1.0
    got = harness.per_layer_metrics(ctx)
    assert set(got) == {"train.compact_kernel_us_per_pair",
                        "train.hist_kernel_us_per_visit",
                        "train.score_update_ms_per_tree"}
    ops = ctx.trace_summary().op_self_s
    compact_s = sum(s for n, s in ops.items()
                    if n.startswith("_pallas_compact_call"))
    hist_s = sum(s for n, s in ops.items()
                 if n.startswith("pallas_histogram"))
    assert compact_s > 0 and hist_s > 0
    assert got["train.compact_kernel_us_per_pair"]["value"] \
        == pytest.approx(1e6 * compact_s / 40)
    assert got["train.hist_kernel_us_per_visit"]["value"] \
        == pytest.approx(1e6 * hist_s / 20)
    assert got["train.score_update_ms_per_tree"]["value"] > 0
    # a program whose notes lack the fields (the parent's): the two ratios
    # are left out of the line, nothing raises
    recorder.snapshot = lambda: [{"kind": "tree_wave", "t": 101.0,
                                  "waves": 19}]
    assert set(harness.per_layer_metrics(ctx)) \
        == {"train.score_update_ms_per_tree"}


# ------------------------------------------------------------- the listing


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_is_listed_with_its_file_and_its_reader(name):
    want_reader, cells = NEW_METRICS[name]
    index = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry, = [m for m in index["per_layer"] if m["name"] == name]
    assert entry["workloads"] == cells
    moves = ("predict_rows_per_s" if name.startswith("predict_batch.")
             else "train_s_per_tree")
    assert entry["moves"] == moves
    e2e, = [m for m in index["end_to_end"] if m["name"] == moves]
    assert set(cells) <= set(e2e["workloads"])
    spec = harness.load_json(os.path.join(BENCH, "metrics", name + ".json"))
    assert spec["reader"] == want_reader and spec["what"]
    assert callable(reader(want_reader).read)
    # appended behind what the benchmark had (PR 34's last entry), under a
    # layer it already named; a later PR's appended entries change neither
    names = [m["name"] for m in index["per_layer"]]
    had = names.index("train_rank.idle_in_eval_pct")
    assert names.index(name) > had
    assert entry["layer"] in {m["layer"] for m in index["per_layer"][:had + 1]}
