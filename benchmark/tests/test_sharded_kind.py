"""The four-chip training cell's own files: the traffic kind
`train_window_sharded` rehearsed on four CPU devices through the benchmark's
command (fixtures/sharded/: a second tiny index, whose configurations set
`--xla_force_host_platform_device_count=4` through `env`), its refusals,
its lower-precision control, the reader `busy_skew`, the two kernel-time
metrics that restore a chip's mean, and what the metrics new in PR 28 read
from a program that has none of what they read (the parent commit's: a
one-chip trace recorded before the spans and the note fields existed).
"""
import json
import os
import subprocess
import sys
import types

import pytest

import harness
import trace as tr
from conftest import BENCH, FIXTURES, REPO
from test_scope_readers import reader, train_small  # noqa: F401

SHARDED = os.path.join(FIXTURES, "sharded")
CELL = "higgs_full.train_4chip"
NEW_IN_PR_28 = ("train_4chip.allreduce_ms_per_tree",
                "train_4chip.ici_bytes_per_tree",
                "train_4chip.chip_busy_skew_pct",
                "train_4chip.idle_in_shard_inputs_pct")


def run_cell(cell: str, root: str = SHARDED, seed: int = 2 ** 31 + 28):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", root,
         "--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    return proc, (json.loads(proc.stdout.strip().splitlines()[-1])
                  if proc.returncode == 0 else None)


@pytest.fixture(scope="module")
def real_index() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the rehearsal


def test_rehearsal_on_four_cpu_devices_through_the_command():
    proc, line = run_cell("tiny.train_4chip")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True, line["compared"]
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0}
    assert set(line["metrics"]) == {"train_s_per_tree", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["compared"]) == {
        "count_mismatch", "leaf_value_gap", "split_gain_gap",
        "split_shortfall", "loss_gap"}
    assert line["compared"]["count_mismatch"]["value"] == 0.0


def test_lower_precision_control_is_not_correct():
    """The configuration's LGBM_TPU_HIST_F32=1 taken away: bfloat16
    histogram operands fail by a limit, not by each."""
    proc, line = run_cell("tiny.train_4chip_bf16")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    c = line["compared"]
    assert c["leaf_value_gap"]["value"] > 3 * c["leaf_value_gap"]["limit"]
    assert c["count_mismatch"]["value"] == 0.0
    assert c["split_shortfall"]["value"] <= c["split_shortfall"]["limit"]


@pytest.mark.parametrize("cell,why", [
    ("tiny.train_4chip_one_chip_learner",
     "grown by DeviceTreeLearner, not DeviceDataParallelTreeLearner"),
    ("tiny.train_4chip_two_device_mesh",
     "mesh spans 2 devices, the cell's chips are 4"),
])
def test_the_kind_refuses_what_is_not_the_sharded_path(cell, why):
    proc, _ = run_cell(cell)
    assert proc.returncode == harness.EXIT_NOT_DEVICE_PATH
    assert why in proc.stderr
    assert "{" not in proc.stdout


def test_the_chip_cell_refuses_the_cpu():
    proc, _ = run_cell(CELL, root=REPO)
    assert proc.returncode == harness.EXIT_NO_DEVICE
    assert "{" not in proc.stdout


def test_fewer_devices_than_the_cells_chips_is_refused(monkeypatch):
    import jax

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    args = types.SimpleNamespace(workload="tiny.train_4chip", seed=1,
                                 seconds=1, trace=0)
    ctx = harness.Context(args, SHARDED, 0.0)
    with pytest.raises(harness.Refused) as refused:
        ctx.claim_devices()
    assert refused.value.code == harness.EXIT_NO_DEVICE


class DeviceDataParallelTreeLearner:
    """A stand-in with the real learner's name and what the kind's checks
    read of it: a four-device mesh, the plane in four equal blocks."""

    def __init__(self, blocks=((28, 2048),) * 4) -> None:
        self.mesh = types.SimpleNamespace(
            devices=types.SimpleNamespace(size=4))
        self.bins_dev = types.SimpleNamespace(
            shape=(28, 8192), addressable_shards=[
                types.SimpleNamespace(device=f"d{i}", data=types.
                                      SimpleNamespace(shape=shape))
                for i, shape in enumerate(blocks)])


def _check(monkeypatch, learner, text: str, hist_rows: int = 5):
    kind = harness.load_module("kinds", "train_window_sharded", [BENCH])
    monkeypatch.setattr(kind, "lower_sharded_whole_tree", lambda _: types.
                        SimpleNamespace(as_text=lambda: text))
    ctx = types.SimpleNamespace(cell={"chips": 4}, rehearsal=False)
    return kind.check_sharded_path(ctx, learner, hist_rows,
                                   lambda t: ["pallas_histogram_x",
                                              "_pallas_compact_call"]
                                   if "tpu_custom_call" in t else [])


WHOLE = ("stablehlo.reduce_scatter stablehlo.all_gather stablehlo.all_reduce "
         "@tpu_custom_call")


def test_a_sound_sharded_program_passes_every_check(monkeypatch):
    assert _check(monkeypatch, DeviceDataParallelTreeLearner(), WHOLE) is None


@pytest.mark.parametrize("text,why", [
    (WHOLE.replace("stablehlo.reduce_scatter", ""), "holds no reduce_scatter"),
    (WHOLE.replace("stablehlo.all_gather", ""), "holds no all_gather"),
    (WHOLE.replace("stablehlo.all_reduce", ""), "holds no all_reduce"),
    (WHOLE.replace("@tpu_custom_call", ""), "did not both reach Mosaic"),
])
def test_a_lowered_program_without_a_collective_or_a_kernel_is_refused(
        monkeypatch, capsys, text, why):
    with pytest.raises(harness.Refused) as refused:
        _check(monkeypatch, DeviceDataParallelTreeLearner(), text)
    assert refused.value.code == harness.EXIT_NOT_DEVICE_PATH
    assert why in capsys.readouterr().err


def test_a_plane_not_split_four_ways_or_rows_that_did_not_move_is_refused(
        monkeypatch, capsys):
    uneven = DeviceDataParallelTreeLearner(
        blocks=((28, 4096), (28, 4096), (28, 0), (28, 0)))
    with pytest.raises(harness.Refused):
        _check(monkeypatch, uneven, WHOLE)
    assert "not split 4 ways" in capsys.readouterr().err
    with pytest.raises(harness.Refused):
        _check(monkeypatch, DeviceDataParallelTreeLearner(), WHOLE,
               hist_rows=0)
    assert "device_hist_rows did not move" in capsys.readouterr().err


# ------------------------------------------------------------- the readers


def test_busy_skew_is_the_busiest_chip_over_the_least_busy_less_one():
    skew = reader("busy_skew")
    assert skew.skew_pct({"a": 10.0, "b": 11.0, "c": 10.5, "d": 10.2}) \
        == pytest.approx(10.0)
    assert skew.skew_pct({"a": 3.0, "b": 3.0}) == 0.0
    assert skew.skew_pct({"a": 3.0}) is None       # one chip: nothing
    assert skew.skew_pct({"a": 3.0, "b": 0.0}) is None
    untraced = types.SimpleNamespace(trace_summary=lambda: None)
    assert skew.read(untraced, {}) is None


def test_busy_skew_from_a_hand_made_four_device_trace():
    """Four device planes inside one window: 8, 8, 8 and 10 us busy."""
    host = [(tr.WINDOW_SPAN, 0.0, 20_000.0)]
    devices = {f"/device:TPU:{i}": [("fusion.1", 1000.0, 8000.0)]
               for i in range(3)}
    devices["/device:TPU:3"] = [("fusion.1", 1000.0, 6000.0),
                                ("all-reduce.2", 9000.0, 4000.0)]
    summary = tr.reduce_events(devices, host)
    ctx = types.SimpleNamespace(trace_summary=lambda: summary)
    assert reader("busy_skew").read(ctx, {}) == pytest.approx(25.0)
    assert summary.busy_s == pytest.approx(8.5e-6)   # the mean over chips


@pytest.mark.parametrize("name,op", [
    ("train_4chip.hist_kernel_ms_per_tree",
     "pallas_histogram_slots_ragged.13:tpu_custom_call"),
    ("train_4chip.compact_kernel_ms_per_tree",
     "_pallas_compact_call.11:tpu_custom_call"),
])
def test_the_kernel_times_of_a_four_chip_cell_are_a_chips_mean(name, op):
    """`trace_ops` sums an operation's self time over the devices: 0.6 s
    on each of four chips over two trees is 300 ms a tree on a chip."""
    spec = harness.load_json(os.path.join(BENCH, "metrics", name + ".json"))
    one_chip = harness.load_json(os.path.join(
        BENCH, "metrics", name.replace("train_4chip.", "train.") + ".json"))
    assert spec["reader"] == one_chip["reader"] == "trace_ops"
    assert spec["pattern"] == one_chip["pattern"]
    assert spec["scale"] * 4 == one_chip["scale"]
    summary = types.SimpleNamespace(op_self_s={op: 4 * 0.6, "fusion.7": 9.0})
    ctx = types.SimpleNamespace(trace_summary=lambda: summary,
                                counts={"window_trees": 2})
    assert reader("trace_ops").read(ctx, spec) == pytest.approx(300.0)


def test_allreduce_scope_seconds_are_a_chips_mean():
    """`trace_scope` on two devices: the collective's self time under
    lgbm.allreduce, the mean over the devices, per tree."""
    ts = reader("trace_scope")
    stack = "jit(body)/shard_map/while/body/lgbm.scan/lgbm.allreduce/psum"
    devices = {
        "/device:TPU:0": [("all-reduce.24", 0.0, 2e6, stack),
                          ("fusion.1", 3e6, 1e6, "jit(body)/lgbm.scan/add")],
        "/device:TPU:1": [("all-reduce.24", 0.0, 4e6, stack)]}
    seconds = ts.scope_seconds(devices, 0.0, 1e7)
    assert seconds["lgbm.allreduce"] == pytest.approx(3e-3)
    assert seconds["lgbm.scan"] == pytest.approx(0.5e-3)


# ------------------------------------ the index, and a program from before


def test_every_metric_listed_for_the_cell_has_a_file_and_a_reader(
        real_index):
    listed = [m for m in real_index["per_layer"]
              if CELL in m.get("workloads", [])]
    assert len(listed) == 23
    assert set(NEW_IN_PR_28) <= {m["name"] for m in listed}
    for metric in listed:
        assert metric["moves"] in ("train_s_per_tree", "setup_s")
        spec = harness.load_json(os.path.join(
            BENCH, "metrics", metric["name"] + ".json"))
        assert os.path.isfile(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
    # the two kernel times that sum over devices are not the cell's
    names = {m["name"] for m in listed}
    assert "train.hist_kernel_ms_per_tree" not in names
    assert "train.compact_kernel_ms_per_tree" not in names
    cell = next(w for w in real_index["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and len(cell["why"]) <= 200
    config = next(c for c in real_index["configs"]
                  if c["name"] == cell["config"])
    assert config["reduced"] == ["num_trees"]
    on_disk = harness.load_json(os.path.join(REPO, config["file"]))
    one_chip = harness.load_json(os.path.join(
        BENCH, "configs", "higgs_binary.json"))
    assert on_disk["rows"] == on_disk["published"]["rows"] == 10_500_000
    assert on_disk["features"] == one_chip["features"]
    assert on_disk["env"] == one_chip["env"]
    assert on_disk["data_seed"] == one_chip["data_seed"]
    assert on_disk["params"] == dict(one_chip["params"], tree_learner="data",
                                     num_machines=4)


def test_metrics_new_in_pr_28_read_nothing_from_the_parents_program(
        train_small, monkeypatch):
    """The driver runs the new cell on the parent commit with this PR's
    benchmark files laid over it: a program with no `shard_inputs` span, no
    `ici_bytes` in its notes, and (in this one-chip recording) no
    collective. Each new reader returns nothing and raises nothing, and
    the line leaves the metric out; the accepted ones still read."""
    from lightgbm_tpu import tracing

    notes = [{"kind": "tree_wave", "t": 1.0, "waves": 19, "wave_k": 21}]
    fake = types.SimpleNamespace(dropped=0, snapshot=lambda: notes)
    monkeypatch.setattr(tracing, "recorder", lambda: fake)
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: train_small)
    args = types.SimpleNamespace(workload="tiny.train_4chip", seed=1,
                                 seconds=1, trace=1)
    ctx = harness.Context(args, SHARDED, 0.0)
    ctx.window_open_at = 0.0
    ctx.counts.update(window_trees=3, window_s=10.0)
    ctx.e2e["train_s_per_tree"] = 1.0
    got = harness.per_layer_metrics(ctx)
    assert not set(NEW_IN_PR_28) & set(got)
    assert got["train.waves_per_tree"]["value"] == pytest.approx(19 / 3)
    assert got["train.route_ms_per_tree"]["value"] > 0
    assert got["train_4chip.hist_kernel_ms_per_tree"]["value"] > 0
