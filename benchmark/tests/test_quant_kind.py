"""The quantized training cell's own files: the traffic kind
`train_window_quant` rehearsed on the CPU through the benchmark's command
(fixtures/quant/: a third tiny index), its plain reference on both values of
each quantization parameter, its refusals, the chip's controls rehearsed on
the interpreted kernels, the three metrics new in PR 32 on a trace recorded
on the chip (recorded/train_quant_small.xplane.pb.gz:
65,536 x 28 rows, 31 leaves, `use_quantized_grad` with leaf renewal, three
trees inside the window span; `_scratch/record_quant_trace.py` of PR 32,
not kept), and what those metrics read from a program that has none of what
they read (the parent commit's: the trace recorded before PR 32).
"""
import gzip
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import harness
import trace as tr
import xplane
from conftest import BENCH, FIXTURES, HERE, REPO
from test_scope_readers import reader, train_small  # noqa: F401

QUANT = os.path.join(FIXTURES, "quant")
CELL = "higgs_binary_quant.train"
NEW_IN_PR_32 = ("train_quant.quantize_ms_per_tree",
                "train_quant.idle_in_quantize_pct",
                "train_quant.int_hist_tree_share")
READINGS = {"count_mismatch", "scale_gap", "quant_outside", "rounding_z",
            "nearest_miss", "leaf_value_gap", "split_gain_gap",
            "split_shortfall", "loss_gap"}


def run_cell(cell: str, root: str = QUANT, seed: int = 2 ** 31 + 32):
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


@pytest.fixture(scope="module")
def train_quant_small(tmp_path_factory) -> str:
    path = tmp_path_factory.mktemp("recorded") / "train_quant_small.xplane.pb"
    with gzip.open(os.path.join(HERE, "recorded",
                                "train_quant_small.xplane.pb.gz"),
                   "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


# ------------------------------------------------------------ the rehearsal


@pytest.mark.parametrize("cell,stochastic", [
    ("tiny.train_quant", True), ("tiny.train_quant_renew16", False)])
def test_rehearsal_through_the_command(cell, stochastic):
    proc, line = run_cell(cell)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True, line["compared"]
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": 0}
    assert set(line["metrics"]) == {"train_s_per_tree", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["compared"]) == READINGS
    c = {k: v["value"] for k, v in line["compared"].items()}
    assert c["count_mismatch"] == c["quant_outside"] == 0.0
    assert c["nearest_miss"] == 0.0
    # the rounding the configuration states is the one that was read
    assert (c["rounding_z"] > 0.5) is stochastic


def test_the_float_learner_under_the_quantized_kind_is_refused():
    proc, _ = run_cell("tiny.train_quant_float_learner")
    assert proc.returncode == harness.EXIT_NOT_DEVICE_PATH
    assert "handed over no integer pack" in proc.stderr
    assert "{" not in proc.stdout


def test_the_chip_cell_refuses_the_cpu():
    proc, _ = run_cell(CELL, root=REPO)
    assert proc.returncode == harness.EXIT_NO_DEVICE
    assert "{" not in proc.stdout


def test_a_program_without_the_integer_pack_is_refused_at_once(
        monkeypatch, capsys):
    """The parent commit's program under this PR's benchmark files: no
    `quant_pack` on its learners. The kind says so before any data is
    made, so the driver's try of the new cell on the parent ends by
    itself, soon, with exit 4."""
    import data
    from lightgbm_tpu.treelearner import serial

    monkeypatch.delattr(serial.SerialTreeLearner, "quant_pack")
    monkeypatch.setattr(data, "make_data", lambda *a: pytest.fail(
        "data was made before the refusal"))
    with pytest.raises(harness.Refused) as refused:
        harness.run(["--root", QUANT, "--workload", "tiny.train_quant",
                     "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert refused.value.code == harness.EXIT_NOT_DEVICE_PATH
    assert "keep no integer pack" in capsys.readouterr().err


class DeviceTreeLearner:
    """A stand-in with the real learner's name and what the checks read."""

    quantized = True


KERNELS = ["_pallas_compact_call", "pallas_histogram_slots_ragged"]


def _check(learner=None, hist_rows=5, share=1.0, kernels=KERNELS,
           rehearsal=False):
    kind = harness.load_module("kinds", "train_window_quant", [BENCH])
    ctx = types.SimpleNamespace(rehearsal=rehearsal)
    return kind.check_quantized_path(ctx, learner or DeviceTreeLearner(),
                                     hist_rows, share, lambda _: kernels)


def test_a_sound_quantized_run_passes_every_check():
    assert _check() is None
    # a rehearsal's kernels are interpreted: the program is not lowered
    assert _check(kernels=None, rehearsal=True) is None


class SerialTreeLearner:
    quantized = True


_Float = type("DeviceTreeLearner", (), {"quantized": False})


@pytest.mark.parametrize("kwargs,why", [
    ({"learner": SerialTreeLearner()},
     "grown by SerialTreeLearner, not DeviceTreeLearner"),
    ({"learner": _Float()}, "the learner is not quantized"),
    ({"hist_rows": 0}, "device_hist_rows did not move"),
    ({"share": 0.5}, "hist_int 1 for a share of 0.5"),
    ({"share": None}, "hist_int 1 for a share of None"),
    ({"kernels": ["pallas_histogram_slots_ragged"]},
     "did not both reach Mosaic"),
    ({"kernels": ["_pallas_compact_call"]}, "did not both reach Mosaic"),
])
def test_what_is_not_the_integer_device_path_is_refused(capsys, kwargs, why):
    with pytest.raises(harness.Refused) as refused:
        _check(**kwargs)
    assert refused.value.code == harness.EXIT_NOT_DEVICE_PATH
    assert why in capsys.readouterr().err


# ------------------------------------------- the chip's controls, rehearsed


@pytest.mark.parametrize("variant,fails", [
    ("sound", set()),
    ("dropped", {"count_mismatch", "leaf_value_gap", "split_gain_gap"}),
    ("float", {"leaf_value_gap", "split_gain_gap"}),
    ("nearest", {"rounding_z"}),
])
def test_a_chip_control_bites_on_the_interpreted_kernel_path(variant, fails):
    """`chip_controls_quant.py` as the chip runs it, on the tiny cell with
    the kernels interpreted. `saturated` and `rounded` cannot bite here: no
    integer sum of 8,192 rows passes 256."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", LGBM_TPU_PALLAS_INTERPRET="1")
    proc = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path.insert(0, sys.argv[1]); "
         "import chip_controls_quant as c; "
         "c.main(sys.argv[2], [2 ** 31 + 32], 'tiny.train_quant', sys.argv[3])",
         HERE, variant, QUANT],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = json.loads(proc.stdout.split("CONTROL ", 1)[1])
    assert said["correct"] is (variant == "sound")
    assert fails <= set(said["failed_limits"]), said


# ------------------------------------------------------------- the metrics


def test_the_cell_is_listed_with_its_metrics_files_and_readers(real_index):
    listed = [m for m in real_index["per_layer"]
              if CELL in m.get("workloads", [])]
    names = {m["name"] for m in listed}
    assert set(NEW_IN_PR_32) <= names
    # every one-chip training metric the benchmark had, and no four-chip one
    assert names - set(NEW_IN_PR_32) == {
        m["name"] for m in real_index["per_layer"]
        if m["name"].startswith("train.")}
    for metric in listed:
        assert metric["moves"] in ("train_s_per_tree", "setup_s")
        spec = harness.load_json(os.path.join(
            BENCH, "metrics", metric["name"] + ".json"))
        assert os.path.isfile(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
    for name in NEW_IN_PR_32:
        metric = next(m for m in listed if m["name"] == name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "train_s_per_tree"
    e2e = next(m for m in real_index["end_to_end"]
               if m["name"] == "train_s_per_tree")
    assert e2e["workloads"][-1] == CELL and e2e["bound"] == 0.01
    cell = real_index["workloads"][-1]
    assert cell["name"] == CELL and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    config = real_index["configs"][-1]
    assert config["name"] == cell["config"]
    assert config["reduced"] == ["num_trees"]
    assert config["source"] not in {c["source"]
                                    for c in real_index["configs"][:-1]}
    on_disk = harness.load_json(os.path.join(REPO, config["file"]))
    full = harness.load_json(os.path.join(BENCH, "configs",
                                          "higgs_full_dp4.json"))
    assert on_disk["source"] == config["source"]
    assert on_disk["rows"] == on_disk["published"]["rows"] == 10_500_000
    assert on_disk["features"] == full["features"] == 28
    assert on_disk["data_seed"] == full["data_seed"]
    assert "env" not in on_disk
    shared = {k: v for k, v in full["params"].items()
              if k not in ("tree_learner", "num_machines")}
    assert on_disk["params"] == dict(
        shared, use_quantized_grad=True, num_grad_quant_bins=4,
        stochastic_rounding=True, quant_train_renew_leaf=False)


def test_the_cells_limits_are_no_looser_than_the_float_cells():
    quant = harness.load_json(os.path.join(
        BENCH, "traffic", "train_window_quant.json"))
    floating = harness.load_json(os.path.join(
        BENCH, "traffic", "train_window.json"))
    assert quant["kind"] == "train_window_quant"
    assert quant["warmup_trees"] == floating["warmup_trees"] == 3
    assert set(quant["limits"]) == READINGS
    for name, limit in floating["limits"].items():
        assert quant["limits"][name] <= limit, name
    for name in ("count_mismatch", "quant_outside", "nearest_miss"):
        assert quant["limits"][name] == 0
    for name in ("leaf_value_gap", "split_gain_gap", "split_shortfall",
                 "loss_gap"):  # integer sums are exact: tighter by 10x
        assert quant["limits"][name] <= floating["limits"][name] / 10


def _recorded_ctx(monkeypatch, path: str, notes: list, root: str = QUANT):
    from lightgbm_tpu import tracing

    fake = types.SimpleNamespace(dropped=0, snapshot=lambda: notes)
    monkeypatch.setattr(tracing, "recorder", lambda: fake)
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    args = types.SimpleNamespace(workload="tiny.train_quant", seed=1,
                                 seconds=1, trace=1)
    ctx = harness.Context(args, root, 0.0)
    ctx.window_open_at = 0.0
    ctx.counts.update(window_trees=3, window_s=10.0)
    ctx.e2e["train_s_per_tree"] = 1.0
    return ctx


def test_the_new_metrics_read_the_recorded_quantized_trace(
        train_quant_small, monkeypatch):
    """`trace_scope` reads `lgbm.quantize` (and the trace holds
    `lgbm.renew_leaves`), `trace_idle_in_span` the host span `quantize`,
    `flight_notes` the notes' `hist_int`."""
    notes = [{"kind": "tree_wave", "t": 1.0 + i, "waves": 5, "hist_int": 1,
              "hist_operand": "int"} for i in range(3)]
    ctx = _recorded_ctx(monkeypatch, train_quant_small, notes)
    got = harness.per_layer_metrics(ctx)
    assert set(NEW_IN_PR_32) <= set(got)
    assert got["train_quant.quantize_ms_per_tree"]["value"] > 0
    assert 0 <= got["train_quant.idle_in_quantize_pct"]["value"] < 100
    assert got["train_quant.int_hist_tree_share"]["value"] == 1.0
    assert got["train.route_ms_per_tree"]["value"] > 0

    raw = xplane.load(train_quant_small)
    lo, hi = tr.window_of(raw["host"])
    sec = reader("trace_scope").scope_seconds(raw["devices"], lo, hi)
    for scope in ("quantize", "renew_leaves", "hist", "compact", "scan"):
        assert sec.get("lgbm." + scope, 0.0) > 0.0, scope
    spec = harness.load_json(os.path.join(
        BENCH, "metrics", "train_quant.quantize_ms_per_tree.json"))
    assert got["train_quant.quantize_ms_per_tree"]["value"] \
        == pytest.approx(1000.0 * sec["lgbm.quantize"] / 3)
    assert spec["scope"] == r"^lgbm\.quantize$"
    host = [e for e in raw["host"] if e[0] == "quantize"]
    assert len([e for e in host if lo <= e[1] < hi]) == 3  # one a tree
    names = {e[0] for events in raw["devices"].values() for e in events}
    assert any(n.startswith("pallas_histogram_slots_ragged") for n in names)


def test_the_new_metrics_read_nothing_from_the_parents_program(
        train_small, monkeypatch):
    """A float program from before PR 32 (no `lgbm.quantize`, no `quantize`
    span, no `hist_int` in its notes): each new reader returns nothing and
    raises nothing; the accepted ones still read."""
    notes = [{"kind": "tree_wave", "t": 1.0, "waves": 19, "wave_k": 21,
              "hist_operand": "bf16x3"}]
    ctx = _recorded_ctx(monkeypatch, train_small, notes)
    got = harness.per_layer_metrics(ctx)
    assert not set(NEW_IN_PR_32) & set(got)
    assert got["train.waves_per_tree"]["value"] == pytest.approx(19 / 3)
    assert got["train.route_ms_per_tree"]["value"] > 0
