"""The EFB cell's own files: the traffic kind `train_window_efb` rehearsed
on the CPU through the benchmark's command (fixtures/efb/: a tiny index),
its refusals, the seeded Allstate table, the work count, and the real
index's entries. tests/test_efb_device.py (tier-1) holds the program to
the plain reference and reads the controls.
"""
import json
import os
import subprocess
import sys

import numpy as np

import data_allstate
import harness
import work_efb
from conftest import BENCH, FIXTURES, REPO

EFB = os.path.join(FIXTURES, "efb")
CELL = "allstate_efb.train"
NEW_IN_PR_40 = ("train_efb.bundle_scan_ms_per_tree",
                "train_efb.bundle_scan_roofline",
                "train_efb.bundled_split_share")
# the categorical cell's metrics of a test set's evaluation, which read the
# same scopes and notes here
SHARED_EVAL = ("train_cat.eval_ms_per_tree", "train_cat.valid_binned_share")
READINGS = {"bin_bound_mismatch", "conflict_rows_mismatch", "count_mismatch",
            "leaf_value_gap",
            "split_gain_gap", "split_shortfall", "loss_gap",
            "valid_score_gap", "auc_gap"}


def run_cell(cell: str, seed: int = 2 ** 31 + 40, prelude: str = ""):
    """One run of a fixture cell through the benchmark's command, in a
    process of its own; `prelude` is Python run before the harness."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--root", EFB, "--workload", cell, "--seed", str(seed),
            "--seconds", "1", "--trace", "0"]
    if prelude:
        cmd = [sys.executable, "-c",
               f"import sys; sys.path[:0] = [{REPO!r}, {BENCH!r}]\n"
               f"{prelude}\nimport harness\nharness.run({args!r})"]
    else:
        cmd = [sys.executable, os.path.join(BENCH, "run.py")] + args
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc, (json.loads(lines[-1]) if proc.returncode == 0 else None)


def test_rehearsal_through_the_command_is_correct_and_names_the_cpu():
    proc, line = run_cell("tiny.train_efb")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == READINGS
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"train_s_per_tree", "setup_s"}


def test_a_table_without_a_bundle_is_refused_with_no_line():
    proc, line = run_cell("tiny.train_efb_none")
    assert proc.returncode == harness.EXIT_NOT_DEVICE_PATH and line is None
    assert "REFUSED" in proc.stderr and "bundle" in proc.stderr


def test_a_program_without_the_bundle_scan_is_refused_at_once():
    """What a program from before the bundle scan does with this cell: no
    data is made."""
    prelude = (
        "import lightgbm_tpu, lightgbm_tpu.ops.split\n"
        "from lightgbm_tpu.utils import timer\n"
        "del timer.SCOPE_BUNDLE_SCAN\n"
        "import data_allstate\n"
        "def made(*a, **k): raise SystemExit('data was made')\n"
        "data_allstate.make_allstate_data = made")
    proc, line = run_cell("tiny.train_efb", prelude=prelude)
    assert proc.returncode == harness.EXIT_NOT_DEVICE_PATH and line is None
    assert "lgbm.bundle_scan" in proc.stderr


def test_the_table_is_csr_one_level_a_column_and_seeded():
    cfg = json.load(open(os.path.join(EFB, "bench", "configs",
                                      "tiny_efb.json")))
    a = data_allstate.make_allstate_data(cfg, 1)
    b = data_allstate.make_allstate_data(cfg, 1)
    c = data_allstate.make_allstate_data(cfg, 2)
    X, y = a["train"]
    assert X.format == "csr" and X.shape == (cfg["rows"], cfg["features"])
    width = len(cfg["numerical"]) + len(cfg["categorical"])
    assert X.nnz == cfg["rows"] * width and X.has_canonical_format
    offs = data_allstate.offsets(cfg)
    for k in range(len(cfg["categorical"])):
        block = X[:, offs[k]:offs[k + 1]]
        assert np.all(np.asarray(block.sum(axis=1)).ravel() == 1.0)
    assert (X != b["train"][0]).nnz == 0 and np.array_equal(y, b["train"][1])
    # another seed: the same rows and labels, other column orders
    assert np.array_equal(y, c["train"][1])
    assert (X != c["train"][0]).nnz > 0
    assert np.array_equal(np.sort(np.asarray(X.sum(axis=0)).ravel()),
                          np.sort(np.asarray(c["train"][0].sum(axis=0))
                                  .ravel()))
    # a large seed and a negative one are seeds too
    for seed in (2 ** 31 + 40, -3):
        assert data_allstate.make_allstate_data(cfg, seed)["train"][0]\
            .shape == X.shape


def test_the_scan_work_counts_histograms_and_records():
    w = work_efb.bundle_scan_work(n_groups=69, group_bins=256,
                                  n_features=4227, wave_k=21, waves=30,
                                  trees=1)
    scans = 1 + 2 * 21 * 30
    assert w.bytes == scans * (69 * 256 * 3 + 4227 * 14) * 4
    assert w.ops == scans * 69 * 256 * 3 and w.operand == "bf16"


def test_the_real_index_lists_the_cell_where_it_reports():
    index = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cells = {w["name"]: w for w in index["workloads"]}
    assert cells[CELL]["chips"] == 1 and cells[CELL]["traffic"] == \
        "train_window_efb"
    train_s = next(m for m in index["end_to_end"]
                   if m["name"] == "train_s_per_tree")
    assert CELL in train_s["workloads"]
    names = [m["name"] for m in index["per_layer"]]
    assert set(NEW_IN_PR_40) <= set(names)
    for m in index["per_layer"]:
        if m["name"] in NEW_IN_PR_40:
            assert m["workloads"] == [CELL]
            assert os.path.isfile(os.path.join(BENCH, "metrics",
                                               m["name"] + ".json"))
        elif "expo_categorical.train" in m.get("workloads", []) \
                and m["name"].startswith("train.") \
                or m["name"] in SHARED_EVAL:
            assert CELL in m["workloads"]
    assert set(SHARED_EVAL) <= set(names)
