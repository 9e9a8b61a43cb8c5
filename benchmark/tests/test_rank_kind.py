"""The lambdarank cell's own files: the traffic kind `train_window_rank`
rehearsed on the CPU through the benchmark's command (fixtures/rank/: a
fourth tiny index), its refusals, the seeded table, the work count, the two
readers new in PR 34 on made-up inputs, and the real index's entries.
tests/test_rank_reference.py (tier-1) holds the program to the plain
reference and reads the six controls.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import data_rank
import harness
import work
import work_rank
from conftest import BENCH, FIXTURES, REPO
from test_scope_readers import reader

RANK = os.path.join(FIXTURES, "rank")
CELL = "mslr_lambdarank.train"
NEW_IN_PR_34 = ("train_rank.pairs_ms_per_tree",
                "train_rank.sort_scatter_ms_per_tree",
                "train_rank.pairs_roofline", "train_rank.pair_fill_share",
                "train_rank.eval_ms_per_tree", "train_rank.idle_in_eval_pct")
READINGS = {"count_mismatch", "leaf_value_gap", "split_gain_gap",
            "split_shortfall", "score_gap", "valid_score_gap", "ndcg_gap"}


def run_cell(cell: str, seed: int = 2 ** 31 + 34):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", RANK,
         "--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    return proc, (json.loads(proc.stdout.strip().splitlines()[-1])
                  if proc.returncode == 0 else None)


def test_rehearsal_through_the_command_is_correct_and_names_the_cpu():
    proc, line = run_cell("tiny.train_rank")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == READINGS
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["plane_groups"] == 136
    assert set(line["metrics"]) == {"train_s_per_tree", "setup_s"}


def test_another_objective_is_refused_with_no_line():
    proc, line = run_cell("tiny.train_rank_binary")
    assert proc.returncode == harness.EXIT_NOT_DEVICE_PATH and line is None
    assert "REFUSED" in proc.stderr and "lambdarank" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_a_program_without_the_scope_is_refused_before_any_data(monkeypatch):
    """The parent commit's program under the new files: exit 4 at once."""
    from lightgbm_tpu.utils import timer

    kind = harness.load_module("kinds", "train_window_rank", [BENCH])
    monkeypatch.delattr(timer, "SCOPE_RANK_PAIRS")
    monkeypatch.setattr(data_rank, "make_rank_data",
                        lambda *a: pytest.fail("data was made"))
    with pytest.raises(harness.Refused) as refused:
        kind.run(types.SimpleNamespace(config={}, traffic={}))
    assert refused.value.code == harness.EXIT_NOT_DEVICE_PATH


@pytest.mark.parametrize("rows,queries", [(2_270_296, 18_919),
                                          (747_218, 6_306), (5000, 40)])
def test_query_sizes_meet_the_counts_exactly(rows, queries):
    sizes = data_rank.query_sizes(rows, queries, np.random.default_rng(3))
    assert sizes.shape == (queries,) and int(sizes.sum()) == rows
    assert sizes.min() == 1 and sizes.max() == 1251
    assert np.median(sizes) < sizes.mean()  # skewed


def test_the_table_is_the_data_seeds_and_the_seed_reorders_its_columns():
    cfg = {"features": 136, "data_seed": 5, "rows": 30000, "queries": 250,
           "valid": {"vali": {"rows": 9000, "queries": 75}}}
    a, b = data_rank.make_rank_data(cfg, 1), data_rank.make_rank_data(cfg, 2)
    for name, rows in (("train", 30000), ("vali", 9000)):
        Xa, ga, sa = a[name]
        Xb, gb, sb = b[name]
        assert Xa.shape == (rows, 136) and Xa.dtype == np.float32
        assert Xa.flags.c_contiguous
        assert np.array_equal(ga, gb) and np.array_equal(sa, sb)
        assert np.array_equal(Xa[:, 0], Xb[:, 0])
        assert not np.array_equal(Xa[:, 1:], Xb[:, 1:])
        assert np.array_equal(np.sort(Xa[:64], axis=1),
                              np.sort(Xb[:64], axis=1))
        assert set(np.unique(ga)) <= {0.0, 1.0, 2.0, 3.0, 4.0}
    grades = a["train"][1]
    shares = np.bincount(grades.astype(int), minlength=5) / len(grades)
    np.testing.assert_allclose(shares, data_rank.GRADE_SHARES, atol=2e-3)
    X = a["train"][0]
    distinct = sorted(len(np.unique(X[:, j])) for j in range(136))
    assert distinct[0] < 40 and distinct[33] <= 201 and distinct[-1] > 20000
    bounds = np.concatenate([[0], np.cumsum(a["train"][2])])
    assert any(len(np.unique(grades[lo:hi])) == 1 and hi - lo > 1
               for lo, hi in zip(bounds[:-1], bounds[1:]))


def test_the_gradient_work_is_counted_from_the_sizes_alone():
    sizes = np.array([1, 2, 30, 31, 1251])
    want = 0 + 1 + (30 * 30 - 465) + (30 * 31 - 465) + (30 * 1251 - 465)
    assert work_rank.pair_positions(sizes, 30) == want
    needed = work_rank.gradient_work(sizes, 30)
    assert needed.ops == want * work_rank.PAIR_OPS
    assert needed.bytes == sizes.sum() * work_rank.DOCUMENT_BYTES
    assert work_rank.pair_positions(sizes, 5) < want


def test_notes_ratio_reads_the_window_s_notes_only(monkeypatch):
    from lightgbm_tpu import tracing

    notes = [{"kind": "rank_gradients", "t": t, "pair_positions": 6,
              "pair_slots": 10} for t in (0.5, 1.5, 2.5)]
    notes.append({"kind": "tree_wave", "t": 1.6, "pair_positions": 99})
    fake = types.SimpleNamespace(dropped=0, snapshot=lambda: notes)
    monkeypatch.setattr(tracing, "recorder", lambda: fake)
    ctx = types.SimpleNamespace(counts={"window_s": 1.0}, window_open_at=1.0,
                                roots=[BENCH])
    spec = {"kind": "rank_gradients", "field": "pair_positions",
            "over": "pair_slots"}
    assert reader("notes_ratio").read(ctx, spec) == pytest.approx(0.6)
    assert reader("notes_ratio").read(
        ctx, dict(spec, kind="no_such_note")) is None
    fake.dropped = 1
    assert reader("notes_ratio").read(ctx, spec) is None


def test_scope_roofline_reads_nothing_without_a_trace_or_a_work_count():
    ctx = types.SimpleNamespace(counts={}, roots=[BENCH],
                                trace_summary=lambda: None)
    spec = {"scope": "^lgbm\\.rank_pairs$", "work": "rank_pair_work"}
    assert reader("scope_roofline").read(ctx, spec) is None
    ctx.trace_summary = lambda: object()
    assert reader("scope_roofline").read(ctx, spec) is None


def test_the_real_index_lists_the_cell_where_it_reports():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        index = json.load(f)
    assert index["workloads"][-1]["name"] == CELL
    assert index["workloads"][-1]["chips"] == 1
    by_name = {m["name"]: m for m in index["per_layer"]}
    for name in NEW_IN_PR_34:
        assert by_name[name]["workloads"] == [CELL]
        spec = harness.load_json(os.path.join(BENCH, "metrics",
                                              name + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    assert [m["name"] for m in index["per_layer"]][-6:] == list(NEW_IN_PR_34)
    cfg = harness.load_json(os.path.join(BENCH, "configs",
                                         "mslr_lambdarank.json"))
    assert cfg["reduced"] == ["num_trees"]
    assert (cfg["rows"], cfg["queries"], cfg["features"]) == (
        2_270_296, 18_919, 136)
    limits = harness.load_json(os.path.join(
        BENCH, "traffic", "train_window_rank.json"))["limits"]
    assert set(limits) == READINGS
    peaks = work.peaks_for("TPU v5 lite")
    least, bound = work.least_seconds(work_rank.gradient_work(
        data_rank.query_sizes(2_270_296, 18_919, np.random.default_rng(1)),
        30), "TPU v5 lite")
    assert bound == "bytes" and least == pytest.approx(
        2_270_296 * 16 / peaks["hbm_bytes_per_s"])
