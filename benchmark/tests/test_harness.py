"""The harness is driven by data: a cell, a configuration, a traffic kind,
a per-layer metric and its reader are found by name in files the harness
has never seen (fixtures/), and the rehearsal cells go through the same
command as the chip's cells, their last line held to the contract.

Then the timed path is broken underneath, once for each fault a cell can
have, and `correct` has to come out false; and the lower-precision control
at test size.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import harness
from conftest import BENCH, FIXTURES, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def run_cell(cell: str, trace: int = 0, seed: int = 2 ** 31 + 77) -> tuple:
    """The cell through the benchmark's own command, as the driver runs it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", FIXTURES,
         "--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    return proc, (json.loads(proc.stdout.strip().splitlines()[-1])
                  if proc.returncode == 0 else None)


def check_line(line: dict, index: dict, cell: str, trace: int) -> None:
    """The contract's result line."""
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "compared"
    assert isinstance(line["correct"], bool)
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        assert key in line["device"]
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in index[kind]
             if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) <= names
    for name, m in line["metrics"].items():
        assert NAME.match(name)
        assert isinstance(m["value"], float) and m["value"] == m["value"]
    if trace:
        assert line["device"]["window_s"] > 0
    else:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    for v in line["compared"].values():
        assert set(v) == {"value", "limit"}


@pytest.fixture(scope="module")
def index() -> dict:
    with open(os.path.join(FIXTURES, "BENCHMARK.json")) as f:
        return json.load(f)


def test_unseen_kind_metric_and_reader_are_found_by_name(index):
    proc, line = run_cell("tiny.unseen")
    assert proc.returncode == 0, proc.stderr[-2000:]
    check_line(line, index, "tiny.unseen", 0)
    assert line["correct"] is True and line["attempted"] == 7
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so


@pytest.mark.parametrize("cell", ["tiny.predict", "tiny.train"])
def test_rehearsal_through_the_command(index, cell):
    proc, line = run_cell(cell)
    assert proc.returncode == 0, proc.stderr[-2000:]
    check_line(line, index, cell, 0)
    assert line["correct"] is True, line["compared"]
    assert line["device"]["platform"] == "cpu"
    # the compared numbers are the last lines of standard error too
    tail = proc.stderr.strip().splitlines()[-(len(line["compared"]) + 1):]
    assert tail[-1] == "correct: True"
    assert all(t.startswith("compared ") for t in tail[:-1])


def test_a_chip_cell_refuses_the_cpu():
    """The real index names no other platform: on the CPU the run ends
    non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "forest500x255.predict_batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=REPO,
        timeout=300)
    assert proc.returncode == harness.EXIT_NO_DEVICE
    assert "{" not in proc.stdout


def test_only_a_fixture_index_may_name_the_cpu(tmp_path, monkeypatch):
    """A `rehearsal` key in the repository's own BENCHMARK.json is not
    honoured: the platform stays the TPU."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    monkeypatch.setattr(harness, "load_json", lambda path: (
        dict(real, rehearsal={"platform": "cpu"})
        if path == os.path.join(REPO, "BENCHMARK.json")
        else json.load(open(path))))
    args = type("A", (), {"seed": 1, "seconds": 1, "trace": 0,
                          "workload": real["workloads"][0]["name"]})
    assert harness.Context(args, REPO, 0.0).platform == "tpu"


# ------------------------------------------------- the timed path, broken

def in_process(cell: str, seed: int = 2 ** 31 + 5) -> dict:
    return harness.run(["--root", FIXTURES, "--workload", cell, "--seed",
                        str(seed), "--seconds", "0.2", "--trace", "0"],
                       check_device=False)


def test_predict_answer_altered_where_it_is_produced(monkeypatch):
    from lightgbm_tpu.models import gbdt

    real = gbdt.predict_raw

    def altered(packed, X, C):
        # every fifth answer: the check reads a seeded sample of the rows
        out = real(packed, X, C)
        return out.at[::5, 0].add(0.01)

    monkeypatch.setattr(gbdt, "predict_raw", altered)
    line = in_process("tiny.predict")
    assert line["correct"] is False
    assert line["compared"]["prob_gap"]["value"] > \
        line["compared"]["prob_gap"]["limit"]


def test_predict_half_of_the_rows_left_out(monkeypatch):
    """The second half of every call answered with the first half's rows."""
    from lightgbm_tpu.models import gbdt

    real = gbdt.predict_raw

    def half(packed, X, C):
        n = X.shape[0] // 2
        out = real(packed, X, C)
        return out.at[n:2 * n].set(out[:n])

    monkeypatch.setattr(gbdt, "predict_raw", half)
    assert in_process("tiny.predict")["correct"] is False


def test_train_sound_run_is_correct():
    line = in_process("tiny.train")
    assert line["correct"] is True, line["compared"]


def test_train_state_returned_unchanged(monkeypatch):
    """The score update dropped: every tree sees the first tree's
    gradients."""
    from lightgbm_tpu.models import gbdt

    monkeypatch.setattr(gbdt, "_apply_split_log_to_score",
                        lambda score, *a, **k: score)
    monkeypatch.setattr(gbdt.GBDT, "_update_train_score",
                        lambda self, tree, class_id: None)
    line = in_process("tiny.train")
    assert line["correct"] is False
    c = line["compared"]
    assert c["leaf_value_gap"]["value"] > c["leaf_value_gap"]["limit"]
    assert c["loss_gap"]["value"] > c["loss_gap"]["limit"]


def test_train_half_of_the_batch_left_out(monkeypatch):
    """Every tree grown on the first half of the rows alone."""
    from lightgbm_tpu.treelearner import device

    real = device.DeviceTreeLearner.train_async

    def half(self, gh_ext, bag_indices=None):
        return real(self, gh_ext,
                    np.arange(self.num_data // 2, dtype=np.int32))

    monkeypatch.setattr(device.DeviceTreeLearner, "train_async", half)
    line = in_process("tiny.train")
    assert line["correct"] is False
    assert line["compared"]["count_mismatch"]["value"] > 0


def test_train_answer_altered_where_it_is_produced(monkeypatch):
    """One leaf's output changed by 1 % as the tree is made."""
    from lightgbm_tpu.treelearner import device

    real = device.DeviceTreeLearner.finalize

    def altered(self, pending):
        tree = real(self, pending)
        tree.leaf_value[1] *= 1.01
        return tree

    monkeypatch.setattr(device.DeviceTreeLearner, "finalize", altered)
    line = in_process("tiny.train")
    assert line["correct"] is False
    c = line["compared"]
    assert c["leaf_value_gap"]["value"] > c["leaf_value_gap"]["limit"]


def test_train_lower_precision_control(index):
    """The control at test size, in a process of its own (the operand type
    is baked into the whole-tree program's trace): the program's own
    lower-precision path, histogram operands in bfloat16, its default when
    the configuration's LGBM_TPU_HIST_F32=1 is taken away, has to fail."""
    proc, line = run_cell("tiny.train_bf16")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    c = line["compared"]
    assert c["leaf_value_gap"]["value"] > 3 * c["leaf_value_gap"]["limit"]


def test_traced_line_from_a_recorded_chip_trace(index, monkeypatch):
    """A traced run end to end, the profiler's file swapped for the trace
    recorded on the chip (a CPU run has no device plane to read): the
    per-layer metrics, busy_s, window_s and the breakdown are in the line;
    a reader with nothing to read stays out of it."""
    import trace as tr

    recorded = os.path.join(os.path.dirname(FIXTURES), "recorded",
                            "predict_small.xplane.pb")
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: recorded)
    line = harness.run(["--root", FIXTURES, "--workload", "tiny.predict",
                        "--seed", "9", "--seconds", "0.2", "--trace", "1"],
                       check_device=False)
    check_line(line, index, "tiny.predict", 1)
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]
    assert len(line["breakdown"]["device_ops"]) <= 10
    # the fixture is a rehearsal: no peaks are read, the share stays out
    assert "predict_batch.step_mfu_pct" not in line["metrics"]


def test_a_traced_cpu_run_is_refused():
    """No operation ran on a device: no line."""
    proc, _ = run_cell("tiny.predict", trace=1)
    assert proc.returncode != 0
    assert "nothing ran on a device" in proc.stderr
    assert "{" not in proc.stdout
