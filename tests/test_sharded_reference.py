"""`tree_learner=data, num_machines=4` held to the mathematics.

tests/test_sharded_device.py compares the sharded device learner with the
one-chip learner on grid-snapped gradients (bit for bit, whatever both
compute). This file compares it with the benchmark's plain reference
(benchmark/reference/gbdt.py: numpy float64 from the raw rows, labels and
the text model alone, nothing of the program), through `lgb.train` and on
the objective's own ungridded float32 gradients: what the chip cell
`higgs_full.train_4chip` decides `correct` by, at test size on four of the
eight CPU devices tests/conftest.py forces, Pallas kernels interpreted.

The limits, each with its reason, between the sound run's reading at this
size (3 trees of 15 leaves on 6,000 rows) and the controls' below:

  count_mismatch   0, exact: every leaf and node count the model states is
                   the count of rows the plain traversal puts there (sound
                   0; a shard left out 87);
  leaf_value_gap   1e-4: float32 sums of ~400 to 6,000 gradients against
                   float64 (sound 3.0e-6; bfloat16 operands 3.2e-3; a shard
                   left out 0.52);
  split_gain_gap   1e-3: a gain is a difference of quotients of such sums
                   (sound 3.5e-6; bfloat16 1.1e-2; a shard left out 0.40);
  split_shortfall  1e-3: no candidate threshold beats the split taken by
                   more than float32 rounding of a near-tie (sound 4e-14;
                   bfloat16 the same: it picks the same splits here; a
                   shard left out 0.61);
  loss_gap         1e-5: the program's float32 scores against the
                   reference's float64 ones (sound 1.6e-8; bfloat16 7.3e-5;
                   a shard left out 5.2e-3).
"""
import importlib.util
import pathlib
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.parallel import learners as learners_mod
from lightgbm_tpu.treelearner import serial as serial_mod

BENCH = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
LIMITS = {"count_mismatch": 0, "leaf_value_gap": 1e-4,
          "split_gain_gap": 1e-3, "split_shortfall": 1e-3, "loss_gap": 1e-5}
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
          "learning_rate": 0.1, "min_sum_hessian_in_leaf": 10,
          "tree_learner": "data", "num_machines": 4, "verbosity": -1}
ROWS, FEATURES, TREES = 6000, 10, 3


def _load(name: str, path: pathlib.Path):
    """A benchmark module by file: benchmark/ holds a `trace.py` and a
    `data.py`, so it is never put on the path of the test process."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def plain():
    """(modeltext, reference) of the benchmark, loaded once; the reference
    imports `modeltext` by that bare name."""
    had = sys.modules.get("modeltext")
    modeltext = _load("modeltext", BENCH / "modeltext.py")
    reference = _load("bench_reference_gbdt", BENCH / "reference" / "gbdt.py")
    yield modeltext, reference
    sys.modules.pop("bench_reference_gbdt", None)
    if had is None:
        sys.modules.pop("modeltext", None)
    else:
        sys.modules["modeltext"] = had


def _data():
    rng = np.random.default_rng(28)
    X = rng.standard_normal((ROWS, FEATURES), dtype=np.float32)
    w = rng.standard_normal(FEATURES, dtype=np.float32)
    noise = rng.standard_normal(ROWS, dtype=np.float32)
    return X, (X @ w + noise > 0).astype(np.float64)


def _readings(monkeypatch, plain, f32: bool = True) -> tuple:
    """Three trees through lgb.train on the four-device mesh, then the
    reference's five numbers for them; and the learner that grew them."""
    modeltext, reference = plain
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("LGBM_TPU_HIST_F32", "1" if f32 else "0")
    # the device learners are for a TPU; the CPU answers for one here
    monkeypatch.setattr(serial_mod, "on_tpu", lambda: True)
    X, y = _data()
    scores = []

    def after(env):
        scores.append(np.asarray(env.model._gbdt.score[0]))

    bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=TREES,
                    callbacks=[after])
    trees = modeltext.parse_model(bst.model_to_string())
    assert len(trees) == TREES
    return (reference.follow(X, y, trees, PARAMS, scores, TREES),
            bst._gbdt.tree_learner)


def test_data_parallel_on_four_devices_agrees_with_the_plain_reference(
        monkeypatch, plain):
    readings, learner = _readings(monkeypatch, plain)
    assert type(learner) is learners_mod.DeviceDataParallelTreeLearner
    assert learner.D == 4
    # 6,000 rows do not divide into four tile-aligned shards: the padding
    # rows are in the program and have to stay out of every count
    assert learner.n_pad > ROWS and learner.n_pad % (4 * 1024) == 0
    assert len(learner.bins_dev.addressable_shards) == 4
    for name, limit in LIMITS.items():
        assert readings[name] <= limit, (name, readings)


def test_control_a_shards_rows_left_out_of_the_reduction_fails(
        monkeypatch, plain):
    """The first shard's gradients, hessians and counts zeroed on their
    way to the mesh: its rows reach no histogram, as if its block were
    missing from the psum_scatter."""
    real = learners_mod.DeviceDataParallelTreeLearner._grow

    def without_first_shard(self, gh_sh, *args, **kwargs):
        hi = self.n_pad // self.D
        return real(self, gh_sh.at[:hi].set(0.0), *args, **kwargs)

    monkeypatch.setattr(learners_mod.DeviceDataParallelTreeLearner,
                        "_grow", without_first_shard)
    readings, _ = _readings(monkeypatch, plain)
    assert readings["count_mismatch"] > 0
    for name in ("leaf_value_gap", "split_gain_gap", "split_shortfall",
                 "loss_gap"):
        assert readings[name] > 10 * LIMITS[name], (name, readings)


def test_control_the_bfloat16_default_fails(monkeypatch, plain):
    """The program's default float path rounds the histogram's gradient
    operand to bfloat16: a lower precision than the cell states has to
    come out as not correct, by a limit and not by all of them."""
    readings, _ = _readings(monkeypatch, plain, f32=False)
    assert readings["count_mismatch"] == 0
    failed = {name for name, limit in LIMITS.items()
              if readings[name] > limit}
    assert {"leaf_value_gap", "split_gain_gap", "loss_gap"} <= failed, \
        readings
    assert failed < set(LIMITS)
