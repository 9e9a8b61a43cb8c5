"""`use_quantized_grad` on the device learner held to the mathematics.

tests/test_quantized.py holds quantized training to AUC parity and to the
other production learners. This file compares it with the benchmark's
plain quantized reference (benchmark/reference/gbdt_quant.py: numpy float64
from the raw rows, labels, the text model, the scores and each tree's int8
pack, which it does not trust; nothing of the program), through `lgb.train`:
what the chip cell `higgs_binary_quant.train` decides `correct` by, at test
size (24,000 x 28, 31 leaves, 3 trees), on the XLA bodies, on the kernel
path interpreted, and under `tree_learner=data` on four of the eight CPU
devices tests/conftest.py forces (the int16-narrowed reduction).

The limits, each with its reason, between the sound runs' largest reading
at this size and the controls' smallest (the cell's own, at 10,500,000 rows,
are in benchmark/traffic/train_window_quant.json and PERF.md section 6):

  count_mismatch   0, exact: counts come from the pack's third channel
                   (sound 0; rows dropped from the histograms 183);
  scale_gap        1e-5: the program's float32 max|g| / (bins / 2) and
                   max|h| / bins against float64 ones from scores that
                   differ by float32 rounding (sound 1.0e-6; the float
                   path, whose scores drift, 3.1e-4);
  quant_outside    0, exact: a row's integer is a neighbour of g / scale
                   (sound 0; half the gradients zeroed 72,000);
  rounding_z       6: the worst of ~60 buckets' standard scores; a sound
                   stream reads 2-4 (sound 2.5; the chance of 6 is 1e-7 a
                   run); nearest rounding 74, a stream from [0, 0.8) 20;
  nearest_miss     0, exact, where the configuration states nearest;
  leaf_value_gap   2e-5: integer sums are exact, so what is left is the
                   float32 product with the scale and one float32 division
                   (sound 1.6e-6). With quant_train_renew_leaf 2e-4: float32
                   sums of the true gradients (sound 2.7e-5). One bin
                   altered by 3 units 1.9e-2; sums rounded to bfloat16
                   7.3e-3; the float path 8.5e-2;
  split_gain_gap   1e-4: a gain is a difference of float32 quotients, after
                   the scan's float32 cumulative sum of 255 scaled bins
                   (sound 3.7e-6; rounded 1.2e-2; altered 3.1e-2; float
                   path 0.13; saturated at int8's 127 8.2e3);
  split_shortfall  1e-4: no candidate beats the split taken by more than a
                   float32 near-tie (sound 0; altered 3.0e-2; float path
                   0.11);
  loss_gap         1e-6: float32 scores against float64 (sound 2.4e-9, with
                   renewal 2.9e-7; altered 1.5e-5; rounded 3.4e-5; float
                   path 1.5e-4).
"""
import importlib.util
import pathlib
import sys
import types

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.parallel import learners as learners_mod
from lightgbm_tpu.treelearner import device as device_mod
from lightgbm_tpu.treelearner import serial as serial_mod

BENCH = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
LIMITS = {"count_mismatch": 0, "scale_gap": 1e-5, "quant_outside": 0,
          "rounding_z": 6.0, "nearest_miss": 0, "leaf_value_gap": 2e-5,
          "split_gain_gap": 1e-4, "split_shortfall": 1e-4, "loss_gap": 1e-6}
RENEW_LEAF_VALUE_LIMIT = 2e-4
PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
          "learning_rate": 0.1, "min_sum_hessian_in_leaf": 10,
          "use_quantized_grad": True, "num_grad_quant_bins": 4,
          "stochastic_rounding": True, "quant_train_renew_leaf": False,
          "verbosity": -1}
ROWS, FEATURES, TREES = 24000, 28, 3


def _load(name: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def plain():
    """(modeltext, gbdt_quant) of the benchmark, loaded once by file:
    benchmark/ holds a `trace.py` and a `data.py`, so it is never put on
    the path of the test process. The reference imports `modeltext` and
    `reference.gbdt` by those names."""
    names = ("modeltext", "reference", "reference.gbdt",
             "reference.gbdt_quant")
    had = {name: sys.modules.get(name) for name in names}
    modeltext = _load("modeltext", BENCH / "modeltext.py")
    package = types.ModuleType("reference")
    package.__path__ = [str(BENCH / "reference")]
    sys.modules["reference"] = package
    _load("reference.gbdt", BENCH / "reference" / "gbdt.py")
    quant = _load("reference.gbdt_quant",
                  BENCH / "reference" / "gbdt_quant.py")
    yield modeltext, quant
    for name, module in had.items():
        if module is None:
            sys.modules.pop(name, None)
        else:
            sys.modules[name] = module


def _data(rows: int):
    rng = np.random.default_rng(32)
    X = rng.standard_normal((rows, FEATURES), dtype=np.float32)
    w = rng.standard_normal(FEATURES, dtype=np.float32)
    noise = rng.standard_normal(rows, dtype=np.float32)
    return X, (X @ w + 2.0 * noise > 0).astype(np.float64)


def _readings(monkeypatch, plain, extra=None, interpret=False,
              stated=None, rows: int = ROWS) -> tuple:
    """Three trees through lgb.train on the device learner, each tree's
    scores and integer pack taken as the benchmark's kind takes them, then
    the reference's readings; and the learner that grew the trees.
    `stated` is the configuration the reference is told, where a control
    runs another."""
    modeltext, quant = plain
    if interpret:
        monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    # the device learners are for a TPU; the CPU answers for one here
    monkeypatch.setattr(serial_mod, "on_tpu", lambda: True)
    params = dict(PARAMS, **(extra or {}))
    X, y = _data(rows)
    scores, packs = [], []

    def after(env):
        gbdt = env.model._gbdt
        scores.append(np.asarray(gbdt.score[0]))
        pack, scales = gbdt.tree_learner.quant_pack()
        packs.append((np.asarray(pack)[:rows], np.asarray(scales)))

    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=TREES,
                    callbacks=[after])
    trees = modeltext.parse_model(bst.model_to_string())
    assert len(trees) == TREES
    assert all(t.num_leaves == params["num_leaves"] for t in trees)
    readings = quant.follow(X, y, trees, dict(params, **(stated or {})),
                            scores, packs, TREES)
    return readings, bst._gbdt.tree_learner


def _hold(readings: dict, renew: bool = False) -> None:
    for name, limit in LIMITS.items():
        if renew and name == "leaf_value_gap":
            limit = RENEW_LEAF_VALUE_LIMIT
        assert readings[name] <= limit, (name, readings)


@pytest.mark.parametrize("renew", [False, True], ids=["", "renew"])
@pytest.mark.parametrize("stochastic", [True, False],
                         ids=["stochastic", "nearest"])
@pytest.mark.parametrize("quant_bins", [4, 16])
def test_device_learner_agrees_with_the_plain_quantized_reference(
        monkeypatch, plain, quant_bins, stochastic, renew):
    """The XLA bodies (what a CPU grows trees with)."""
    readings, learner = _readings(monkeypatch, plain, {
        "num_grad_quant_bins": quant_bins, "stochastic_rounding": stochastic,
        "quant_train_renew_leaf": renew})
    assert type(learner) is device_mod.DeviceTreeLearner
    assert learner.quantized and learner.hist_operand == "xla"
    _hold(readings, renew)
    # the reading that does not apply stays out of the way
    assert readings["nearest_miss" if stochastic else "rounding_z"] == 0.0
    if stochastic:
        assert readings["rounding_z"] > 0.5  # buckets were read


@pytest.mark.parametrize("quant_bins,stochastic,renew", [
    (4, True, False), (16, False, True)])
def test_kernel_path_interpreted_agrees_with_the_plain_quantized_reference(
        monkeypatch, plain, quant_bins, stochastic, renew):
    """The ragged kernel's integer policy (one bfloat16 limb, int32
    accumulation) and the compaction kernel carrying int8 values in the
    float32 payload, interpreted."""
    readings, learner = _readings(monkeypatch, plain, {
        "num_grad_quant_bins": quant_bins, "stochastic_rounding": stochastic,
        "quant_train_renew_leaf": renew}, interpret=True)
    assert type(learner) is device_mod.DeviceTreeLearner
    assert learner.hist_operand == "int"
    _hold(readings, renew)


@pytest.mark.parametrize("rows,quant_bins,stochastic,renew", [
    (ROWS, 4, True, False), (ROWS, 16, True, True), (ROWS, 4, False, False),
    (6000, 4, True, False), (6000, 4, False, True)])
def test_data_parallel_on_four_devices_agrees_with_the_same_reference(
        monkeypatch, plain, rows, quant_bins, stochastic, renew):
    """The histograms' psum_scatter in int32 at 24,000 rows, and narrowed to
    int16 at 6,000 (6,000 x 4 bins < 32,000: no sum can pass 2**15)."""
    real = learners_mod.DeviceDataParallelTreeLearner._narrow
    narrowed = []

    def spy(self, leaf_sh):
        narrowed.append(real(self, leaf_sh))
        return narrowed[-1]

    monkeypatch.setattr(learners_mod.DeviceDataParallelTreeLearner,
                        "_narrow", spy)
    readings, learner = _readings(monkeypatch, plain, {
        "num_grad_quant_bins": quant_bins, "stochastic_rounding": stochastic,
        "quant_train_renew_leaf": renew, "tree_learner": "data",
        "num_machines": 4}, rows=rows)
    assert type(learner) is learners_mod.DeviceDataParallelTreeLearner
    assert learner.D == 4 and learner.quantized
    assert narrowed == [rows == 6000] * TREES
    _hold(readings, renew)


# ------------------------------------------------------------ the controls


def test_control_nearest_rounding_where_stochastic_is_stated_fails(
        monkeypatch, plain):
    readings, _ = _readings(monkeypatch, plain,
                            {"stochastic_rounding": False},
                            stated={"stochastic_rounding": True})
    assert readings["rounding_z"] > 10 * LIMITS["rounding_z"], readings
    # every integer is still a neighbour, and the tree is the integers'
    for name in ("count_mismatch", "scale_gap", "quant_outside",
                 "leaf_value_gap", "split_gain_gap"):
        assert readings[name] <= LIMITS[name], (name, readings)


def test_control_a_stream_from_a_shorter_interval_fails(monkeypatch, plain):
    """r ~ U[0, 0.8): every integer a neighbour, the shares biased."""
    real = jax.random.uniform

    def short(key, shape=(), dtype=float, minval=0.0, maxval=1.0, **kw):
        return real(key, shape, dtype, minval, maxval, **kw) * 0.8

    from lightgbm_tpu.ops import quantize

    monkeypatch.setattr(quantize.jax.random, "uniform", short)
    quantize.quantize_pack.clear_cache()
    quantize.discretize_gradients.clear_cache()
    try:
        readings, _ = _readings(monkeypatch, plain)
    finally:
        monkeypatch.undo()
        quantize.quantize_pack.clear_cache()
        quantize.discretize_gradients.clear_cache()
    assert readings["rounding_z"] > 3 * LIMITS["rounding_z"], readings
    assert readings["quant_outside"] == 0.0


def test_control_the_float_path_handed_over_as_quantized_fails(
        monkeypatch, plain):
    """The tree grown from the float gradients (the program's default
    float path) while the learner says quantized and hands over a sound
    integer pack: the integers do not make that tree."""
    real = device_mod.DeviceTreeLearner.train_async

    def float_tree(self, gh_ext, bag_indices=None):
        self._prepare_gh(gh_ext)  # a sound pack and scales, not used
        self.quantized = False
        try:
            return real(self, gh_ext, bag_indices)
        finally:
            self.quantized = True

    monkeypatch.setattr(device_mod.DeviceTreeLearner, "train_async",
                        float_tree)
    readings, learner = _readings(monkeypatch, plain)
    assert learner.quantized
    for name in ("quant_outside", "count_mismatch"):
        assert readings[name] == 0.0, (name, readings)
    assert readings["rounding_z"] <= LIMITS["rounding_z"]
    for name in ("leaf_value_gap", "split_gain_gap", "split_shortfall",
                 "loss_gap"):
        assert readings[name] > 10 * LIMITS[name], (name, readings)


FAULTS = {
    # one bin of every integer histogram off by 3 gradient units (of the
    # first group's, which the leaf totals are summed from)
    "altered": lambda real, bins, gh, nb: real(bins, gh, nb).at[
        0, 100, 0].add(3.0),
    # sums saturated at int8's 127
    "saturated": lambda real, bins, gh, nb: jax.numpy.clip(
        real(bins, gh, nb), -127.0, 127.0),
    # sums rounded to bfloat16's eight bits
    "rounded": lambda real, bins, gh, nb: real(bins, gh, nb).astype(
        jax.numpy.bfloat16).astype(jax.numpy.float32),
    # every second position's row left out of every histogram
    "dropped": lambda real, bins, gh, nb: real(
        bins, gh.at[::2].set(0.0), nb),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_control_a_faulty_integer_histogram_fails(monkeypatch, plain, fault):
    """The XLA body's histogram made wrong inside the whole-tree program
    (traced anew, and thrown away after): the pack handed over is sound,
    the tree is not the integers' tree."""
    real = device_mod.build_histogram
    monkeypatch.setattr(
        device_mod, "build_histogram",
        lambda bins, gh, nb: FAULTS[fault](real, bins, gh, nb))
    device_mod.grow_tree_on_device.clear_cache()
    try:
        readings, _ = _readings(monkeypatch, plain)
    finally:
        monkeypatch.undo()
        device_mod.grow_tree_on_device.clear_cache()
    # as the harness decides: a NaN (rows dropped leave leaves empty) fails
    failed = [name for name, limit in LIMITS.items()
              if not readings[name] <= limit]
    assert "split_gain_gap" in failed and "leaf_value_gap" in failed, \
        readings
    assert not readings["split_gain_gap"] <= 10 * LIMITS["split_gain_gap"]


def test_control_half_the_gradients_zeroed_before_quantization_fails(
        monkeypatch, plain):
    """The benchmark's `half` control: the second half of the rows carries
    zero gradient and hessian into the discretizer. The tree is still the
    integers' tree; the integers are not the gradients'."""
    real = device_mod.DeviceTreeLearner.train_async

    def half(self, gh_ext, bag_indices=None):
        n = self.num_data
        return real(self, gh_ext.at[n // 2:n].set(0.0), bag_indices)

    monkeypatch.setattr(device_mod.DeviceTreeLearner, "train_async", half)
    readings, _ = _readings(monkeypatch, plain)
    assert readings["quant_outside"] > TREES * ROWS // 2, readings
    assert readings["rounding_z"] > 10 * LIMITS["rounding_z"], readings
    assert readings["leaf_value_gap"] <= LIMITS["leaf_value_gap"]


def test_a_reading_that_cannot_be_computed_is_nan_not_zero(
        monkeypatch, plain):
    """The last followed tree's pack with no gradient and no hessian
    anywhere: every leaf's output is 0 / 0. Python's `max(0.0, nan)` is 0.0, which would pass a limit
    (on the chip, PR 32: rows dropped from the histograms left leaves empty
    and three readings said 0.0); the harness counts NaN as not correct."""
    _, quant = plain
    real = quant.follow

    def emptied(X, y, trees, params, scores, packs, n_follow):
        q, scales = packs[-1]
        packs = packs[:-1] + [(q * np.array([0, 0, 1], q.dtype), scales)]
        return real(X, y, trees, params, scores, packs, n_follow)

    monkeypatch.setattr(quant, "follow", emptied)
    readings, _ = _readings(monkeypatch, plain)
    for name in ("leaf_value_gap", "split_gain_gap", "split_shortfall",
                 "loss_gap"):
        assert np.isnan(readings[name]), (name, readings)
    assert readings["count_mismatch"] == 0.0


def test_the_reference_imports_nothing_of_the_program():
    text = (BENCH / "reference" / "gbdt_quant.py").read_text()
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports and not any("lightgbm_tpu" in ln or "jax" in ln
                               for ln in imports), imports
