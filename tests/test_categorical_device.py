"""Categorical features on the whole-tree device program, held to the
benchmark's plain categorical reference (benchmark/reference/gbdt_cat.py:
numpy float64, LightGBM's FindBestThresholdCategoricalInner and
CategoricalDecision, nothing of the program): what the chip cell
`expo_categorical.train` decides `correct` by, at test size.

The data (20,000 training rows, 4,000 test rows): a column of three
categories (four bins with bin 0: the one-hot lane), a column of 300
categories, Zipf 1.1 as the cell's airports (~270 binned, past the uint8
plane's 256 bins, so the plane is uint16; the rest past the cut in bin 0),
a column of 20 categories with NaN, two numerical columns; the test set
adds categories the training set never saw and NaN. 15 leaves, three
trees through lgb.train with the test set evaluated (AUC) every tree, on
the XLA bodies and on the kernel path interpreted.

The limits, each between the sound runs' largest reading at this size
and the controls' smallest (sound: XLA bodies / interpreted kernels; the
controls on the XLA bodies):

  bin_map_mismatch 0, exact: the model's bin_2_categorical against the
                   reference's own from the raw column (sound 0; the
                   mapper's cut at 98 % in place of 99 %: the last test);
  count_mismatch   0, exact: every leaf's and node's rows by traversal of
                   the raw rows with the model's category sets (sound 0;
                   routed by the numerical bin <= k 75; the left set
                   shifted one place along the ctr order 38);
  leaf_value_gap   2e-4 (sound 4.7e-5 / 1.3e-6; cat_l2 dropped 0.117;
                   shifted 3.1);
  split_gain_gap   3e-4 (sound 7.0e-5 / 2.1e-6; cat_l2 dropped 0.347);
  split_shortfall  1e-3 (sound 4e-13; ctr without cat_smooth 0.101;
                   cat_l2 dropped 0.32);
  cat_set_gap      1e-3: a categorical split's left set against the
                   smoothed ctr order (sound 0; unsmoothed 0.545; shifted
                   0.503; routed numerically 1.0);
  loss_gap         1e-5 (sound 1.6e-7 / 2.6e-9; cat_l2 dropped 7.4e-4);
  valid_score_gap  1e-4: the test scores by the gather traversal against
                   the reference's own traversal (sound 1.5e-5 / 2.7e-7;
                   cat_l2 dropped 0.049);
  auc_gap          1e-5 (sound 2.5e-8; no fault of the trees moves it: the
                   metric with tied negatives at full credit fails it,
                   the last test).
"""
import importlib.util
import inspect
import pathlib
import sys
import textwrap
import types

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as CoreDataset
from lightgbm_tpu.ops import split as split_mod
from lightgbm_tpu.treelearner import device as device_mod
from lightgbm_tpu.treelearner import serial as serial_mod

BENCH = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
LIMITS = {"bin_map_mismatch": 0, "count_mismatch": 0, "leaf_value_gap": 2e-4,
          "split_gain_gap": 3e-4, "split_shortfall": 1e-3,
          "cat_set_gap": 1e-3, "loss_gap": 1e-5, "valid_score_gap": 1e-4,
          "auc_gap": 1e-5}
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
          "learning_rate": 0.1, "min_data_in_leaf": 0,
          "min_sum_hessian_in_leaf": 5, "max_cat_to_onehot": 4,
          "max_cat_threshold": 32, "cat_smooth": 10, "cat_l2": 10,
          "min_data_per_group": 50, "metric": "auc", "verbosity": -1}
CATS = [0, 1, 2]
TREES = 3


def _load(name: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    """benchmark/reference/gbdt_cat.py, loaded by file (benchmark/ holds a
    `trace.py` and a `data.py`, so it is never put on the path)."""
    names = ("modeltext", "reference", "reference.gbdt",
             "reference.gbdt_quant", "reference.gbdt_cat")
    had = {name: sys.modules.get(name) for name in names}
    _load("modeltext", BENCH / "modeltext.py")
    package = types.ModuleType("reference")
    package.__path__ = [str(BENCH / "reference")]
    sys.modules["reference"] = package
    _load("reference.gbdt", BENCH / "reference" / "gbdt.py")
    _load("reference.gbdt_quant", BENCH / "reference" / "gbdt_quant.py")
    yield _load("reference.gbdt_cat", BENCH / "reference" / "gbdt_cat.py")
    for name, module in had.items():
        if module is None:
            sys.modules.pop(name, None)
        else:
            sys.modules[name] = module


def _table(rng, n: int, unseen: bool) -> tuple:
    w = 1.0 / np.arange(1, 301) ** 1.1
    big = rng.choice(300, n, p=w / w.sum())
    small = rng.integers(0, 3, n)
    mid = rng.integers(0, 20, n).astype(np.float64)
    x1, x2 = rng.standard_normal(n), rng.random(n)
    eff = np.random.default_rng(38).standard_normal(300)
    logit = eff[big] + (small == 1) + 0.5 * np.sin(mid) + x1 - 0.4
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float64)
    mid[rng.random(n) < 0.02] = np.nan
    if unseen:
        big = np.where(rng.random(n) < 0.02, 1000 + rng.integers(0, 5, n),
                       big)
        mid[rng.random(n) < 0.02] = 77
    X = np.column_stack([small, big, mid, x1, x2]).astype(np.float32)
    return X, y


@pytest.fixture(scope="module")
def sets() -> dict:
    rng = np.random.default_rng(2026)
    return {"train": _table(rng, 20000, False),
            "test": _table(rng, 4000, True)}


def _train(monkeypatch, sets, interpret=False, trees=TREES):
    """lgb.train on the device learner with the test set evaluated every
    tree; what the benchmark's kind hands the reference, taken as it takes
    it; the booster."""
    if interpret:
        monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setenv("LGBM_TPU_HIST_F32", "1")
    monkeypatch.setattr(serial_mod, "on_tpu", lambda: True)
    X, y = sets["train"]
    ds = lgb.Dataset(X, label=y, categorical_feature=CATS)
    test = lgb.Dataset(sets["test"][0], label=sets["test"][1], reference=ds)
    handed = {"train_scores": [], "test_scores": [], "auc": []}

    def after(env):
        g = env.model._gbdt
        handed["train_scores"].append(np.asarray(g.score[0]))
        handed["test_scores"].append(np.asarray(g.valid_sets[0].score[0]))
        handed["auc"].append(float(env.evaluation_result_list[0][2]))
    after.order = 100

    bst = lgb.train(PARAMS, ds, num_boost_round=trees, valid_sets=[test],
                    valid_names=["test"], callbacks=[after])
    assert type(bst._gbdt.tree_learner).__name__ == "DeviceTreeLearner"
    return handed, bst


def _readings(ref, sets, handed, bst) -> dict:
    trees, bin_maps = ref.parse_model(bst.model_to_string())
    return ref.follow(sets, trees, bin_maps, PARAMS, handed, TREES)


def _failed(readings: dict) -> list:
    return sorted(k for k, v in readings.items()
                  if not v <= LIMITS[k])


@pytest.fixture(scope="module")
def sound(sets):
    mp = pytest.MonkeyPatch()
    try:
        yield _train(mp, sets)
    finally:
        mp.undo()


def test_the_data_works_both_lanes_bin_0_and_the_uint16_plane(sound):
    _, bst = sound
    core = bst._gbdt.train_set
    bins = [core.mappers[f].num_bin for f in CATS]
    assert bins[0] <= PARAMS["max_cat_to_onehot"] < bins[2]
    # most of the 300 categories binned, past the uint8 plane's 256 bins;
    # the rest past the cut in bin 0
    assert 256 < bins[1] < 300
    plane = np.asarray(bst._gbdt.tree_learner.bins_dev)
    assert plane.dtype == np.uint16
    group = core.feature_to_group[1][0]
    assert 0 < np.mean(plane[group] == 0) < 0.02


def test_device_trees_follow_the_plain_reference(ref, sets, sound):
    handed, bst = sound
    readings = _readings(ref, sets, handed, bst)
    assert _failed(readings) == [], readings
    trees, _ = ref.parse_model(bst.model_to_string())
    n_cat = sum(s is not None for t in trees for s in t.cat_sets)
    assert n_cat >= 5
    # one-hot and many-vs-many splits both taken
    sizes = {len(s) for t in trees for s in t.cat_sets if s is not None}
    assert 1 in sizes and max(sizes) > 1


def test_the_interpreted_kernels_follow_the_reference_too(
        ref, sets, monkeypatch):
    jax.clear_caches()  # the switch is read as the program is traced
    try:
        handed, bst = _train(monkeypatch, sets, interpret=True)
        assert bst._gbdt.tree_learner.hist_operand == "bf16x3"
        assert _failed(_readings(ref, sets, handed, bst)) == []
    finally:
        jax.clear_caches()


def test_the_tree_notes_count_categorical_splits(sound):
    from lightgbm_tpu import tracing
    from lightgbm_tpu.utils.timer import global_timer

    _, bst = sound
    notes = [n for n in tracing.recorder().snapshot()
             if n["kind"] == "tree_wave" and "cat_splits" in n]
    assert notes and all(n["cat_splits"] <= n["committed"] for n in notes)
    cats = [n for n in notes if n["cat_splits"]]
    assert cats and all(n["cat_left_bins"] >= n["cat_splits"] for n in cats)
    assert global_timer.counters.get("device_cat_splits", 0) > 0
    assert global_timer.counters.get("device_cat_trees", 0) > 0


def _rewritten(module, name: str, replacements: list):
    """`module.name` recompiled with lines of its source replaced: a fault
    planted in the program for a control, nothing else changed."""
    src = textwrap.dedent(inspect.getsource(getattr(module, name)))
    for old, new in replacements:
        assert old in src, old
        src = src.replace(old, new)
    namespace = dict(vars(module))
    exec(compile(src, module.__file__, "exec"), namespace)
    return namespace[name]


CONTROLS = {
    # (b) categorical bins routed by the numerical bin <= k
    "numerical_route": (device_mod, "_cat_go_left", [(
        "return jnp.where(cat_rows[0] > 0.5, bit > 0, go_left_num)",
        "return go_left_num")]),
    # (c) the ctr without cat_smooth
    "unsmoothed": (split_mod, "per_feature_best_categorical", [(
        "g / (h + cat_smooth)", "g / h")]),
    # (d) cat_l2 dropped
    "no_cat_l2": (split_mod, "per_feature_best_categorical", [(
        "l2c = l2 + cat_l2", "l2c = l2")]),
    # (e) the left set shifted one place along the ctr order
    "shifted": (split_mod, "per_feature_best_categorical", [
        ("jnp.where(lane == 1, 0, used - k))",
         "jnp.where(lane == 1, 1, used - k - 1))"),
        ("jnp.where(lane == 1, k, used))",
         "jnp.where(lane == 1, k + 1, used - 1))")]),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_every_control_fails_a_limit(ref, sets, monkeypatch, control):
    module, name, replacements = CONTROLS[control]
    monkeypatch.setattr(module, name, _rewritten(module, name, replacements))
    jax.clear_caches()  # no program of the sound runs is reused
    try:
        handed, bst = _train(monkeypatch, sets)
        failed = _failed(_readings(ref, sets, handed, bst))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert failed, control


def _lowered(learner, n: int) -> str:
    import jax.numpy as jnp

    return device_mod.grow_tree_on_device.lower(
        jnp.zeros(learner.bins_dev.shape, learner.bins_dev.dtype),
        jnp.zeros((n, 3), jnp.float32), jnp.zeros(n, jnp.int32),
        learner.meta, learner.tables, learner.params_dev,
        jnp.ones(len(learner.meta.real_feature), bool),
        learner.config.num_leaves, learner.group_bin_padded,
        learner.config.max_depth, quantized=False, scale_vec=None,
        batch=learner.wave_k, bagged=False).as_text(debug_info=True)


def test_a_numerical_data_set_lowers_no_categorical_lane(sets):
    """The five numerical cells' program: the route's [9, K] field matrix
    and no lgbm.cat_* scope; the categorical data set's program adds the
    [1 + W, K] one under lgbm.cat_route and lgbm.cat_scan (W the 16-bit
    words of the widest categorical column's bins)."""
    X, y = sets["train"]
    cfg = Config(dict(PARAMS, num_leaves=15))
    k = device_mod.WAVE_K if cfg.num_leaves > device_mod.WAVE_K else 15
    num = device_mod.DeviceTreeLearner(
        cfg, CoreDataset.from_matrix(X[:, 3:], label=y, config=cfg))
    text = _lowered(num, len(y))
    assert "lgbm.cat_" not in text
    cat = device_mod.DeviceTreeLearner(
        cfg, CoreDataset.from_matrix(X, label=y, config=cfg,
                                     categorical_feature=CATS))
    words = 1 + split_mod.cat_words(cat.meta.max_bins)
    assert words == 18  # ~270 bins
    assert f"tensor<9x{k}xf32>" in text
    assert f"tensor<{words}x{k}xf32>" not in text
    text = _lowered(cat, len(y))
    assert "lgbm.cat_route" in text and "lgbm.cat_scan" in text
    assert f"tensor<9x{k}xf32>" in text and f"tensor<{words}x{k}xf32>" in text


def test_a_saved_categorical_model_predicts_as_the_reference_traverses(
        ref, sets, sound, tmp_path):
    _, bst = sound
    path = tmp_path / "cat.txt"
    bst.save_model(str(path))
    Xt = sets["test"][0]
    got = lgb.Booster(model_file=str(path)).predict(Xt, raw_score=True)
    trees, _ = ref.parse_model(path.read_text())
    want = sum(t.leaf_value[ref.route(t, Xt.astype(np.float64))]
               for t in trees)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        got, bst.predict(Xt, raw_score=True), rtol=0, atol=0)


def test_auc_against_the_plain_reference_and_its_failing_control(ref):
    """The metric `auc_gap` reads: one jitted program against the float64
    count, on 100,000 scores with many ties; the metric with tied
    negatives given full credit fails the limit."""
    from lightgbm_tpu.metrics import binary as binary_mod

    rng = np.random.default_rng(7)
    y = (rng.random(100000) < 0.2).astype(np.float64)
    score = np.round(rng.standard_normal(100000) + y, 2).astype(np.float32)
    args = (jax.numpy.asarray(score), jax.numpy.asarray(y, np.float32), None)
    want = ref.auc(score, y)
    assert abs(float(binary_mod._auc(*args)) - want) <= LIMITS["auc_gap"]
    planted = _rewritten(binary_mod, "_auc_body", [(
        "credit = neg_before_seg + 0.5 * neg_in_seg",
        "credit = neg_before_seg + neg_in_seg")])
    assert abs(float(jax.jit(planted)(*args)) - want) > LIMITS["auc_gap"]


def test_categorical_binning_is_the_mapper_s_lookup_row_by_row(sets):
    """The vectorized categorical `values_to_bins` against the mapper's own
    `categorical_2_bin.get(int(v), 0)` a row at a time: NaN, negative
    values, fractions, categories past the cut and unseen ones."""
    import math

    X, y = sets["train"]
    core = CoreDataset.from_matrix(X, label=y, config=Config(PARAMS),
                                   categorical_feature=CATS)
    mapper = core.mappers[1]
    col = np.concatenate([sets["test"][0][:, 1].astype(np.float64),
                          [np.nan, -1.0, -3.5, 2.7, 1e6, 0.0, 299.0]])
    want = [0 if math.isnan(v) else mapper.categorical_2_bin.get(int(v), 0)
            for v in col]
    np.testing.assert_array_equal(mapper.values_to_bins(col), want)


def test_a_mapper_that_bins_other_categories_fails_the_map_check(
        ref, sets, sound, monkeypatch):
    """bin_map_mismatch holds the model's category-to-bin map to the one
    the reference works out from the raw column: the mapper's 99 % cut
    planted at 98 % bins fewer airports, and the check sees it."""
    from lightgbm_tpu.io import binning

    handed, bst = sound
    src = textwrap.dedent(inspect.getsource(binning.BinMapper.find_bin))
    old = "(total_sample_cnt - na_cnt) * 0.99)"
    assert old in src
    namespace = dict(vars(binning))
    exec(compile(src.replace(old, "(total_sample_cnt - na_cnt) * 0.98)"),
                 binning.__file__, "exec"), namespace)
    monkeypatch.setattr(binning.BinMapper, "find_bin", namespace["find_bin"])
    X, y = sets["train"]
    planted = CoreDataset.from_matrix(X, label=y, config=Config(PARAMS),
                                      categorical_feature=CATS)
    model = [planted.mappers[f].bin_2_categorical if f in CATS else None
             for f in range(X.shape[1])]
    trees, stated = ref.parse_model(bst.model_to_string())
    assert stated[1] != model[1]
    readings = ref.follow(sets, trees, model, PARAMS, handed, 1)
    assert readings["bin_map_mismatch"] > LIMITS["bin_map_mismatch"]
    assert ref.follow(sets, trees, stated, PARAMS, handed, 1)[
        "bin_map_mismatch"] == 0


def test_the_validation_pack_grows_with_the_tree_s_words_not_the_codes(
        monkeypatch):
    """Category codes in the millions: each per-tree
    validation pack holds the tree's own bitset words rounded up to a power
    of two (at least gbdt.VALID_CAT_WORDS), not num_leaves - 1 times the
    words of the largest code, and scores the test set as predict does.
    The pack is the fallback of a set without a plane (one not binned with
    the training set's mappers) and of a tree read from model text; the
    set here is given none."""
    from lightgbm_tpu.models import gbdt as gbdt_mod

    monkeypatch.setattr(gbdt_mod.GBDT, "_valid_plane", lambda self, v: None)

    rng = np.random.default_rng(5)
    X, y = _table(rng, 5000, False)
    Xt = _table(rng, 1000, True)[0]
    X[:, 1] *= 10000
    Xt[:, 1] *= 10000
    packs = []
    real = gbdt_mod.pack_ensemble

    def counted(trees, **kw):
        packed = real(trees, **kw)
        packs.append((len(trees[0].cat_threshold), packed.cat_words.shape[0]))
        return packed
    gbdt_mod.pack_ensemble = counted
    try:
        ds = lgb.Dataset(X, label=y, categorical_feature=CATS)
        test = lgb.Dataset(Xt, label=y[:1000], reference=ds)
        bst = lgb.train(dict(PARAMS, num_leaves=7), ds, num_boost_round=2,
                        valid_sets=[test], valid_names=["test"])
    finally:
        gbdt_mod.pack_ensemble = real
    assert len(packs) == 2
    for own, held in packs:
        assert own > gbdt_mod.VALID_CAT_WORDS  # codes up to 2,990,000
        assert own <= held < 2 * own and held & (held - 1) == 0
    np.testing.assert_allclose(
        np.asarray(bst._gbdt.valid_sets[0].score[0]),
        bst.predict(Xt, raw_score=True), rtol=1e-6, atol=1e-6)
