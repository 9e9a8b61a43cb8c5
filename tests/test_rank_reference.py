"""`objective=lambdarank` through `lgb.train` held to the mathematics.

tests/test_ranking.py holds the ranking objectives to NDCG thresholds on
example files. This file compares lambdarank with the benchmark's plain
reference (benchmark/reference/lambdarank.py: numpy float64, a Python loop
over queries, nothing of the program): what the chip cell
`mslr_lambdarank.train` decides `correct` by, at test size: seeded ragged
queries of 1 to ~300 documents (some past the truncation level, one of a
single document, several whose grades are all equal), 136 features so that
the plane has five group blocks of 32, two validation sets, 15 leaves,
three trees, on the XLA bodies and on the kernel path interpreted.

The limits (the CPU twins of benchmark/traffic/train_window_rank.json's),
each between the sound runs' largest reading at this size (4,685 rows; the
XLA bodies and the interpreted kernels, whose three bfloat16 limbs hold a
float32 exactly, read the same to every digit) and the controls' smallest.
The reference takes a tree's gradients from the scores the program handed
over after the tree before, which `score_gap` has held to its own (its
docstring says why: LambdaRank is discontinuous where two scores cross):

  count_mismatch   0, exact: leaf and node counts by plain traversal
                   (sound 0; no control moves it);
  leaf_value_gap   2e-4: float32 pair terms, float32 histogram sums of
                   hundreds of lambdas of either sign and one float32
                   division against float64 (sound 1.4e-5; bfloat16
                   operands 1.6e-3; truncation ignored 4.3e-2; the discount
                   off by one 0.14; an unstable sort 0.26; the norm dropped
                   0.67; the last block's features dropped 7.7e-6: that tree
                   is a sound tree of the other 128 features);
  split_gain_gap   3e-4: a gain is a difference of float32 quotients of
                   such sums (sound 1.3e-5; bfloat16 1.5e-3; truncation
                   5.2e-2; discount 0.30; unstable 1.2; norm 9.1);
  split_shortfall  1e-3: no candidate beats the split taken by more than a
                   float32 near-tie (sound 3e-15; the last block's eight
                   features dropped from the histograms 1.12, the only
                   reading that sees it; truncation 5.1e-2; discount 0.20;
                   norm 1.8; unstable 2.2; bfloat16 3e-15);
  score_gap        1e-4: the program's float32 training scores against the
                   reference's own (sound 6.6e-6; bfloat16 7.7e-4;
                   truncation 1.4e-2; discount 6.6e-2; unstable 0.17; norm
                   0.31);
  valid_score_gap  1e-4: the validation scores by the packed one-tree
                   predictor in float32 against the reference's traversal
                   with its own leaf values (sound 6.4e-6; bfloat16 8.3e-4;
                   the others as score_gap);
  ndcg_gap         1e-5: NDCG@1, 3, 5, 10 of vali and test, counted by the
                   reference from the validation scores the program handed
                   over, against the program's values: the metric's float32
                   sums against float64 (sound 1.9e-7; the metric's own
                   discounts off by one position 0.27; the other controls
                   do not touch the metric).
"""
import importlib.util
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.metadata import Metadata
from lightgbm_tpu.metrics import create_metric
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.objectives import rank as rank_mod
from lightgbm_tpu.treelearner import device as device_mod
from lightgbm_tpu.treelearner import serial as serial_mod

BENCH = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
LIMITS = {"count_mismatch": 0, "leaf_value_gap": 2e-4,
          "split_gain_gap": 3e-4, "split_shortfall": 1e-3,
          "score_gap": 1e-4, "valid_score_gap": 1e-4, "ndcg_gap": 1e-5}
PARAMS = {"objective": "lambdarank", "num_leaves": 15, "max_bin": 255,
          "learning_rate": 0.1, "min_data_in_leaf": 0,
          "min_sum_hessian_in_leaf": 1.0, "lambdarank_truncation_level": 30,
          "lambdarank_norm": True, "metric": "ndcg",
          "eval_at": [1, 3, 5, 10], "verbosity": -1}
FEATURES, TREES = 136, 3
# float32 pair terms (exp, two divisions, a sum of up to 300 terms of
# either sign) against float64: 3e-7 of the largest lambda was read
GRADIENT_TOLERANCE = 5e-6


def _load(name: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def plain():
    """(modeltext, reference/lambdarank, work_rank) of the benchmark,
    loaded by file: benchmark/ holds a `trace.py` and a `data.py`, so it is
    never put on the path of the test process."""
    names = ("modeltext", "work", "work_rank", "reference", "reference.gbdt",
             "reference.gbdt_quant", "reference.lambdarank")
    had = {name: sys.modules.get(name) for name in names}
    modeltext = _load("modeltext", BENCH / "modeltext.py")
    _load("work", BENCH / "work.py")
    work_rank = _load("work_rank", BENCH / "work_rank.py")
    package = types.ModuleType("reference")
    package.__path__ = [str(BENCH / "reference")]
    sys.modules["reference"] = package
    _load("reference.gbdt", BENCH / "reference" / "gbdt.py")
    _load("reference.gbdt_quant", BENCH / "reference" / "gbdt_quant.py")
    ref = _load("reference.lambdarank", BENCH / "reference" / "lambdarank.py")
    yield modeltext, ref, work_rank
    for name, module in had.items():
        if module is None:
            sys.modules.pop(name, None)
        else:
            sys.modules[name] = module


def _sizes(rng, queries: int) -> np.ndarray:
    """Ragged: 1 to ~300 documents, one query of a single document, some
    past the truncation level of 30."""
    sizes = np.clip(np.rint(rng.lognormal(3.4, 0.9, queries)), 2,
                    300).astype(np.int64)
    sizes[0], sizes[1], sizes[2] = 1, 300, 31
    return sizes


def _set(seed: int, queries: int) -> tuple:
    """(X [n, 136] float32, grades [n] float64 0..4, sizes [Q]): normal,
    count and mostly-zero columns; grades from a noisy utility, with the
    queries 3 to 6 of one grade only."""
    rng = np.random.default_rng(seed)
    sizes = _sizes(rng, queries)
    n = int(sizes.sum())
    X = rng.standard_normal((n, FEATURES), dtype=np.float32)
    X[:, 1::8] = rng.poisson(3.0, (n, len(range(1, FEATURES, 8))))
    # mostly zero, a small count elsewhere: the binner puts these sparse
    # columns last in the plane, so the fifth group block holds eight of them
    sparse = range(3, FEATURES, 8)
    X[:, 3::8] = (1 + rng.poisson(1.0, (n, len(sparse)))) * (
        rng.random((n, len(sparse))) < 0.1)
    w = np.random.default_rng(34).standard_normal(FEATURES) * (
        np.random.default_rng(35).random(FEATURES) < 0.25)
    w[3::8] = 2.0  # every one of them informative
    utility = X @ w / 3.0 + rng.standard_normal(n)
    grades = np.searchsorted(np.quantile(utility, [0.51, 0.84, 0.97, 0.99]),
                             utility).astype(np.float64)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    for q, grade in zip(range(3, 7), (0.0, 0.0, 2.0, 4.0)):
        grades[bounds[q]:bounds[q + 1]] = grade
    return X, grades, sizes


@pytest.fixture(scope="module")
def sets() -> dict:
    return {"train": _set(1, 120), "vali": _set(2, 40), "test": _set(3, 40)}


def _metadata(grades, sizes) -> Metadata:
    md = Metadata(len(grades))
    md.set_label(grades)
    md.set_query(sizes)
    return md


# -------------------------------------------------------------- gradients


@pytest.mark.parametrize("extra", [
    {}, {"lambdarank_norm": False}, {"lambdarank_truncation_level": 5},
    {"sigmoid": 2.0}], ids=["default", "no_norm", "truncation5", "sigmoid2"])
def test_gradients_of_every_document_against_the_plain_reference(
        plain, sets, extra):
    _, ref, work_rank = plain
    _, grades, sizes = sets["train"]
    n = len(grades)
    rng = np.random.default_rng(4)
    # rounded to a tenth: most documents tie with another of their query
    score = np.round(rng.standard_normal(n), 1).astype(np.float32)
    obj = create_objective("lambdarank", Config(dict(PARAMS, **extra)))
    obj.init(_metadata(grades, sizes), n)
    for s in (score, np.zeros(n, np.float32)):  # and every document tied
        g, h = (np.asarray(a, dtype=np.float64)
                for a in obj.get_gradients(jnp.asarray(s)))
        want_g, want_h = ref.gradients(s, grades, sizes, dict(PARAMS, **extra))
        assert np.abs(g - want_g).max() <= (
            GRADIENT_TOLERANCE * np.abs(want_g).max())
        assert np.abs(h - want_h).max() <= (
            GRADIENT_TOLERANCE * np.abs(want_h).max())
    # a single document and one grade only: no pair, no gradient
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    for q in (0, 3, 4, 5, 6):
        assert not np.any(g[bounds[q]:bounds[q + 1]])
    truncation = dict(PARAMS, **extra)["lambdarank_truncation_level"]
    assert obj.pair_positions == work_rank.pair_positions(sizes, truncation)
    assert obj.pair_slots >= obj.pair_positions


def test_the_gradient_pass_is_one_program_an_iteration(sets):
    """The jaxpr of a gradient pass is one call of one jitted program: no
    eager gather, scatter or concatenate beside it; and three passes compile
    it once."""
    _, grades, sizes = sets["train"]
    n = len(grades)
    obj = create_objective("lambdarank", Config(PARAMS))
    obj.init(_metadata(grades, sizes), n)
    jaxpr = jax.make_jaxpr(obj.get_gradients)(jnp.zeros(n, jnp.float32))
    assert [eqn.primitive.name for eqn in jaxpr.eqns] in (["jit"], ["pjit"])
    inner = str(jaxpr.eqns[0].params["jaxpr"])
    assert "sort" in inner and "gather" in inner
    lowered = jax.jit(obj.get_gradients).lower(
        jnp.zeros(n, jnp.float32)).as_text(debug_info=True)
    for scope in ("rank_sort", "rank_pairs", "rank_scatter"):
        assert f"lgbm.gradients/lgbm.{scope}/" in lowered, scope
    for _ in range(3):
        obj.get_gradients(jnp.zeros(n, jnp.float32))
    assert obj._program._cache_size() == 1


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_ndcg_at_1_3_5_10_against_the_plain_reference(plain, sets, ties):
    _, ref, _ = plain
    _, grades, sizes = sets["vali"]
    n = len(grades)
    score = np.random.default_rng(5).standard_normal(n).astype(np.float32)
    if ties:
        score = np.round(score, 0)
    metric = create_metric("ndcg", Config(PARAMS))
    metric.init(_metadata(grades, sizes), n)
    got = metric.eval(jnp.asarray(score), None)
    want = ref.ndcg(score, grades, sizes, PARAMS["eval_at"])
    assert metric.name == ["ndcg@1", "ndcg@3", "ndcg@5", "ndcg@10"]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


# ---------------------------------------------------------- followed trees


def _readings(monkeypatch, plain, sets, extra=None, interpret=False,
              f32=True) -> tuple:
    """Three iterations through lgb.train on the device learner with both
    validation sets, what the benchmark's kind hands the reference taken as
    it takes it, then the reference's readings under PARAMS (a control
    trains under `extra`, the reference is told PARAMS); and the learner."""
    modeltext, ref, _ = plain
    if interpret:
        monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    if f32:
        monkeypatch.setenv("LGBM_TPU_HIST_F32", "1")
    else:
        monkeypatch.delenv("LGBM_TPU_HIST_F32", raising=False)
    # the device learners are for a TPU; the CPU answers for one here
    monkeypatch.setattr(serial_mod, "on_tpu", lambda: True)
    X, grades, sizes = sets["train"]
    ds = lgb.Dataset(X, label=grades, group=sizes)
    names = ["vali", "test"]
    valid = [lgb.Dataset(sets[k][0], label=sets[k][1], group=sets[k][2],
                         reference=ds) for k in names]
    handed = {"train_scores": [], "ndcg": {},
              "valid_scores": {k: [] for k in names}}

    def after(env):
        gbdt = env.model._gbdt
        handed["train_scores"].append(np.asarray(gbdt.score[0]))
        for k, vd in zip(names, gbdt.valid_sets):
            handed["valid_scores"][k].append(np.asarray(vd.score[0]))
        for k, metric, value, _ in env.evaluation_result_list:
            handed["ndcg"].setdefault(k, {}).setdefault(metric, []).append(
                value)

    bst = lgb.train(dict(PARAMS, **(extra or {})), ds, num_boost_round=TREES,
                    valid_sets=valid, valid_names=names, callbacks=[after])
    trees = modeltext.parse_model(bst.model_to_string())
    assert len(trees) == TREES
    assert all(t.num_leaves == PARAMS["num_leaves"] for t in trees)
    assert sorted(handed["ndcg"]["test"]) == sorted(
        f"ndcg@{k}" for k in PARAMS["eval_at"])
    return ref.follow(sets, trees, PARAMS, handed, TREES), \
        bst._gbdt.tree_learner


def _failed(readings: dict) -> list:
    """As the harness decides: a NaN fails."""
    return sorted(name for name, limit in LIMITS.items()
                  if not readings[name] <= limit)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla_bodies", "kernels_interpreted"])
def test_three_trees_followed_by_the_plain_reference(monkeypatch, plain,
                                                     sets, interpret):
    readings, learner = _readings(monkeypatch, plain, sets,
                                  interpret=interpret)
    assert type(learner) is device_mod.DeviceTreeLearner
    # 136 groups, padded to five blocks of 32 for the histogram kernel
    assert learner.bins_dev.shape[0] == FEATURES
    assert learner.hist_operand == ("bf16x3" if interpret else "xla")
    assert not _failed(readings), readings


# ------------------------------------------------------------ the controls


def test_control_truncation_ignored_fails(monkeypatch, plain, sets):
    readings, _ = _readings(monkeypatch, plain, sets,
                            {"lambdarank_truncation_level": 100000})
    assert readings["count_mismatch"] == 0.0
    for name in ("leaf_value_gap", "split_gain_gap", "valid_score_gap"):
        assert readings[name] > 10 * LIMITS[name], (name, readings)


def test_control_the_norm_dropped_fails(monkeypatch, plain, sets):
    readings, _ = _readings(monkeypatch, plain, sets,
                            {"lambdarank_norm": False})
    for name in ("leaf_value_gap", "split_gain_gap", "valid_score_gap"):
        assert readings[name] > 10 * LIMITS[name], (name, readings)


def test_control_the_discount_off_by_one_position_fails(monkeypatch, plain,
                                                        sets):
    monkeypatch.setattr(rank_mod, "discounts", lambda n: jnp.asarray(
        1.0 / np.log2(np.arange(n) + 3.0), dtype=jnp.float32))
    readings, _ = _readings(monkeypatch, plain, sets)
    for name in ("leaf_value_gap", "split_gain_gap"):
        assert readings[name] > 10 * LIMITS[name], (name, readings)


def test_control_the_metrics_discount_off_by_one_fails(monkeypatch, plain,
                                                      sets):
    """The NDCG program's own discounts shifted: the trees are sound, the
    values reported are not the scores' NDCG."""
    from lightgbm_tpu.metrics import rank as metric_mod

    monkeypatch.setattr(metric_mod, "discounts", lambda n: jnp.asarray(
        1.0 / np.log2(np.arange(n) + 3.0), dtype=jnp.float32))
    readings, _ = _readings(monkeypatch, plain, sets)
    assert _failed(readings) == ["ndcg_gap"], readings
    assert readings["ndcg_gap"] > 100 * LIMITS["ndcg_gap"]


def test_control_an_unstable_sort_fails(monkeypatch, plain, sets):
    """Equal scores ordered from the last row to the first: after the
    first tree every document of a leaf ties."""
    def last_first(key, *carried):
        within = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
        key_s, _, *ordered, order = jax.lax.sort(
            (key, -within) + carried + (within,), dimension=1, num_keys=2)
        by_rank = jnp.broadcast_to(
            rank_mod.discounts(key.shape[1])[None, :], key.shape)
        _, rank, disc = jax.lax.sort((order, within, by_rank), dimension=1,
                                     num_keys=1)
        return (key_s, *ordered, rank, disc)

    monkeypatch.setattr(rank_mod, "rank_documents", last_first)
    readings, _ = _readings(monkeypatch, plain, sets)
    for name in ("leaf_value_gap", "split_gain_gap"):
        assert readings[name] > 10 * LIMITS[name], (name, readings)


def test_control_the_last_group_blocks_features_dropped_fails(
        monkeypatch, plain, sets):
    """Features 128 to 135, the eight real groups of the fifth block, left
    out of every histogram: the tree is a sound tree of the other 128."""
    real = device_mod.build_histogram
    monkeypatch.setattr(
        device_mod, "build_histogram",
        lambda bins, gh, nb: real(bins, gh, nb).at[128:].set(0.0))
    device_mod.grow_tree_on_device.clear_cache()
    try:
        readings, _ = _readings(monkeypatch, plain, sets)
    finally:
        monkeypatch.undo()
        device_mod.grow_tree_on_device.clear_cache()
    assert "split_shortfall" in _failed(readings), readings
    assert readings["split_shortfall"] > 10 * LIMITS["split_shortfall"]


def test_control_bfloat16_histogram_operands_fail(monkeypatch, plain, sets):
    """The program's default path: the histogram's gradient operand as one
    bfloat16 limb (the configuration's `env` unset), interpreted."""
    readings, learner = _readings(monkeypatch, plain, sets, interpret=True,
                                  f32=False)
    assert learner.hist_operand == "bf16"
    failed = _failed(readings)
    assert "leaf_value_gap" in failed and "split_gain_gap" in failed, readings


def test_the_reference_imports_nothing_of_the_program():
    for name in ("lambdarank.py",):
        text = (BENCH / "reference" / name).read_text()
        imports = [ln for ln in text.splitlines()
                   if ln.startswith(("import ", "from "))]
        assert imports and not any("lightgbm_tpu" in ln or "jax" in ln
                                   for ln in imports), imports
