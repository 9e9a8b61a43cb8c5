"""Categorical split tests (FindBestThresholdCategoricalInner parity,
feature_histogram.cpp:147-241)."""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _cat_data(n=2000, n_cats=12, seed=9):
    rng = np.random.RandomState(seed)
    cats = rng.randint(0, n_cats, size=n)
    # category effect: a few categories strongly positive
    effect = np.where(np.isin(cats, [2, 5, 7]), 2.0, -1.0)
    X = np.column_stack([cats.astype(np.float64), rng.randn(n)])
    y = (effect + 0.3 * X[:, 1] + rng.randn(n) * 0.3 > 0).astype(np.float64)
    return X, y, cats


def test_categorical_sorted_subset_split():
    X, y, cats = _cat_data()
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "min_data_in_leaf": 20, "verbosity": -1},
                    ds, num_boost_round=15)
    acc = np.mean((bst.predict(X) > 0.5) == y)
    assert acc > 0.85, acc
    # the model must contain at least one categorical split
    dumped = bst.dump_model()

    def has_cat(node):
        if "split_feature" in node:
            return (node["decision_type"] == "==" or
                    has_cat(node["left_child"]) or has_cat(node["right_child"]))
        return False

    assert any(has_cat(t["tree_structure"]) for t in dumped["tree_info"])


def test_categorical_onehot_split():
    # few categories -> one-hot path (max_cat_to_onehot default 4)
    rng = np.random.RandomState(3)
    cats = rng.randint(0, 3, size=1500)
    y = (cats == 1).astype(np.float64)
    X = np.column_stack([cats.astype(np.float64), rng.randn(1500)])
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 4,
                     "verbosity": -1}, ds, num_boost_round=10)
    acc = np.mean((bst.predict(X) > 0.5) == y)
    assert acc > 0.99, acc


def test_categorical_model_roundtrip(tmp_path):
    X, y, _ = _cat_data()
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, ds, num_boost_round=8)
    pred = bst.predict(X)
    path = str(tmp_path / "cat.txt")
    bst.save_model(path)
    re_pred = lgb.Booster(model_file=path).predict(X)
    np.testing.assert_allclose(re_pred, pred, rtol=1e-5, atol=1e-6)


def test_categorical_unseen_category_goes_right():
    X, y, _ = _cat_data()
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, ds, num_boost_round=8)
    X_unseen = X[:5].copy()
    X_unseen[:, 0] = 999  # never-seen category
    out = bst.predict(X_unseen)
    assert np.isfinite(out).all()


def test_categorical_score_consistency():
    """Internal train score must equal fresh prediction (partition decisions
    and stored bitsets agree)."""
    X, y, _ = _cat_data(n=1200)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, ds, num_boost_round=6)
    internal = np.asarray(bst._gbdt.score[0])
    fresh = bst.predict(X, raw_score=True)
    np.testing.assert_allclose(internal, fresh, rtol=1e-4, atol=1e-4)


def test_categorical_with_numerical_mix():
    rng = np.random.RandomState(5)
    n = 2000
    cats = rng.randint(0, 8, size=n)
    x1 = rng.randn(n)
    y = ((np.isin(cats, [1, 3]) & (x1 > 0)) | (x1 > 1.5)).astype(np.float64)
    X = np.column_stack([x1, cats.astype(np.float64), rng.randn(n)])
    ds = lgb.Dataset(X, label=y, categorical_feature=[1])
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, ds, num_boost_round=20)
    acc = np.mean((bst.predict(X) > 0.5) == y)
    assert acc > 0.93, acc


@pytest.mark.parametrize("boosting", ["gbdt", "rf"])
def test_bagged_out_of_bag_rows_follow_the_categorical_sets(boosting):
    """Under bagging the out-of-bag rows' training scores come from the
    bin-space traversal of each tree (ops/score.py add_tree_to_score):
    categorical nodes route them by the bit of their bin in the left set,
    as prediction does by category, never numerically by bin <= k."""
    X, y, _ = _cat_data(n=4000, n_cats=20)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "boosting": boosting, "bagging_fraction": 0.5,
                     "bagging_freq": 1, "min_data_per_group": 5,
                     "cat_smooth": 1, "verbosity": -1},
                    ds, num_boost_round=5)
    g = bst._gbdt
    assert any((t.decision_type[:t.num_leaves - 1] & 1).any()
               for t in g.models)
    np.testing.assert_allclose(np.asarray(g.score[0]),
                               bst.predict(X, raw_score=True),
                               rtol=1e-5, atol=1e-5)
