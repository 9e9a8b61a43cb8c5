"""End-to-end training tests: the v0 demo slice.

Mirrors the reference's golden-threshold strategy (tests/distributed/
_test_distributed.py asserts accuracy >= thresholds on known data; the
examples/ configs are the fixtures)."""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def make_synthetic(n=2000, f=10, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f))
    logit = 1.5 * X[:, 0] - 2.0 * X[:, 1] + X[:, 2] * X[:, 3]
    y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(np.float64)
    return X, y


def auc_np(y, p):
    order = np.argsort(p)
    y = y[order]
    n_pos = y.sum()
    n_neg = len(y) - n_pos
    ranks = np.arange(1, len(y) + 1)
    return (ranks[y > 0].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


def test_binary_synthetic_train_auc():
    X, y = make_synthetic()
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1},
                    ds, num_boost_round=30)
    pred = bst.predict(X)
    assert pred.min() >= 0 and pred.max() <= 1
    auc = auc_np(y, pred)
    assert auc > 0.97, f"train AUC too low: {auc}"


def test_binary_valid_and_early_stopping():
    X, y = make_synthetic(3000)
    Xtr, ytr, Xv, yv = X[:2000], y[:2000], X[2000:], y[2000:]
    ds = lgb.Dataset(Xtr, label=ytr)
    dv = lgb.Dataset(Xv, label=yv, reference=ds)
    record = {}
    bst = lgb.train({"objective": "binary", "metric": "auc,binary_logloss",
                     "num_leaves": 15, "verbosity": -1},
                    ds, num_boost_round=40, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(record)])
    assert "valid_0" in record
    assert len(record["valid_0"]["auc"]) == 40
    assert record["valid_0"]["auc"][-1] > 0.9
    # logloss should improve over training
    assert record["valid_0"]["binary_logloss"][-1] < record["valid_0"]["binary_logloss"][0]


def test_model_save_load_predict_consistency(tmp_path):
    X, y = make_synthetic(1000)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                    ds, num_boost_round=10)
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    bst2 = lgb.Booster(model_file=path)
    p1 = bst.predict(X[:100])
    p2 = bst2.predict(X[:100])
    np.testing.assert_allclose(p1, p2, rtol=1e-5)
    # host-side tree predict agrees with device path
    model = lgb.GBDTModel.from_file(path)
    import math
    for i in range(5):
        raw_host = sum(t.predict(X[i]) for t in model.trees)
        p_host = 1.0 / (1.0 + math.exp(-raw_host))
        assert abs(p_host - p1[i]) < 1e-4


def test_reference_example_binary_auc(examples):
    """Train from files in the reference example's format (label-first
    TSV, 7,000 x 28 train, 500 test; tests/conftest.py `examples`); the AUC
    threshold mirrors the distributed-test accuracy gates."""
    ds = lgb.Dataset(str(examples / "binary.train"),
                     params={"header": False})
    dv = lgb.Dataset(str(examples / "binary.test"), reference=ds)
    rec = {}
    bst = lgb.train({"objective": "binary", "metric": "auc", "num_leaves": 31,
                     "learning_rate": 0.1, "verbosity": -1},
                    ds, num_boost_round=50, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(rec)])
    auc = rec["valid_0"]["auc"][-1]
    # On the seeded files an untrained model reads 0.5, the first tree
    # alone 0.784 and 50 rounds 0.8527: 0.80 asks for more than one tree's
    # worth of learning with a margin of 0.05. (The reference's own
    # binary.train, a 7k-row HIGGS subset, was held to the same 0.80.)
    assert auc > 0.80, f"reference-example AUC too low: {auc}"
    assert auc > rec["valid_0"]["auc"][0] + 0.03


def test_regression_l2():
    rng = np.random.RandomState(3)
    X = rng.uniform(-3, 3, size=(2000, 5))
    y = X[:, 0] ** 2 + 2 * np.sin(X[:, 1]) + rng.normal(scale=0.1, size=2000)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "regression", "num_leaves": 31, "verbosity": -1},
                    ds, num_boost_round=50)
    pred = bst.predict(X)
    mse = float(np.mean((pred - y) ** 2))
    var = float(np.var(y))
    assert mse < 0.1 * var, f"mse {mse} vs var {var}"


def test_custom_objective_fobj():
    X, y = make_synthetic(1000)
    ds = lgb.Dataset(X, label=y)

    def logloss_obj(preds, train_data):
        labels = train_data.get_label()
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - labels, p * (1 - p)

    # objective 'none' without fobj must fail like the reference
    with pytest.raises(lgb.LightGBMError):
        lgb.train({"objective": "none", "num_leaves": 7, "verbosity": -1},
                  ds, num_boost_round=2)
    # custom objective through params callable
    ds2 = lgb.Dataset(X, label=y)
    bst2 = lgb.train({"objective": logloss_obj, "num_leaves": 7, "verbosity": -1},
                     ds2, num_boost_round=20)
    raw = bst2.predict(X, raw_score=True)
    auc = auc_np(y, raw)
    assert auc > 0.95


def test_predict_start_iteration(rng):
    """start_iteration slices the ensemble (Booster.predict parity with
    python-package predict(start_iteration=...))."""
    X = rng.randn(800, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=6)
    full = bst.predict(X, raw_score=True)
    head = bst.predict(X, raw_score=True, num_iteration=2)
    tail = bst.predict(X, raw_score=True, start_iteration=2)
    np.testing.assert_allclose(head + tail, full, rtol=1e-5, atol=1e-6)
    mid = bst.predict(X, raw_score=True, start_iteration=2, num_iteration=2)
    last = bst.predict(X, raw_score=True, start_iteration=4)
    np.testing.assert_allclose(head + mid + last, full, rtol=1e-5, atol=1e-6)
