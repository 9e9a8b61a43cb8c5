"""The seeded forest is the stated size and goes through the program's
normal loader; the plain reference agrees with the program on it."""
import numpy as np

import data
import modeltext
from reference import forest as reference


def test_seeded_forest_is_500_by_255_and_round_trips():
    import lightgbm_tpu as lgb

    text = data.make_forest(2 ** 31 + 11, 500, 255, 28, 0.1, 20)
    assert text == data.make_forest(2 ** 31 + 11, 500, 255, 28, 0.1, 20)
    trees = modeltext.parse_model(text)
    assert len(trees) == 500
    assert all(t.num_leaves == 255 for t in trees)
    assert max(int(t.depths()[1].max()) for t in trees) == 20
    assert all(t.leaf_count.sum() == t.internal_count[0] for t in trees)
    bst = lgb.Booster(model_str=text)
    assert bst.num_trees() == 500
    again = modeltext.parse_model(bst.model_to_string())
    assert all(np.array_equal(a.threshold, b.threshold)
               and np.array_equal(a.left_child, b.left_child)
               and np.array_equal(a.leaf_value, b.leaf_value)
               for a, b in zip(trees, again))
    X = data.make_rows(512, 28, 5)
    want = reference.predict_proba(trees, X)
    assert np.max(np.abs(bst.predict(X) - want)) < 1e-5


def test_lower_precision_reference_is_told_apart():
    """The control at test size: the reference with its leaf values and its
    running score in bfloat16 reads far over any limit a float32 run
    needs."""
    import ml_dtypes

    trees = modeltext.parse_model(data.make_forest(3, 60, 31, 28, 0.1, 8))
    X = data.make_rows(1024, 28, 3)
    want = reference.predict_proba(trees, X)
    low = reference.predict_proba(trees, X, dtype=ml_dtypes.bfloat16)
    assert np.max(np.abs(low - want)) > 1e-3


def test_a_seed_reorders_the_training_data_and_redraws_nothing():
    Xa, ya = data.make_data(512, 28, seed=2 ** 31 + 1, data_seed=7)
    Xb, yb = data.make_data(512, 28, seed=2 ** 31 + 2, data_seed=7)
    assert not np.array_equal(Xa, Xb)
    assert np.array_equal(np.sort(Xa, axis=1), np.sort(Xb, axis=1))
    assert np.array_equal(ya, yb)
    Xc, yc = data.make_data(512, 28, seed=2 ** 31 + 1, data_seed=7)
    assert np.array_equal(Xa, Xc) and np.array_equal(ya, yc)
