import jax
import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.common import MISSING_ZERO, K_ZERO_THRESHOLD
from lightgbm_tpu.models.tree import Tree, MISSING_NONE, MISSING_NAN
from lightgbm_tpu.ops.predict import pack_ensemble, predict_raw, predict_leaf_indices
from lightgbm_tpu.utils.log import LightGBMError
from tests.test_tree import make_simple_tree


# --------------------------------------------------------------- reference
# Verbatim copy of the pre-fusion per-tree traversal (one vmap lane per
# tree, one X gather per tree per level): the bit-identity oracle for the
# fused level-synchronous path.

def _ref_tree_leaf_index(packed, tree_idx, X, max_depth):
    sf = packed.split_feature[tree_idx]
    th = packed.threshold[tree_idx]
    dt = packed.decision_type[tree_idx]
    lc = packed.left_child[tree_idx]
    rc = packed.right_child[tree_idx]
    co = packed.cat_offset[tree_idx]
    cn = packed.cat_n_words[tree_idx]
    n = X.shape[0]
    single_leaf = packed.num_leaves[tree_idx] <= 1

    def body(_, node):
        active = node >= 0
        nd = jnp.maximum(node, 0)
        feat = sf[nd]
        fval = jnp.take_along_axis(X, feat[:, None], axis=1)[:, 0]
        d = dt[nd]
        is_cat = (d & 1) > 0
        default_left = (d & 2) > 0
        missing_type = (d >> 2) & 3
        is_nan = jnp.isnan(fval)
        fval_num = jnp.where(is_nan & (missing_type != MISSING_NAN), 0.0, fval)
        is_missing = ((missing_type == MISSING_ZERO)
                      & (jnp.abs(fval_num) <= K_ZERO_THRESHOLD)) | (
            (missing_type == MISSING_NAN) & jnp.isnan(fval_num))
        go_left_num = jnp.where(is_missing, default_left, fval_num <= th[nd])
        int_fval = jnp.where(is_nan, -1, fval.astype(jnp.int32))
        word_idx = jnp.clip(int_fval, 0, None) // 32
        bit_idx = jnp.clip(int_fval, 0, None) % 32
        in_range = (int_fval >= 0) & (word_idx < cn[nd])
        word = packed.cat_words[jnp.clip(co[nd] + word_idx, 0,
                                         packed.cat_words.shape[0] - 1)]
        go_left_cat = in_range & (((word >> bit_idx.astype(jnp.uint32)) & 1) > 0)
        go_left = jnp.where(is_cat, go_left_cat, go_left_num)
        nxt = jnp.where(go_left, lc[nd], rc[nd])
        return jnp.where(active, nxt, node)

    node0 = jnp.zeros(n, dtype=jnp.int32)
    node = jax.lax.fori_loop(0, max_depth, body, node0)
    return jnp.where(single_leaf, 0, ~node)


def _ref_predict_raw(packed, X, num_tree_per_iteration=1):
    T = packed.num_trees
    if T == 0:
        return np.zeros((X.shape[0], num_tree_per_iteration), dtype=X.dtype)

    def tree_score(k):
        leaf = _ref_tree_leaf_index(packed, k, X, packed.max_depth)
        base = packed.leaf_value[k][leaf]
        if not packed.linear:
            return base
        feats = packed.lin_feat[k][leaf]
        used = feats >= 0
        fv = jnp.take_along_axis(X, jnp.clip(feats, 0, X.shape[1] - 1), axis=1)
        bad = (used & ~jnp.isfinite(fv)).any(axis=1)
        fv = jnp.where(used, fv, 0.0)
        lin = packed.lin_const[k][leaf] + jnp.where(
            used, packed.lin_coeff[k][leaf] * fv, 0.0).sum(axis=1)
        return jnp.where(bad, base, lin)

    scores = jax.vmap(tree_score)(jnp.arange(T, dtype=jnp.int32))
    scores = scores.reshape(T // num_tree_per_iteration,
                            num_tree_per_iteration, X.shape[0])
    return np.asarray(scores.sum(axis=0).T)


def _nan_cat_tree():
    t = Tree(max_leaves=3)
    right = t.split(leaf=0, feature_inner=0, real_feature=0, threshold_bin=1,
                    threshold_double=0.5, default_left=True,
                    missing_type=MISSING_NAN, gain=1.0, left_value=-1.0,
                    right_value=1.0, left_count=1, right_count=1,
                    left_weight=1.0, right_weight=1.0, parent_value=0.0)
    t.split_categorical(leaf=right, feature_inner=1, real_feature=1,
                        bin_bitset=[0b110], value_bitset=[0b110],
                        missing_type=MISSING_NONE, gain=1.0,
                        left_value=5.0, right_value=7.0, left_count=1,
                        right_count=1, left_weight=1.0, right_weight=1.0,
                        parent_value=1.0)
    return t


def test_packed_matches_host_predict(rng):
    trees = [make_simple_tree() for _ in range(3)]
    trees[1].shrink(0.5)
    packed = pack_ensemble(trees)
    X = rng.uniform(-1, 5, size=(64, 2)).astype(np.float32)
    out = np.asarray(predict_raw(packed, jnp.asarray(X)))
    expected = np.array([[sum(t.predict(row) for t in trees)] for row in X])
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_packed_handles_nan_and_categorical(rng):
    t = Tree(max_leaves=3)
    right = t.split(leaf=0, feature_inner=0, real_feature=0, threshold_bin=1,
                    threshold_double=0.5, default_left=True, missing_type=MISSING_NAN,
                    gain=1.0, left_value=-1.0, right_value=1.0, left_count=1, right_count=1,
                    left_weight=1.0, right_weight=1.0, parent_value=0.0)
    t.split_categorical(leaf=right, feature_inner=1, real_feature=1,
                        bin_bitset=[0b110], value_bitset=[0b110],
                        missing_type=MISSING_NONE, gain=1.0,
                        left_value=5.0, right_value=7.0, left_count=1, right_count=1,
                        left_weight=1.0, right_weight=1.0, parent_value=1.0)
    packed = pack_ensemble([t])
    X = np.array([
        [np.nan, 0.0],   # nan -> default left -> -1
        [1.0, 1.0],      # right, cat 1 in {1,2} -> 5
        [1.0, 2.0],      # -> 5
        [1.0, 3.0],      # -> 7
        [1.0, np.nan],   # cat nan -> right -> 7
    ], dtype=np.float32)
    out = np.asarray(predict_raw(packed, jnp.asarray(X)))[:, 0]
    np.testing.assert_allclose(out, [-1.0, 5.0, 5.0, 7.0, 7.0])
    host = np.array([t.predict(row) for row in X])
    np.testing.assert_allclose(out, host)


def test_multiclass_grouping(rng):
    # 2 iterations x 2 classes = 4 trees; class k sums trees k, k+2
    trees = []
    for v in (1.0, 10.0, 100.0, 1000.0):
        t = Tree(max_leaves=2)
        t.split(leaf=0, feature_inner=0, real_feature=0, threshold_bin=1,
                threshold_double=0.5, default_left=False, missing_type=MISSING_NONE,
                gain=1.0, left_value=v, right_value=-v, left_count=1, right_count=1,
                left_weight=1.0, right_weight=1.0, parent_value=0.0)
        trees.append(t)
    packed = pack_ensemble(trees)
    X = np.array([[0.0], [1.0]], dtype=np.float32)
    out = np.asarray(predict_raw(packed, jnp.asarray(X), num_tree_per_iteration=2))
    np.testing.assert_allclose(out, [[101.0, 1010.0], [-101.0, -1010.0]])


def test_leaf_indices(rng):
    trees = [make_simple_tree()]
    packed = pack_ensemble(trees)
    X = np.array([[0.0, 0.0], [1.0, 2.0], [1.0, 3.0]], dtype=np.float32)
    leaves = np.asarray(predict_leaf_indices(packed, jnp.asarray(X)))
    assert leaves[:, 0].tolist() == [0, 1, 2]


def test_stump_only_model():
    t = Tree(max_leaves=2)
    t.as_constant_tree(0.25)
    packed = pack_ensemble([t])
    X = np.zeros((4, 1), dtype=np.float32)
    out = np.asarray(predict_raw(packed, jnp.asarray(X)))
    np.testing.assert_allclose(out, 0.25)


# ------------------------------------- fused traversal bit-identity locks

def _trained_ensembles(rng):
    """(name, packed, X, C) across ensemble types: trained numerical with
    NaNs, hand-built categorical + NaN, trained multiclass, linear trees."""
    out = []
    Xn = rng.randn(400, 5).astype(np.float64)
    Xn[rng.rand(400, 5) < 0.1] = np.nan
    yn = (np.nan_to_num(Xn[:, 0]) + 0.5 * np.nan_to_num(Xn[:, 1]) > 0)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "use_missing": True},
                    lgb.Dataset(Xn, label=yn.astype(float)),
                    num_boost_round=8)
    out.append(("numerical_nan", bst._gbdt._packed(),
                Xn.astype(np.float32), 1))

    cat_trees = [_nan_cat_tree(), make_simple_tree()]
    Xc = np.array([[np.nan, 0.0], [1.0, 1.0], [1.0, 2.0], [1.0, 3.0],
                   [1.0, np.nan], [0.2, 1.5], [0.9, 2.5]], dtype=np.float32)
    out.append(("categorical_nan", pack_ensemble(cat_trees), Xc, 1))

    Xm = rng.randn(300, 4).astype(np.float64)
    ym = ((Xm[:, 0] > 0).astype(int) + (Xm[:, 1] > 0).astype(int)).astype(float)
    bm = lgb.train({"objective": "multiclass", "num_class": 3,
                    "num_leaves": 7, "verbosity": -1},
                   lgb.Dataset(Xm, label=ym), num_boost_round=5)
    out.append(("multiclass", bm._gbdt._packed(), Xm.astype(np.float32), 3))

    Xl = rng.rand(300, 3).astype(np.float64)
    yl = 2.0 * Xl[:, 0] - Xl[:, 1] + 0.1 * rng.randn(300)
    bl = lgb.train({"objective": "regression", "num_leaves": 7,
                    "linear_tree": True, "verbosity": -1},
                   lgb.Dataset(Xl, label=yl), num_boost_round=5)
    Xl32 = Xl.astype(np.float32).copy()
    Xl32[0, 1] = np.nan  # linear fallback-to-constant path
    out.append(("linear", bl._gbdt._packed(), Xl32, 1))
    return out


@pytest.mark.slow  # tier-1 budget triage: heavy full-training driver, runs in the slow tier
def test_fused_bit_identical_to_per_tree_reference(rng):
    for name, packed, X, C in _trained_ensembles(rng):
        got = np.asarray(predict_raw(packed, jnp.asarray(X), C))
        ref = _ref_predict_raw(packed, jnp.asarray(X), C)
        np.testing.assert_array_equal(got, ref, err_msg=name)


def test_fused_leaf_indices_bit_identical(rng):
    for name, packed, X, C in _trained_ensembles(rng):
        got = np.asarray(predict_leaf_indices(packed, jnp.asarray(X)))
        ref = np.stack([np.asarray(_ref_tree_leaf_index(
            packed, k, jnp.asarray(X), packed.max_depth))
            for k in range(packed.num_trees)], axis=1)
        np.testing.assert_array_equal(got, ref, err_msg=name)


def test_pallas_interpret_bit_identical(rng):
    from lightgbm_tpu.ops.predict_pallas import pallas_predict_raw

    for name, packed, X, C in _trained_ensembles(rng):
        if packed.linear:
            continue  # linear ensembles keep the XLA path
        got = np.asarray(pallas_predict_raw(packed, jnp.asarray(X), C,
                                            tile_rows=128, interpret=True))
        ref = np.asarray(predict_raw(packed, jnp.asarray(X), C))
        np.testing.assert_array_equal(got, ref, err_msg=name)


def test_pallas_env_flag_interprets_only_when_asked(rng, monkeypatch):
    # LGBM_TPU_PREDICT_PALLAS=1 routes predict_raw to the kernel; it runs
    # interpreted only under LGBM_TPU_PALLAS_INTERPRET=1. Off a TPU without
    # it the compiled kernel fails to lower, loudly — never a quiet
    # interpreted run under the kernel's name.
    monkeypatch.delenv("LGBM_TPU_PREDICT_PALLAS", raising=False)
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    name, packed, X, C = _trained_ensembles(rng)[0]
    ref = np.asarray(predict_raw(packed, jnp.asarray(X), C))
    monkeypatch.setenv("LGBM_TPU_PREDICT_PALLAS", "1")
    with pytest.raises(ValueError, match="interpret mode"):
        predict_raw(packed, jnp.asarray(X), C)
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    got = np.asarray(predict_raw(packed, jnp.asarray(X), C))
    np.testing.assert_array_equal(got, ref, err_msg=name)


def test_ragged_tree_count_is_fatal():
    trees = [make_simple_tree() for _ in range(5)]
    packed = pack_ensemble(trees)
    X = jnp.zeros((3, 2), dtype=jnp.float32)
    with pytest.raises(LightGBMError, match="whole iterations"):
        predict_raw(packed, X, num_tree_per_iteration=2)


def test_multiclass_partial_iteration_predict(rng):
    # num_iteration slicing on a multiclass booster: T = 2 iters * 3
    # classes; the slice must stay a whole-iteration multiple and match
    # the host sum over trees[:2*C]
    X = rng.randn(200, 4)
    y = ((X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)).astype(float)
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 7, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=4)
    C = 3
    out = bst.predict(X, raw_score=True, num_iteration=2)
    trees = bst._gbdt.models[: 2 * C]
    host = np.zeros((X.shape[0], C))
    for m, t in enumerate(trees):
        host[:, m % C] += [t.predict(row) for row in X]
    np.testing.assert_allclose(out, host, rtol=1e-5, atol=1e-6)


def test_predict_routes_f64_when_x64_enabled():
    # a threshold whose decision differs between f32 and f64 inputs: the
    # old forced-f32 upload sent both rows left; x64 callers must keep
    # their f64 values end to end
    x32 = np.float64(np.float32(1.0000001))
    t64 = x32 + 1e-12
    tree = Tree(max_leaves=2)
    tree.split(0, 0, 0, 1, t64, False, MISSING_NONE, 1.0, -1.0, 1.0,
               1, 1, 1.0, 1.0, 0.0)
    jax.config.update("jax_enable_x64", True)
    try:
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.models.gbdt import GBDT

        g = GBDT(Config({}), None, None)
        g.models = [tree]
        X = np.array([[x32], [t64 + 1e-12]], dtype=np.float64)
        out = g.predict(X, raw_score=True)
        assert out[0] == -1.0  # x32 <= t64 in f64
        assert out[1] == 1.0   # t64 + eps > t64: right — lost under f32
    finally:
        jax.config.update("jax_enable_x64", False)


def test_threshold_downcast_preserves_f32_decisions():
    import math
    # threshold not representable in f32, just above a representable value
    x = np.float32(1.0000001)
    t64 = float(x) + 1e-12  # x <= t64 in f64
    tree = Tree(max_leaves=2)
    tree.split(0, 0, 0, 1, t64, False, MISSING_NONE, 1.0, -1.0, 1.0, 1, 1, 1.0, 1.0, 0.0)
    packed = pack_ensemble([tree])
    X = jnp.asarray(np.array([[x], [np.nextafter(x, np.float32(2.0))]], dtype=np.float32))
    out = np.asarray(predict_raw(packed, X))[:, 0]
    assert out[0] == -1.0  # x <= t64 -> left, preserved after downcast
    assert out[1] == 1.0
