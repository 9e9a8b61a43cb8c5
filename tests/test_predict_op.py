import jax
import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import tracing
from lightgbm_tpu.common import MISSING_ZERO, K_ZERO_THRESHOLD
from lightgbm_tpu.models.tree import Tree, MISSING_NONE, MISSING_NAN
from lightgbm_tpu.ops import predict as predict_mod
from lightgbm_tpu.ops.predict import pack_ensemble, predict_raw, predict_leaf_indices
from lightgbm_tpu.utils.log import LightGBMError
from lightgbm_tpu.utils.timer import SPAN_PREDICT_TRAVERSE, global_timer
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


# ------------------------------------------- dense (gather-free) evaluation
#
# The TPU's program for numerical forests, reached here on the CPU by
# answering on_tpu() for pack_ensemble (the pack decides which program
# scores it) and calling the dense program directly. The gather traversal
# is the reference: leaf membership must agree exactly.

@pytest.fixture
def as_on_tpu(monkeypatch):
    monkeypatch.setattr(predict_mod, "on_tpu", lambda: True)


def _random_tree(rng, num_leaves, n_features, missing_types=(MISSING_NONE,),
                 max_leaves=None):
    """A seeded numerical tree: the leaf to split, the feature, the
    threshold, the missing type and the default side all drawn."""
    t = Tree(max_leaves=max_leaves or max(num_leaves, 2))
    if num_leaves == 1:
        t.as_constant_tree(float(rng.randn()))
        return t
    for _ in range(num_leaves - 1):
        t.split(leaf=int(rng.randint(t.num_leaves)), feature_inner=0,
                real_feature=int(rng.randint(n_features)), threshold_bin=1,
                threshold_double=float(rng.randn()),
                default_left=bool(rng.randint(2)),
                missing_type=int(missing_types[rng.randint(
                    len(missing_types))]),
                gain=1.0, left_value=float(rng.randn()),
                right_value=float(rng.randn()), left_count=1, right_count=1,
                left_weight=1.0, right_weight=1.0, parent_value=0.0)
    return t


def _dense_leaves(packed, X):
    """[N, T] leaf per row per tree from the dense program's path match,
    the whole forest as one block; asserts one real leaf matches."""
    T, Lp, Ip = packed.path.shape

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, Ip - a.shape[1])))

    match = np.asarray(predict_mod._dense_leaf_match(
        jnp.asarray(X).T, padded(packed.split_feature),
        padded(packed.threshold), padded(packed.decision_type),
        packed.path, packed.path_depth))  # [T, Lp, N]
    assert (match.sum(axis=1) == 1).all()
    real = np.arange(Lp)[None, :, None] < np.asarray(
        packed.num_leaves)[:, None, None]
    assert not (match & ~real).any()
    return match.argmax(axis=1).T


_FOREST_SHAPES = {
    "ragged": dict(leaves=[2, 3, 7, 15, 31, 6]),
    "stump_among": dict(leaves=[1, 9, 1, 4]),
    "stump_alone": dict(leaves=[1]),
    "padded_nodes": dict(leaves=[5, 33]),  # I = 32 -> Ip = 32, L = 33 -> Lp = 64
    "fixed_leaves": dict(leaves=[7], fixed_leaves=31, fixed_depth=12),
    "deep_chain": dict(leaves=[40]),
}


@pytest.mark.parametrize("shape", sorted(_FOREST_SHAPES))
def test_dense_leaf_membership_equals_the_traversal(rng, as_on_tpu, shape):
    spec = _FOREST_SHAPES[shape]
    trees = [_random_tree(rng, n, 6, (MISSING_NONE, MISSING_ZERO, MISSING_NAN))
             for n in spec["leaves"]]
    packed = pack_ensemble(trees, fixed_leaves=spec.get("fixed_leaves", 0),
                           fixed_depth=spec.get("fixed_depth", 0))
    assert packed.dense
    X = rng.randn(300, 6).astype(np.float32)
    X[rng.rand(300, 6) < 0.1] = np.nan
    X[rng.rand(300, 6) < 0.1] = 0.0
    want = np.asarray(predict_mod._traverse_leaves(packed, jnp.asarray(X)))
    np.testing.assert_array_equal(_dense_leaves(packed, X), want)


def _ulp_neighbours(v):
    v = np.float32(v)
    return [np.nextafter(v, np.float32(-np.inf)), v,
            np.nextafter(v, np.float32(np.inf))]


@pytest.mark.parametrize("default_left", [False, True])
@pytest.mark.parametrize("missing_type",
                         [MISSING_NONE, MISSING_ZERO, MISSING_NAN])
def test_dense_decisions_agree_on_special_values(as_on_tpu, missing_type,
                                                 default_left):
    """NaN, the infinities, both zeros, the zero band's edge and the values
    one ulp either side of each threshold, through a three-node tree whose
    thresholds include one float32 cannot hold."""
    thresholds = [0.5, 1.0000001 + 1e-12, -1e-35]
    tree = Tree(max_leaves=4)
    leaf = 0
    for k, thr in enumerate(thresholds):
        leaf = tree.split(leaf, 0, 0, 1, thr, default_left, missing_type,
                          1.0, float(k + 1), float(-k - 1), 1, 1, 1.0, 1.0,
                          0.0)
    packed = pack_ensemble([tree])
    assert packed.dense
    values = [np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-35, -1e-35]
    for thr in np.asarray(packed.threshold)[0]:
        values += _ulp_neighbours(thr)
    values += _ulp_neighbours(K_ZERO_THRESHOLD) + _ulp_neighbours(
        -K_ZERO_THRESHOLD)
    X = np.array(values, dtype=np.float32)[:, None]
    want = np.asarray(predict_mod._traverse_leaves(packed, jnp.asarray(X)))
    np.testing.assert_array_equal(_dense_leaves(packed, X), want)
    # and the scores carry them: bit-equal where one tree is summed
    np.testing.assert_array_equal(
        np.asarray(predict_mod._predict_raw_dense(packed, jnp.asarray(X), 1)),
        np.asarray(predict_mod._predict_raw_fused(packed, jnp.asarray(X), 1)))


@pytest.mark.parametrize("case", ["one_tree_bit_equal", "forest",
                                  "multiclass_3", "ragged_row_block",
                                  "tree_blocks", "no_rows"])
def test_dense_scores_equal_the_fused_traversal(rng, as_on_tpu, monkeypatch,
                                                case):
    n_trees = {"one_tree_bit_equal": 1, "multiclass_3": 12}.get(case, 10)
    C = 3 if case == "multiclass_3" else 1
    n = {"ragged_row_block": 333, "tree_blocks": 150,
         "no_rows": 0}.get(case, 256)
    if case == "ragged_row_block":  # three 128-row chunks, the last 77 rows
        monkeypatch.setattr(predict_mod, "_DENSE_ROW_CHUNK", 128)
    if case == "tree_blocks":  # three blocks of 4 trees, the last 2 padding
        monkeypatch.setattr(predict_mod, "_DENSE_STEP_ELEMS", 4 * 32 * 256)
    trees = [_random_tree(rng, int(rng.randint(2, 30)), 5,
                          (MISSING_NONE, MISSING_NAN))
             for _ in range(n_trees)]
    packed = pack_ensemble(trees)
    X = rng.randn(n, 5).astype(np.float32)
    X[rng.rand(n, 5) < 0.05] = np.nan
    # jit caches by the function: the patched block sizes need a fresh trace
    dense = jax.jit(predict_mod._predict_raw_dense.__wrapped__,
                    static_argnames=("num_tree_per_iteration",))
    got = np.asarray(dense(packed, jnp.asarray(X), num_tree_per_iteration=C))
    want = np.asarray(predict_mod._predict_raw_fused(packed, jnp.asarray(X), C))
    assert got.shape == want.shape == (n, C)
    if case == "one_tree_bit_equal":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_tree_slice_of_a_dense_pack(rng, as_on_tpu):
    trees = [_random_tree(rng, int(rng.randint(2, 20)), 4) for _ in range(9)]
    packed = pack_ensemble(trees)
    sl = packed.tree_slice(3, 7)
    assert sl.dense and sl.num_trees == 4
    assert sl.path.shape == (4,) + packed.path.shape[1:]
    assert sl.path_depth.shape == (4, packed.path_depth.shape[1])
    X = jnp.asarray(rng.randn(64, 4).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(predict_mod._predict_raw_dense(sl, X, 1)),
        np.asarray(predict_mod._predict_raw_fused(
            pack_ensemble(trees[3:7]), X, 1)), rtol=0, atol=1e-6)
    # the aux data follows the slice through a pytree round trip
    leaves, treedef = jax.tree_util.tree_flatten(sl)
    assert jax.tree_util.tree_unflatten(treedef, leaves).dense


def test_path_tables_of_the_simple_tree():
    """f0 <= 0.5 -> leaf0; else f1 <= 2.5 -> leaf1 else leaf2."""
    t = make_simple_tree()
    path, depth = predict_mod.path_tables(
        t.left_child[None, :2], t.right_child[None, :2],
        np.array([3], dtype=np.int32), Lp=4, Ip=4)
    assert path[0].tolist() == [[1, 0, 0, 0], [-1, 1, 0, 0], [-1, -1, 0, 0],
                                [0, 0, 0, 0]]
    assert depth[0].tolist() == [1.0, 2.0, 2.0, np.inf]


# ----------------------------------------------- which program scores a pack

def _categorical_pack():
    return pack_ensemble([_nan_cat_tree(), make_simple_tree()])


def _linear_pack():
    t = make_simple_tree()
    t.is_linear = True
    t.leaf_const = np.array(t.leaf_value[: t.max_leaves], dtype=np.float64)
    t.leaf_coeff = [[0.5], [], []] + [[]] * (t.max_leaves - 3)
    t.leaf_features = [[0], [], []] + [[]] * (t.max_leaves - 3)
    return pack_ensemble([t])


def _over_the_byte_bound_pack(monkeypatch):
    monkeypatch.setattr(predict_mod, "DENSE_PATH_BYTES_MAX", 3 * 32 * 32 - 1)
    return pack_ensemble([make_simple_tree() for _ in range(3)])


def _cpu_pack(monkeypatch):
    monkeypatch.undo()  # on_tpu() answers for this process again: the CPU
    return pack_ensemble([make_simple_tree()])


_GATHER_PACKS = {
    "one_categorical_node": lambda mp: _categorical_pack(),
    "linear_leaves": lambda mp: _linear_pack(),
    "over_the_byte_bound": _over_the_byte_bound_pack,
    "cpu_backend": _cpu_pack,
}


def _run_and_read_the_choice(packed, X):
    tracing.recorder().reset()
    before = {k: global_timer.counters[k]
              for k in ("predict_dense_calls", "predict_gather_calls")}
    out = predict_raw(packed, X)
    notes = [r for r in tracing.recorder().snapshot()
             if r["kind"] == SPAN_PREDICT_TRAVERSE]
    assert len(notes) == 1
    assert notes[0]["rows"] == X.shape[0]
    assert notes[0]["trees"] == packed.num_trees
    moved = {k: global_timer.counters[k] - v for k, v in before.items()}
    return out, notes[0]["dense"], moved


@pytest.mark.parametrize("why", sorted(_GATHER_PACKS))
def test_a_pack_the_dense_program_cannot_serve_takes_the_gather_path(
        as_on_tpu, monkeypatch, why):
    packed = _GATHER_PACKS[why](monkeypatch)
    assert not packed.dense and packed.path is None
    X = jnp.asarray(np.array([[0.0, 0.0], [1.0, 2.0], [1.0, 3.0]],
                             dtype=np.float32))
    _, dense, moved = _run_and_read_the_choice(packed, X)
    assert dense == 0
    assert moved == {"predict_dense_calls": 0, "predict_gather_calls": 1}


def test_a_numerical_pack_on_the_tpu_takes_the_dense_path(rng, as_on_tpu):
    trees = [make_simple_tree() for _ in range(3)]
    packed = pack_ensemble(trees)
    assert packed.dense
    assert predict_mod.fused_program(packed) is predict_mod._predict_raw_dense
    X = rng.uniform(-1, 5, size=(64, 2)).astype(np.float32)
    out, dense, moved = _run_and_read_the_choice(packed, jnp.asarray(X))
    assert dense == 1
    assert moved == {"predict_dense_calls": 1, "predict_gather_calls": 0}
    expected = np.array([[sum(t.predict(row) for t in trees)] for row in X])
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)


@pytest.mark.parametrize("entry", ["aot", "sharded"])
def test_the_other_entries_run_the_packs_own_program(rng, as_on_tpu, entry):
    """The AOT bundle's executable and the row-sharded call lower
    `fused_program(packed)`: a dense pack's answers come from the dense
    program there too, and equal the traversal's."""
    trees = [_random_tree(rng, int(rng.randint(2, 20)), 4) for _ in range(6)]
    packed = pack_ensemble(trees)
    assert packed.dense
    X = rng.randn(64, 4).astype(np.float32)
    if entry == "aot":
        compiled = predict_mod.aot_compile(packed, 64, 4, 1)
        assert "lgbm.path_match" in compiled.as_text()
        got = np.asarray(compiled(packed, jnp.asarray(X)))
    else:
        from lightgbm_tpu.parallel.predict import predict_raw_sharded

        got = predict_raw_sharded(packed, X, 1)
    want = np.asarray(predict_mod._predict_raw_fused(packed, jnp.asarray(X), 1))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
