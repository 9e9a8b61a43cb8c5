"""A categorical run scores its validation sets in bin space
(models/gbdt.py `_update_valid_scores`, ops/score.py `replay_tree_to_score`):
each in-process tree's splits replayed over the set's binned plane, rows on
the lanes. The scores must be the numbers the one-tree pack's `predict_raw`
over the raw matrix gives, bit for bit, after every tree: the decisions are
LightGBM's bin-space ones, the add the same float32 add of the same float32
leaf value.

The data holds categorical columns (NaN, negative codes, codes the training
set never saw and rare codes the mapper put in bin 0 in the validation
set), numerical columns with and without NaN, and two sparse numerical
columns that never hold a value in the same row, which EFB bundles into one
group; a variant switches zero_as_missing on, another gives a categorical
column 300 categories so that the plane is uint16. Values are float32, so
that the raw matrix holds the values the mappers binned.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import tracing
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.ops.predict import pack_ensemble, predict_raw
from lightgbm_tpu.ops.score import (SPLIT_TABLE_FIELDS, replay_tree_to_score,
                                    split_table)
from lightgbm_tpu.utils.timer import global_timer

PARAMS = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.3,
          "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1,
          "min_data_per_group": 5, "cat_smooth": 1, "max_cat_to_onehot": 4,
          "verbosity": -1}
CATS = [0, 1, 2]
TREES = 4


def _table(rng, n: int, big: int, valid: bool) -> tuple:
    small = rng.integers(0, 6, n).astype(np.float64)
    wide = rng.integers(0, big, n).astype(np.float64)
    mid = rng.integers(0, 30, n).astype(np.float64)
    rare = rng.random(n) < 0.01  # codes seen once or twice: bin 0
    mid[rare] = 31 + rng.integers(0, 40, int(rare.sum()))
    num_nan = rng.standard_normal(n)
    num_nan[rng.random(n) < 0.05] = np.nan
    num = np.round(rng.standard_normal(n), 1)
    num[rng.random(n) < 0.2] = 0.0
    # two sparse columns never nonzero in one row: one EFB bundle
    owner = rng.random(n)
    sp_a = np.where(owner < 0.08, rng.integers(1, 9, n), 0.0)
    sp_b = np.where(owner > 0.92, rng.integers(1, 9, n), 0.0)
    logit = (np.sin(wide) + (small == 2) - 0.1 * mid / 3 + np.nan_to_num(
        num_nan) + 0.5 * num + 0.3 * (sp_a - sp_b))
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float64)
    if valid:
        wide[rng.random(n) < 0.03] = 5000  # unseen
        mid[rng.random(n) < 0.03] = np.nan
        small[rng.random(n) < 0.03] = -3  # negative
        num[rng.random(n) < 0.03] = np.nan  # NaN where training had none
    X = np.column_stack([small, wide, mid, num_nan, num, sp_a, sp_b])
    return X.astype(np.float32), y


def _sets(big: int) -> dict:
    rng = np.random.default_rng(39)
    return {"train": _table(rng, 3000, big, False),
            "valid": _table(rng, 1200, big, True)}


def _raw_update(vd, tree, before, class_id, cfg):
    """What the one-tree pack over the raw matrix adds: the fallback's
    path."""
    packed = pack_ensemble([tree], fixed_leaves=cfg.num_leaves,
                           fixed_depth=cfg.num_leaves - 1)
    return gbdt_mod._add_valid_delta(jnp.asarray(before),
                                     predict_raw(packed, vd.raw), class_id)


@pytest.fixture
def checked(monkeypatch):
    """Every validation update of the run, held to the raw path's numbers
    bit for bit; returns the list of (binned, tree) it saw."""
    real = gbdt_mod.GBDT._update_valid_scores
    seen = []

    def update(self, tree, class_id):
        before = [np.asarray(vd.score) for vd in self.valid_sets]
        n0 = tracing.recorder().total
        real(self, tree, class_id)
        notes = [r for r in tracing.recorder().snapshot()
                 if r["kind"] == "valid_update" and r["seq"] >= n0]
        assert len(notes) == len(self.valid_sets)
        for vd, b, note in zip(self.valid_sets, before, notes):
            want = _raw_update(vd, tree, b, class_id, self.config)
            assert np.array_equal(np.asarray(vd.score), np.asarray(want))
            assert note["rows"] == vd.raw.shape[0]
            assert note["splits"] == tree.num_leaves - 1
            seen.append((note["binned"], tree))
    monkeypatch.setattr(gbdt_mod.GBDT, "_update_valid_scores", update)
    return seen


def _train(sets, params, rounds=TREES, cats=CATS, **kw):
    X, y = sets["train"]
    ds = lgb.Dataset(X, label=y, categorical_feature=cats)
    valid = lgb.Dataset(sets["valid"][0], label=sets["valid"][1],
                        reference=ds)
    return lgb.train(dict(PARAMS, **params), ds, num_boost_round=rounds,
                     valid_sets=[valid], valid_names=["valid"], **kw)


@pytest.mark.parametrize("big,params,plane", [
    (40, {}, np.uint8),
    (40, {"zero_as_missing": True}, np.uint8),
    (300, {}, np.uint16),
], ids=["nan", "zero_as_missing", "uint16_plane"])
def test_binned_update_equals_the_raw_traversal_bit_for_bit(
        checked, big, params, plane):
    bst = _train(_sets(big), params)
    g = bst._gbdt
    vd = g.valid_sets[0]
    assert vd.plane is not None and vd.plane.dtype == plane
    assert vd.plane.shape == (g.train_set.num_groups, 1200)
    # the sparse pair is one bundle: the EFB translation is exercised
    assert any(grp.is_multi for grp in g.train_set.groups)
    assert len(checked) == TREES and all(b == 1 for b, _ in checked)
    assert any((t.decision_type[:t.num_leaves - 1] & 1).any()
               for _, t in checked)
    # after the last tree too: the set's scores are Booster.predict's
    np.testing.assert_allclose(np.asarray(vd.score[0]),
                               bst.predict(_sets(big)["valid"][0],
                                           raw_score=True), rtol=1e-6,
                               atol=1e-6)


def test_the_mapper_s_bin_zero_goes_right():
    """Codes the mapper put in bin 0 (NaN, negative, unseen, rare) are in
    no left set: the table's words never hold bit 0 of a categorical
    node, and the validation set has rows in bin 0 of every categorical
    column."""
    sets = _sets(40)
    bst = _train(sets, {})
    g = bst._gbdt
    fields, n_words = g._split_fields
    k = len(SPLIT_TABLE_FIELDS)
    plane = np.asarray(g.valid_sets[0].plane)
    for f in CATS:
        assert (plane[g.train_set.feature_to_group[f][0]] == 0).any()
    for tree in g.models:
        table = split_table(tree, fields, n_words, PARAMS["num_leaves"] - 1)
        cat = table[:, 9] == 1
        assert cat.any()
        assert not (table[cat, k] & 1).any()


def test_the_split_table_names_each_node_s_split_leaf():
    """Node i split the leftmost leaf of its subtree; replayed in creation
    order the table sends every training row to the leaf the learner's
    partition put it in."""
    sets = _sets(40)
    bst = _train(sets, {}, rounds=1)
    g = bst._gbdt
    tree = g.models[0]
    fields, n_words = g._split_fields
    table = split_table(tree, fields, n_words, 14)
    assert table.shape == (14, len(SPLIT_TABLE_FIELDS) + n_words)
    assert table[0, 10] == 0
    for i in range(tree.num_leaves - 1):
        for child, side in ((tree.left_child[i], 0), (tree.right_child[i], 1)):
            if child >= 0:
                want = table[i, 10] if side == 0 else i + 1
                assert table[child, 10] == want
    # each row's leaf id as its value
    score = replay_tree_to_score(
        jnp.zeros((1, g.num_data), jnp.float32),
        jnp.asarray(g.train_set.bins), jnp.asarray(table),
        np.int32(tree.num_leaves - 1), jnp.arange(15, dtype=jnp.float32), 0)
    leaf = np.asarray(score[0]).astype(np.int64)
    counts = np.bincount(leaf, minlength=tree.num_leaves)
    np.testing.assert_array_equal(counts[:tree.num_leaves],
                                  tree.leaf_count[:tree.num_leaves])


@pytest.mark.parametrize("boosting", ["dart", "rf"])
def test_dart_and_rf_take_the_path_and_agree(checked, boosting):
    params = {"boosting": boosting, "drop_rate": 0.5, "skip_drop": 0.0}
    if boosting == "rf":
        params = {"boosting": "rf", "bagging_fraction": 0.7,
                  "bagging_freq": 1}
    _train(_sets(40), params, rounds=5)
    assert any(b == 1 for b, _ in checked)
    assert all(b == 1 for b, t in checked if t.num_leaves > 1)


def test_rollback_takes_the_path_and_restores_the_scores(checked):
    sets = _sets(40)
    bst = _train(sets, {}, rounds=3)
    g = bst._gbdt
    before = np.asarray(g.valid_sets[0].score)
    bst.update()
    n = len(checked)
    bst.rollback_one_iter()
    assert [b for b, _ in checked[n:]] == [1]
    np.testing.assert_allclose(np.asarray(g.valid_sets[0].score), before,
                               rtol=1e-6, atol=1e-6)


def _moved(fn) -> tuple:
    """The valid_update notes `fn` stamped, and how far it moved the
    counters of the two paths."""
    names = ("valid_binned_updates", "predict_gather_calls",
             "predict_dense_calls")
    was = [global_timer.counters[k] for k in names]
    n0 = tracing.recorder().total
    fn()
    notes = [r for r in tracing.recorder().snapshot()
             if r["kind"] == "valid_update" and r["seq"] >= n0]
    return notes, [global_timer.counters[k] - w for k, w in zip(names, was)]


def test_a_tree_read_from_model_text_takes_the_raw_path():
    bst = _train(_sets(40), {}, rounds=2)
    g = bst._gbdt
    loaded = lgb.Booster(model_str=bst.model_to_string())._gbdt.models[1]
    assert g.models[1].has_bin_thresholds and not loaded.has_bin_thresholds
    start = np.asarray(g.valid_sets[0].score)
    notes, moved = _moved(lambda: g._update_valid_scores(loaded, 0))
    assert [n["binned"] for n in notes] == [0] and moved == [0, 1, 0]
    by_text = np.asarray(g.valid_sets[0].score)
    g.valid_sets[0].score = jnp.asarray(start)
    notes, moved = _moved(lambda: g._update_valid_scores(g.models[1], 0))
    assert [n["binned"] for n in notes] == [1] and moved == [1, 0, 0]
    np.testing.assert_array_equal(np.asarray(g.valid_sets[0].score), by_text)


def test_a_numerical_run_keeps_predict_raw():
    """A run with neither a categorical feature nor a bundle (the sparse
    pair kept apart: enable_bundle off) scores its set by the one-tree
    pack."""
    sets = _sets(40)
    notes, moved = _moved(lambda: _train(sets, {"enable_bundle": False},
                                         cats=[]))
    assert [n["binned"] for n in notes] == [0] * TREES
    assert moved == [0, TREES, 0]  # off the TPU a numerical pack traverses


def test_a_bundled_numerical_run_takes_the_bin_space_update(checked):
    """The same numerical run with its sparse pair bundled: the set takes
    every tree in bin space, bit for bit the raw traversal's numbers, its
    table carrying no bitset word; its raw matrix is placed only for the
    check's own traversal."""
    sets = _sets(40)
    notes, moved = _moved(lambda: _train(sets, {}, cats=[]))
    assert [n["binned"] for n in notes] == [1] * TREES
    assert moved[0] == TREES and moved[2] == 0
    assert [b for b, _ in checked] == [1] * TREES
    g = checked[0][1]
    assert g.num_leaves > 1


def test_a_sparse_bundled_set_is_never_made_dense():
    """A CSR validation set of a bundled run keeps its entries on the host:
    every tree takes the bin-space update, so no raw matrix is placed."""
    import scipy.sparse as sp

    sets = _sets(40)
    X, y = sets["train"]
    ds = lgb.Dataset(sp.csr_matrix(X[:, 3:]), label=y)
    valid = lgb.Dataset(sp.csr_matrix(sets["valid"][0][:, 3:]),
                        label=sets["valid"][1], reference=ds)
    bst = lgb.train(PARAMS, ds, num_boost_round=2, valid_sets=[valid])
    vd = bst._gbdt.valid_sets[0]
    assert vd.plane is not None and vd._raw is None
    np.testing.assert_allclose(
        np.asarray(vd.score[0]),
        bst.predict(sets["valid"][0][:, 3:], raw_score=True),
        rtol=1e-6, atol=1e-6)


def test_a_linear_tree_falls_back(checked):
    _train(_sets(40), {"linear_tree": True}, rounds=2)
    assert checked and all(b == 0 for b, _ in checked)


# ------------------------------------------------------------------- layout
# Rows on the lanes, as the tree program's operands are: a per-row
# array [Nv, k] would sit on the sublanes, 1 lane of 128 in use, which is
# what the gather traversal's [N, T = 1] arrays cost.

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("plane_dtype", [jnp.uint8, jnp.uint16])
def test_the_update_program_keeps_rows_on_the_lanes(plane_dtype):
    nv, groups, words = 1000, 7, 9
    k = len(SPLIT_TABLE_FIELDS) + words
    jaxpr = jax.make_jaxpr(replay_tree_to_score, static_argnums=(5,))(
        jnp.zeros((1, nv), jnp.float32), jnp.zeros((groups, nv), plane_dtype),
        jnp.zeros((254, k), jnp.int32), np.int32(3),
        jnp.zeros(255, jnp.float32), 0).jaxpr
    eqns = list(_eqns(jaxpr))
    assert any(e.primitive.name == "while" for e in eqns)
    for eqn in eqns:
        if eqn.primitive.name == "gather":
            assert nv not in eqn.invars[1].aval.shape, eqn
        for v in (*eqn.invars, *eqn.outvars):
            shape = getattr(v.aval, "shape", ())
            if nv in shape:
                assert shape[-1] == nv and len(shape) <= 2, (eqn, shape)


# ---------------------------------------------------------------------- AUC
# The other program of the test set's evaluation: one stable sort carries
# the labels and weights, the tie segments are read off prefix sums by
# scans; no row-sized gather or scatter.

def test_auc_sorts_once_and_gathers_nothing():
    from lightgbm_tpu.metrics import binary

    n = 1000
    jaxpr = jax.make_jaxpr(binary._auc)(jnp.zeros(n), jnp.zeros(n), None)
    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert names.count("sort") == 1
    assert not {"gather", "scatter", "scatter-add"} & set(names)


@pytest.mark.parametrize("weighted", [False, True])
def test_auc_gives_tied_negatives_half_credit(weighted):
    from lightgbm_tpu.metrics import binary

    rng = np.random.default_rng(7)
    n = 5000
    s = np.round(rng.standard_normal(n), 1).astype(np.float32)  # ties
    y = (rng.random(n) < 0.3).astype(np.float32)
    w = rng.random(n).astype(np.float32) if weighted else np.ones(n,
                                                                  np.float32)
    neg, pos = w * (1 - y), w * y
    credit = np.array([neg[s < v].sum() + 0.5 * neg[s == v].sum()
                       for v in s], dtype=np.float64)
    want = (pos * credit).sum() / (pos.sum() * neg.sum())
    got = float(binary._auc(jnp.asarray(s), jnp.asarray(y),
                            jnp.asarray(w) if weighted else None))
    assert abs(got - want) < 1e-6
