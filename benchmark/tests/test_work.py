"""The two work-count functions on trees small enough to count by hand."""
import numpy as np
import pytest

import work
from modeltext import PlainTree, parse_model, route


def three_leaf_tree() -> PlainTree:
    # node 0 splits 100 rows into leaf 0 (30) and node 1 (70);
    # node 1 splits its 70 into leaf 1 (60) and leaf 2 (10)
    return PlainTree(
        split_feature=np.array([0, 1]), threshold=np.array([0.0, 1.0]),
        left_child=np.array([~0, ~1]), right_child=np.array([1, ~2]),
        split_gain=np.array([5.0, 2.0]), internal_count=np.array([100, 70]),
        leaf_value=np.array([0.1, -0.2, 0.3]),
        leaf_count=np.array([30, 60, 10]))


def stump(n_left: int, n_right: int) -> PlainTree:
    return PlainTree(
        split_feature=np.array([0]), threshold=np.array([0.0]),
        left_child=np.array([~0]), right_child=np.array([~1]),
        split_gain=np.array([1.0]),
        internal_count=np.array([n_left + n_right]),
        leaf_value=np.array([1.0, -1.0]),
        leaf_count=np.array([n_left, n_right]))


def test_tree_rows_by_hand():
    tree = three_leaf_tree()
    # root 100, then the smaller child of each split: 30 and 10
    assert work.tree_hist_rows(tree) == 100 + 30 + 10
    # one routing pass over each split parent: 100 and 70
    assert work.tree_routed_rows(tree) == 170
    w = work.train_tree_work(tree, n_features=28, bin_bytes=1, gh_bytes=4,
                             operand="bf16")
    assert w.bytes == 140 * (28 + 8) + 170 * 5
    assert w.ops == 140 * 28 * 2 + 170


def test_depths_and_parents():
    tree = three_leaf_tree()
    node_depth, leaf_depth = tree.depths()
    assert node_depth.tolist() == [0, 1]
    assert leaf_depth.tolist() == [1, 2, 2]
    node_parent, leaf_parent = tree.parents()
    assert node_parent.tolist() == [-1, 0]
    assert leaf_parent.tolist() == [0, 1, 1]


def test_forest_visits_by_hand():
    forest = [three_leaf_tree(), stump(50, 50)]
    # tree 1: (30*1 + 60*2 + 10*2) / 100 = 1.7 visits a row; the stump: 1
    assert work.forest_visits_per_row(forest) == pytest.approx(2.7)
    w = work.predict_work(forest, rows=1000, n_features=28)
    assert w.bytes == pytest.approx(
        2700 * 20 + 2 * 1000 * 4 + 1000 * (28 * 4 + 4))
    assert w.ops == pytest.approx(2700 + 2000)


def test_route_matches_the_counts():
    tree = three_leaf_tree()
    X = np.array([[-1.0, 0.0], [1.0, 0.5], [1.0, 2.0], [0.0, 9.0]],
                 dtype=np.float32)
    assert route(tree, X).tolist() == [0, 1, 2, 0]


def test_least_seconds_names_its_bound():
    by_bytes = work.Work(bytes=819e9, ops=1.0, operand="bf16")
    assert work.least_seconds(by_bytes, "TPU v5 lite") == (1.0, "bytes")
    by_ops = work.Work(bytes=1.0, ops=393e12, operand="int8")
    assert work.least_seconds(by_ops, "TPU v5 lite") == (1.0, "ops")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks_for("_source")


def test_parse_refuses_categorical():
    text = ("tree\n\nTree=0\nnum_leaves=2\nnum_cat=1\nsplit_feature=0\n"
            "split_gain=1\nthreshold=0\ndecision_type=1\nleft_child=-1\n"
            "right_child=-2\nleaf_value=0 0\nleaf_count=1 1\n"
            "internal_count=2\n\nend of trees\n")
    with pytest.raises(ValueError):
        parse_model(text)
