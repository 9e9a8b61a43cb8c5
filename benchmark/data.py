"""Everything the benchmark makes from --seed: training data, predict rows
and the seeded forest. `make_data` is `bench.py`'s generator, copied (the
original stays where it is until a later PR deletes it: PERF.md, Open
questions) so that no later PR can change the yardstick.
"""
from __future__ import annotations

import numpy as np


def make_data(n_rows: int, n_features: int, seed: int, data_seed: int):
    """Seeded synthetic binary data at HIGGS's widths: dense standard-normal
    f32 features, labels from a random linear logit plus unit noise.

    The rows, the logit and the labels come from the configuration's
    `data_seed`, the same in every run; `seed` draws the order of the
    columns after the first. So every seed gives other inputs and the same
    work: the trees of two seeds are each other's, feature for renamed
    feature. What moved the work, each repeatably (my chip runs, PR 25):
    fresh data from every seed, seconds per tree 4.38 to 4.53 over six
    seeds while one seed repeated to 0.03 %; the two classes' names
    swapped, +0.7 % (float32 rounds sigmoid(s) and sigmoid(-s) differently,
    and a near-tie falls the other way); and another feature in column 0,
    +0.7 % on three orders of thirteen: the learner takes the root's totals
    from column 0's histogram (`hist_totals`), so its float32 rounding, and
    with it a near-tie somewhere below, follows whichever feature sits
    there."""
    rng = np.random.default_rng(data_seed)
    X = rng.standard_normal((n_rows, n_features), dtype=np.float32)
    w = rng.standard_normal(n_features, dtype=np.float32)
    logit = X @ w
    noise = rng.standard_normal(n_rows, dtype=np.float32)
    tail = 1 + np.random.default_rng([seed, 0]).permutation(n_features - 1)
    X = np.ascontiguousarray(X[:, np.concatenate([[0], tail])])
    y = (logit + noise > 0).astype(np.float64)
    return X, y


def make_rows(n_rows: int, n_features: int, seed: int) -> np.ndarray:
    """Seeded predict rows: dense standard-normal f32."""
    rng = np.random.default_rng([seed, 1])
    return rng.standard_normal((n_rows, n_features), dtype=np.float32)


def _fmt(values, as_int: bool = False) -> str:
    if as_int:
        return " ".join(str(int(v)) for v in values)
    return " ".join(repr(float(v)) for v in values)


def _seeded_tree(rng, sample_t: np.ndarray, num_leaves: int, scale: float,
                 rows_stand_for: int, max_depth: int, spine: bool) -> str:
    """One leaf-wise tree in LightGBM's text format. The leaf to split is
    drawn in proportion to the sample rows it holds (a trained tree splits
    its large leaves first) among the leaves above `max_depth`, the feature
    uniformly, the threshold half way between two of the leaf's own sample
    values, so no child is empty. With `spine` the first `max_depth` splits
    go straight down (an eighth of the rows peeled off to the left each
    time), so that the tree is exactly `max_depth` deep.
    `sample_t` is feature-major [F, n]."""
    n_feat, n = sample_t.shape
    n_int = num_leaves - 1
    split_feature = np.zeros(n_int, dtype=np.int64)
    threshold = np.zeros(n_int, dtype=np.float64)
    left = np.zeros(n_int, dtype=np.int64)
    right = np.zeros(n_int, dtype=np.int64)
    internal_count = np.zeros(n_int, dtype=np.int64)
    leaf_parent = np.full(num_leaves, -1, dtype=np.int64)
    leaf_rows = [np.arange(n)] + [None] * (num_leaves - 1)
    sizes = np.zeros(num_leaves, dtype=np.float64)
    sizes[0] = n
    depth = np.zeros(num_leaves, dtype=np.int64)
    draws = rng.random((n_int, 4))
    for i in range(n_int):
        on_spine = spine and i < max_depth
        if on_spine:
            leaf = i  # the right child of the split before
        else:
            cum = np.cumsum(np.where((sizes >= 2) & (depth < max_depth),
                                     sizes, 0.0))
            leaf = int(np.searchsorted(cum, draws[i, 0] * cum[-1],
                                       side="right"))
        rows = leaf_rows[leaf]
        feat = int(draws[i, 1] * n_feat)
        vals = sample_t[feat, rows]
        if on_spine:
            thr = float(np.partition(vals, rows.shape[0] // 8)[
                rows.shape[0] // 8])
        else:
            a = int(draws[i, 2] * rows.shape[0])
            b = (a + 1 + int(draws[i, 3] * (rows.shape[0] - 1))) \
                % rows.shape[0]
            thr = 0.5 * (float(vals[a]) + float(vals[b]))
        go_left = vals <= thr
        if go_left.all() or not go_left.any():  # equal values: by position
            go_left = np.arange(rows.shape[0]) < max(rows.shape[0] // 8, 1)
        # LightGBM's numbering: the split leaf keeps its id as the left
        # child, the new leaf i + 1 is the right child, the split is node i
        new_leaf = i + 1
        if leaf_parent[leaf] >= 0:
            p = leaf_parent[leaf]
            if left[p] == ~leaf:
                left[p] = i
            else:
                right[p] = i
        split_feature[i], threshold[i] = feat, thr
        left[i], right[i] = ~leaf, ~new_leaf
        internal_count[i] = rows.shape[0]
        leaf_parent[leaf] = leaf_parent[new_leaf] = i
        leaf_rows[leaf], leaf_rows[new_leaf] = rows[go_left], rows[~go_left]
        sizes[leaf] = leaf_rows[leaf].shape[0]
        sizes[new_leaf] = leaf_rows[new_leaf].shape[0]
        depth[leaf] += 1
        depth[new_leaf] = depth[leaf]
    per_row = rows_stand_for / n
    leaf_count = np.array([len(r) for r in leaf_rows]) * per_row
    leaf_value = rng.standard_normal(num_leaves) * scale
    lines = [
        f"num_leaves={num_leaves}", "num_cat=0",
        "split_feature=" + _fmt(split_feature, True),
        "split_gain=" + _fmt(np.ones(n_int)),
        "threshold=" + _fmt(threshold),
        "decision_type=" + _fmt(np.zeros(n_int), True),
        "left_child=" + _fmt(left, True),
        "right_child=" + _fmt(right, True),
        "leaf_value=" + _fmt(leaf_value),
        "leaf_weight=" + _fmt(leaf_count * 0.25),
        "leaf_count=" + _fmt(leaf_count, True),
        "internal_value=" + _fmt(np.zeros(n_int)),
        "internal_weight=" + _fmt(internal_count * per_row * 0.25),
        "internal_count=" + _fmt(internal_count * per_row, True),
        "is_linear=0", "shrinkage=0.1", "", ""]
    return "\n".join(lines) + "\n"


def make_forest(seed: int, num_trees: int, num_leaves: int, n_features: int,
                leaf_scale: float, max_depth: int, sample_rows: int = 4096,
                rows_stand_for: int = 1 << 20) -> str:
    """A seeded binary ensemble as a LightGBM model string, for
    `lgb.Booster(model_str=...)`: the shape a HIGGS run leaves behind
    (num_trees x num_leaves), its tree shapes assumed (configs/
    forest500x255_binary.json). Counts are those of a seeded sample of the
    predict rows' own distribution pushed through each tree, scaled to
    `rows_stand_for`, so the expected path length the work count reads from
    the model is the one the traffic meets. Every seed's forest is exactly
    `max_depth` deep (its first tree has a spine that long, no tree goes
    deeper): the program's traversal walks every row through as many levels
    as the deepest tree has, so a depth left to the seed would make the
    seed change the work."""
    rng = np.random.default_rng([seed, 2])
    sample = rng.standard_normal((sample_rows, n_features), dtype=np.float32)
    sample_t = np.ascontiguousarray(sample.T)
    trees = [f"Tree={t}\n" + _seeded_tree(rng, sample_t, num_leaves,
                                          leaf_scale, rows_stand_for,
                                          max_depth, spine=(t == 0))
             for t in range(num_trees)]
    names = " ".join(f"Column_{i}" for i in range(n_features))
    infos = " ".join("[-6:6]" for _ in range(n_features))
    head = ["tree", "version=v4", "num_class=1", "num_tree_per_iteration=1",
            "label_index=0", f"max_feature_idx={n_features - 1}",
            "objective=binary sigmoid:1", "feature_names=" + names,
            "feature_infos=" + infos,
            "tree_sizes=" + " ".join(str(len(t)) for t in trees), "", ""]
    return ("\n".join(head) + "".join(trees) + "end of trees\n\n"
            "feature_importances:\n\nparameters:\n[boosting: gbdt]\n"
            "[objective: binary]\nend of parameters\n")
