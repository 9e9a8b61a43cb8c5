"""The plain reference for training: leaf-wise histogram GBDT, binary
log-loss, LightGBM's gain and leaf-output formulas, numpy float64.

It follows the program's first trees. What the program hands over is its
output and nothing else: the text model, and its training scores after each
followed tree. From the raw rows and labels alone the reference then works
out, for each followed tree,

  * the gradients, from its own scores (its own leaf values on the
    program's partition, never the program's numbers);
  * every leaf's rows, by plain traversal of the raw values: the counts the
    tree states have to be those (`count_mismatch`, exact);
  * every leaf's output, -G / (H + lambda_l2) * learning_rate
    (`leaf_value_gap`), and every split's gain,
    GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2) (`split_gain_gap`): the worst
    node's gap against the reference's own value of that node or of the
    median node, whichever is larger;
  * whether each split was the best on offer (`split_shortfall`): the
    reference histograms every node over every threshold that the model
    itself uses anywhere (a subset of the program's bin boundaries, taken
    from its output, not from its tables), applies min_data_in_leaf and
    min_sum_hessian_in_leaf, and asks that no candidate beats the split
    taken, and that no leaf that was waiting had a better split than the
    one the leaf-wise order took first;
  * the log-loss after the tree, against the loss of the program's own
    scores (`loss_gap`).
"""
from __future__ import annotations

import numpy as np

from modeltext import PlainTree, route


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-x))


def logloss(score: np.ndarray, y: np.ndarray) -> float:
    margin = np.where(y > 0.5, score, -score)
    return float(np.mean(np.logaddexp(0.0, -margin)))


def init_score(y: np.ndarray) -> float:
    p = float(np.mean(y))
    return float(np.log(p / (1.0 - p)))


def _node_totals(tree: PlainTree, per_leaf: np.ndarray) -> np.ndarray:
    """Per-leaf sums [L, ...] to per-internal-node sums [I, ...]: a child
    is made after its parent, so one pass from the last node up."""
    n_int = tree.split_feature.shape[0]
    out = np.zeros((n_int,) + per_leaf.shape[1:], dtype=per_leaf.dtype)
    for i in range(n_int - 1, -1, -1):
        for child in (tree.left_child[i], tree.right_child[i]):
            out[i] += out[child] if child >= 0 else per_leaf[~child]
    return out


def _child_values(child: np.ndarray, node_vals: np.ndarray,
                  leaf_vals: np.ndarray) -> np.ndarray:
    out = np.empty(child.shape[0], dtype=node_vals.dtype)
    inner = child >= 0
    out[inner] = node_vals[child[inner]]
    out[~inner] = leaf_vals[~child[~inner]]
    return out


def _gain(gl, hl, gr, hr, l2):
    g, h = gl + gr, hl + hr
    return gl * gl / (hl + l2) + gr * gr / (hr + l2) - g * g / (h + l2)


def _worst_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The worst entry's |got - want| against max(|want|, median |want|)."""
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    return float(np.max(np.abs(got - want) / scale))


def candidate_bins(X: np.ndarray, trees: list) -> tuple:
    """Every threshold the model uses, per feature, sorted; and every row's
    bin among them: bin <= k exactly when x <= candidates[k]."""
    n_feat = X.shape[1]
    cands = [[] for _ in range(n_feat)]
    for tree in trees:
        for f, t in zip(tree.split_feature, tree.threshold):
            cands[int(f)].append(float(t))
    cands = [np.unique(np.asarray(c, dtype=np.float64)) for c in cands]
    bins = [np.searchsorted(c, X[:, f], side="left").astype(np.int32)
            if c.size else None for f, c in enumerate(cands)]
    return cands, bins


def best_candidate_gain(tree: PlainTree, leaf: np.ndarray, g: np.ndarray,
                        h: np.ndarray, bins: list, cands: list, l2: float,
                        min_data: int, min_hess: float) -> np.ndarray:
    """For every internal node, the best gain over all candidate
    (feature, threshold) pairs that leave both children their minimum rows
    and hessian (with a little slack, so that a candidate on the edge by
    the program's float32 sums is not held against it)."""
    n_leaves, n_int = tree.num_leaves, tree.split_feature.shape[0]
    best = np.full(n_int, -np.inf)
    ones = np.ones_like(g)
    for f, cand in enumerate(cands):
        if cand.size == 0:
            continue
        n_bins = cand.size + 1
        key = leaf * n_bins + bins[f]
        size = n_leaves * n_bins
        per_leaf = np.stack(
            [np.bincount(key, weights=w, minlength=size).reshape(
                n_leaves, n_bins) for w in (ones, g, h)], axis=-1)
        node = _node_totals(tree, per_leaf)          # [I, bins, 3]
        left = np.cumsum(node, axis=1)[:, :-1, :]    # rows with x <= cand k
        total = node.sum(axis=1, keepdims=True)
        right = total - left
        ok = ((left[..., 0] >= min_data) & (right[..., 0] >= min_data)
              & (left[..., 2] >= min_hess * (1 + 1e-4))
              & (right[..., 2] >= min_hess * (1 + 1e-4)))
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = _gain(left[..., 1], left[..., 2], right[..., 1],
                         right[..., 2], l2)
        gain = np.where(ok, gain, -np.inf)
        best = np.maximum(best, gain.max(axis=1))
    return best


def follow(X: np.ndarray, y: np.ndarray, trees: list, params: dict,
           program_scores: list, n_follow: int) -> dict:
    """Follow the program's first `n_follow` trees; returns the readings,
    each the worst over the followed trees."""
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    n = X.shape[0]
    init = init_score(y)
    score = np.full(n, init, dtype=np.float64)
    cands, bins = candidate_bins(X, trees)
    out = {"count_mismatch": 0.0, "leaf_value_gap": 0.0,
           "split_gain_gap": 0.0, "split_shortfall": 0.0, "loss_gap": 0.0}
    for t in range(n_follow):
        tree = trees[t]
        p = _sigmoid(score)
        g, h = p - y, p * (1.0 - p)
        leaf = route(tree, X)
        n_leaves = tree.num_leaves
        cnt = np.bincount(leaf, minlength=n_leaves)
        G = np.bincount(leaf, weights=g, minlength=n_leaves)
        H = np.bincount(leaf, weights=h, minlength=n_leaves)
        node_cnt = _node_totals(tree, cnt)
        node_G, node_H = _node_totals(tree, G), _node_totals(tree, H)
        out["count_mismatch"] += float(
            np.sum(cnt != tree.leaf_count)
            + np.sum(node_cnt != tree.internal_count))
        want = -G / (H + l2) * lr
        got = tree.leaf_value - (init if t == 0 else 0.0)
        out["leaf_value_gap"] = max(out["leaf_value_gap"],
                                    _worst_gap(got, want))
        gl = _child_values(tree.left_child, node_G, G)
        hl = _child_values(tree.left_child, node_H, H)
        gain = _gain(gl, hl, node_G - gl, node_H - hl, l2)
        out["split_gain_gap"] = max(out["split_gain_gap"],
                                    _worst_gap(tree.split_gain, gain))
        # the best on offer, and the leaf-wise order
        scale = np.maximum(gain, np.median(gain))
        best = best_candidate_gain(tree, leaf, g, h, bins, cands, l2,
                                   min_data, min_hess)
        short = np.max(np.maximum(best - gain, 0.0) / scale)
        node_parent, _ = tree.parents()
        for i in range(gain.shape[0]):
            # nodes split later whose leaf was already waiting at split i
            later = np.nonzero(node_parent[i + 1:] < i)[0] + i + 1
            if later.size:
                short = max(short, float(
                    np.max(np.maximum(gain[later] - gain[i], 0.0))
                    / scale[i]))
        out["split_shortfall"] = max(out["split_shortfall"], float(short))
        score = score + want[leaf]
        ref_loss = logloss(score, y)
        prog_loss = logloss(np.asarray(program_scores[t], dtype=np.float64),
                            y)
        out["loss_gap"] = max(out["loss_gap"],
                              abs(prog_loss - ref_loss) / ref_loss)
    return out
