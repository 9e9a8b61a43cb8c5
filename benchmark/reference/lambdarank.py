"""The plain reference for `objective=lambdarank`: LightGBM's
`LambdarankNDCG::GetGradientsForOneQuery` (`src/objective/rank_objective.hpp`)
and `NDCGMetric` (`src/metric/rank_metric.hpp`, `dcg_calculator.cpp`) in
numpy float64, a Python loop over queries, no padding, no buckets.

The equations, for one query of n documents with scores s and grades l,
truncation level T, sigmoid sigma:

  * the documents sorted by score, descending, STABLY (equal scores keep
    their row order, as `std::stable_sort` leaves them);
  * for every rank i < min(T, n) and every rank j > i whose grades differ
    (`high` the one with the larger grade, `low` the other):
        delta  = s_high - s_low
        dNDCG  = |gain(l_high) - gain(l_low)| * |disc(i) - disc(j)| / maxDCG@T
                 with gain(l) = 2^l - 1 and disc(r) = 1 / log2(2 + r);
                 under `lambdarank_norm`, where the query's best and worst
                 scores differ, dNDCG /= 0.01 + |delta|
        rho    = 1 / (1 + exp(sigma * delta))
        lambda_high -= sigma * dNDCG * rho
        lambda_low  += sigma * dNDCG * rho
        hessian of both += sigma^2 * dNDCG * rho * (1 - rho)
  * under the norm, with S = 2 * sigma * sum(dNDCG * rho) > 0, every lambda
    and hessian of the query times log2(1 + S) / S;
  * a query whose maxDCG@T is 0 (no relevant document) gets zeros.

`follow` does for this objective what `reference/gbdt.py`'s does for
log-loss. It is handed the raw rows, grades and query sizes of the three
sets, the text model, and what the program produced after each followed
tree: its training scores, its validation scores and the NDCG values it
reported. It keeps scores of its OWN (its own leaf values on the program's
partition, never the program's numbers) and holds the program's to them
(`score_gap`, `valid_score_gap`); it does not trust what it is handed. For
each followed tree it works out:

  * the gradients and hessians above, from the training scores the program
    handed over after the tree before (zeros before the first), which
    `score_gap` has held to its own: LambdaRank is discontinuous where two
    scores cross, the scores of two documents in different leaves of two
    trees differ by 1e-6 often enough at 2.27 M rows (255 x 255 sums over a
    range of ~0.2), and float32 scores order such a pair otherwise than
    float64 ones built from leaf values that differ in the sixth digit. On
    the chip, gradients from the reference's own scores read the sound
    program's leaf outputs 3.2e-3 off and its gains 6.2e-3 (my chip run,
    PR 34): a measure of that discontinuity, not of the program. The order
    of equal scores is still the source's (stable, by row);
  * `count_mismatch`: every leaf's and node's rows by plain traversal
    (exact);
  * `leaf_value_gap`: every leaf's output -G / (H + lambda_l2) *
    learning_rate, and `split_gain_gap`: every split's gain, both from its
    own gradients: a wrong pair term, discount, truncation, norm or tie
    order shows here;
  * `split_shortfall`: whether each split was the best on offer under
    `min_data_in_leaf` and `min_sum_hessian_in_leaf`, over every threshold
    the followed trees use and every partition a column of at most
    `max_bin` distinct values offers (`candidate_bins`), and whether the
    leaf-wise order held;
  * `score_gap`, `valid_score_gap`: the program's training scores, and its
    validation scores, against its own: by traversal of the same trees
    over the train, vali and test rows with its own leaf values;
  * `ndcg_gap`: NDCG at every `eval_at` of vali and test, counted by this
    file from the validation scores the program handed over, against the
    values the program reported.

Readings are folded so that a NaN stays a NaN (`gbdt_quant._worst`).

Departures from the source, on purpose: the exact sigmoid where LightGBM
reads a table of 1,048,576 entries over [-50/sigma/2, 50/sigma/2]
(`ConstructSigmoidTable`: its step of ~5e-5 in sigma*delta moves rho by
~1e-5 at most); float64 throughout where the source accumulates lambdas in
`score_t` (float32); equal scores are ordered by row (stable), and nearly
equal ones as the program's float32 scores order them (above): after the
first tree every document of a leaf ties exactly, so the stable order is
part of the result. Query weights, position debiasing and `label_gain`
other than the default are not read: the benchmark's configuration has
none.

Imports nothing of `lightgbm_tpu` or `jax`.
"""
from __future__ import annotations

import numpy as np

from modeltext import route
from reference.gbdt import (_child_values, _gain, _node_totals, _worst_gap,
                            best_candidate_gain)
from reference.gbdt_quant import _worst

DISTINCT_SAMPLE = 20000   # rows looked at before a column is sorted whole
READINGS = ("count_mismatch", "leaf_value_gap", "split_gain_gap",
            "split_shortfall", "score_gap", "valid_score_gap", "ndcg_gap")


def label_gains(max_label: int = 31) -> np.ndarray:
    """DCGCalculator::DefaultLabelGain: 2^l - 1."""
    return np.array([float((1 << l) - 1) for l in range(max_label)])


def discount(rank: np.ndarray) -> np.ndarray:
    return 1.0 / np.log2(2.0 + rank)


def max_dcg(grades: np.ndarray, k: int, gains: np.ndarray) -> float:
    """DCGCalculator::CalMaxDCGAtK: the k best grades in order."""
    best = np.sort(grades.astype(np.int64))[::-1][:k]
    return float(np.sum(gains[best] * discount(np.arange(best.shape[0]))))


def boundaries(sizes: np.ndarray) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(np.asarray(sizes, dtype=np.int64))])


def query_gradients(s: np.ndarray, grades: np.ndarray, gains: np.ndarray,
                    truncation: int, sigma: float, norm: bool) -> tuple:
    """(lambdas [n], hessians [n]) of one query, in the query's row order."""
    n = s.shape[0]
    lam, hes = np.zeros(n), np.zeros(n)
    top = max_dcg(grades, truncation, gains)
    if n < 2 or top <= 0.0:
        return lam, hes
    order = np.argsort(-s, kind="stable")
    ss, ls = s[order], grades[order].astype(np.int64)
    gs = gains[ls]
    m = min(truncation, n)
    disc = discount(np.arange(n))
    i, j = np.arange(m)[:, None], np.arange(n)[None, :]
    pair = (j > i) & (ls[:m, None] != ls[None, :])
    i_is_high = ls[:m, None] > ls[None, :]
    ds = ss[:m, None] - ss[None, :]
    delta = np.where(i_is_high, ds, -ds)          # s_high - s_low
    d_ndcg = (np.abs(gs[:m, None] - gs[None, :])
              * np.abs(disc[:m, None] - disc[None, :]) / top)
    if norm and ss[0] != ss[n - 1]:
        d_ndcg = d_ndcg / (0.01 + np.abs(delta))
    with np.errstate(over="ignore"):
        rho = 1.0 / (1.0 + np.exp(sigma * delta))
    p_lambda = np.where(pair, sigma * d_ndcg * rho, 0.0)
    p_hess = np.where(pair, sigma * sigma * d_ndcg * rho * (1.0 - rho), 0.0)
    to_low = np.where(i_is_high, p_lambda, -p_lambda)  # what j gains, i loses
    sorted_lam, sorted_hes = np.zeros(n), np.zeros(n)
    sorted_lam[:m] -= to_low.sum(axis=1)
    sorted_lam += to_low.sum(axis=0)
    sorted_hes[:m] += p_hess.sum(axis=1)
    sorted_hes += p_hess.sum(axis=0)
    if norm:
        total = 2.0 * float(p_lambda.sum())
        if total > 0.0:
            factor = np.log2(1.0 + total) / total
            sorted_lam *= factor
            sorted_hes *= factor
    lam[order], hes[order] = sorted_lam, sorted_hes
    return lam, hes


def gradients(score: np.ndarray, grades: np.ndarray, sizes: np.ndarray,
              params: dict) -> tuple:
    """(lambdas [N], hessians [N]) of every document, float64."""
    gains = label_gains()
    truncation = int(params.get("lambdarank_truncation_level", 30))
    sigma = float(params.get("sigmoid", 1.0))
    norm = bool(params.get("lambdarank_norm", True))
    score = np.asarray(score, dtype=np.float64)
    bounds = boundaries(sizes)
    lam, hes = np.zeros(score.shape[0]), np.zeros(score.shape[0])
    for q in range(bounds.shape[0] - 1):
        lo, hi = bounds[q], bounds[q + 1]
        lam[lo:hi], hes[lo:hi] = query_gradients(
            score[lo:hi], grades[lo:hi], gains, truncation, sigma, norm)
    return lam, hes


def ndcg(score: np.ndarray, grades: np.ndarray, sizes: np.ndarray,
         eval_at) -> list:
    """NDCG at each k, the mean over the queries as `NDCGMetric::Eval`
    counts it: documents by score, descending, stably; a query with no
    relevant document counts as 1."""
    gains = label_gains()
    ks = [int(k) for k in eval_at]
    score = np.asarray(score, dtype=np.float64)
    bounds = boundaries(sizes)
    totals = np.zeros(len(ks))
    for q in range(bounds.shape[0] - 1):
        lo, hi = bounds[q], bounds[q + 1]
        lab = grades[lo:hi].astype(np.int64)
        disc = discount(np.arange(hi - lo))
        order = np.argsort(-score[lo:hi], kind="stable")
        got = np.cumsum(gains[lab[order]] * disc)
        best = np.cumsum(gains[np.sort(lab)[::-1]] * disc)  # maxDCG@1..n
        for at, k in enumerate(ks):
            k = min(k, hi - lo)
            totals[at] += got[k - 1] / best[k - 1] if best[k - 1] > 0 else 1.0
    return list(totals / (bounds.shape[0] - 1))


def candidate_bins(X: np.ndarray, trees: list, max_bin: int) -> tuple:
    """`reference.gbdt.candidate_bins` (every threshold the model uses, per
    feature, and every row's bin among them) with, for a column of at most
    `max_bin` distinct values, every midpoint between two of them as well:
    such a column bins one value a bin, so these are exactly the partitions
    it offers the program, whether the model ever used the column or not (a
    feature left out of the histograms is never used: its own thresholds
    would never be candidates). A column of more distinct values offers the
    program partitions this file cannot know without the program's bin
    boundaries, and stays with the model's thresholds."""
    n_feat = X.shape[1]
    used = [[] for _ in range(n_feat)]
    for tree in trees:
        for f, t in zip(tree.split_feature, tree.threshold):
            used[int(f)].append(float(t))
    cands, bins = [], []
    for f in range(n_feat):
        col = X[:, f]
        own = np.zeros(0)
        if np.unique(col[:DISTINCT_SAMPLE]).size <= max_bin:
            values = np.unique(col).astype(np.float64)
            if 1 < values.size <= max_bin:
                own = 0.5 * (values[:-1] + values[1:])
        c = np.unique(np.concatenate([np.asarray(used[f], np.float64), own]))
        cands.append(c)
        bins.append(np.searchsorted(c, col, side="left").astype(np.int32)
                    if c.size else None)
    return cands, bins


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    """The worst |got - want| against the largest |want|."""
    got = np.asarray(got, dtype=np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-300)
    return float(np.max(np.abs(got - want)) / scale)


def follow(sets: dict, trees: list, params: dict, program: dict,
           n_follow: int) -> dict:
    """Follow the program's first `n_follow` trees; the readings, each the
    worst over the followed trees.

    sets     {"train": (X, grades, sizes), "vali": ..., "test": ...}
    program  {"train_scores": [scores [N] after tree t],
              "valid_scores": {name: [scores after tree t]},
              "ndcg": {name: {"ndcg@k": [value after tree t]}}}
    """
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    eval_at = [int(k) for k in params.get("eval_at", [1, 2, 3, 4, 5])]
    X, grades, sizes = sets["train"]
    valid_names = [name for name in sets if name != "train"]
    score = np.zeros(X.shape[0])   # lambdarank boosts from 0
    valid_score = {name: np.zeros(sets[name][0].shape[0])
                   for name in valid_names}
    cands, bins = candidate_bins(X, trees[:n_follow],
                                 int(params.get("max_bin", 255)))
    out = dict.fromkeys(READINGS, 0.0)
    for t in range(n_follow):
        tree = trees[t]
        basis = (np.zeros(X.shape[0]) if t == 0
                 else program["train_scores"][t - 1])
        g, h = gradients(basis, grades, sizes, params)
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
        with np.errstate(divide="ignore", invalid="ignore"):
            want = -G / (H + l2) * lr
            out["leaf_value_gap"] = _worst(
                out["leaf_value_gap"], _worst_gap(tree.leaf_value, want))
            gl = _child_values(tree.left_child, node_G, G)
            hl = _child_values(tree.left_child, node_H, H)
            gain = _gain(gl, hl, node_G - gl, node_H - hl, l2)
            out["split_gain_gap"] = _worst(
                out["split_gain_gap"], _worst_gap(tree.split_gain, gain))
            # the best on offer, and the leaf-wise order
            scale = np.maximum(gain, np.median(gain))
            best = best_candidate_gain(tree, leaf, g, h, bins, cands, l2,
                                       min_data, min_hess)
            short = float(np.max(np.maximum(best - gain, 0.0) / scale))
            node_parent, _ = tree.parents()
            for i in range(gain.shape[0]):
                # nodes split later whose leaf was waiting at split i
                later = np.nonzero(node_parent[i + 1:] < i)[0] + i + 1
                if later.size:
                    short = _worst(short, float(
                        np.max(np.maximum(gain[later] - gain[i], 0.0))
                        / scale[i]))
        out["split_shortfall"] = _worst(out["split_shortfall"], short)
        score = score + want[leaf]
        out["score_gap"] = _worst(
            out["score_gap"], _gap(program["train_scores"][t], score))
        for name in valid_names:
            Xv, grades_v, sizes_v = sets[name]
            valid_score[name] = valid_score[name] + want[route(tree, Xv)]
            theirs = program["valid_scores"][name][t]
            out["valid_score_gap"] = _worst(
                out["valid_score_gap"], _gap(theirs, valid_score[name]))
            counted = ndcg(theirs, grades_v, sizes_v, eval_at)
            said = [program["ndcg"][name][f"ndcg@{k}"][t] for k in eval_at]
            out["ndcg_gap"] = _worst(out["ndcg_gap"],
                                     _gap(said, np.asarray(counted)))
    return out
