"""The plain reference for quantized-gradient training (LightGBM's
`use_quantized_grad`: Shi et al., "Quantized Training of Gradient Boosting
Decision Trees", NeurIPS 2022; `GradientDiscretizer`), numpy float64.

`reference/gbdt.py` cannot judge this path: rounding every gradient to
`num_grad_quant_bins + 1` levels moves a 400-row leaf's gradient sum by
percents, past every limit of a float cell. This follower is handed, beside
what `gbdt.py` is handed (raw rows, labels, the text model, the program's
training scores after each followed tree), each followed tree's integer
pack `[N, 3]` int8 (gradient, hessian, one) and its two float32 scales. It
does not trust them. From its own float64 gradients (its own scores: its
own leaf values on the program's partition) it works out, for each tree,

  * `scale_gap`: the scales against `grad_scale = max|g| / (bins / 2)` and
    `hess_scale = max|h| / bins` (or `max|h|` with every integer 1 where
    all hessians are equal: the first tree of a binary run; upstream takes
    that branch from the objective, the program from the data, and the
    products `integer * scale` are the same);
  * `quant_outside`: the rows whose integers are not one of the two
    neighbours of `g / grad_scale` and `h / hess_scale`, or whose third
    channel is not one (exact: 0);
  * the rounding the configuration states. `stochastic_rounding=true`:
    rows are bucketed by the fractional part of `|g| / grad_scale` (and of
    `h / hess_scale`), ten buckets a channel, and a bucket's share of rows
    rounded away from zero has to be the bucket's mean fraction:
    `rounding_z` is the worst bucket's distance in standard deviations of
    that share (buckets whose share has next to no variance are left to
    `quant_outside`). Nearest rounding reads hundreds there, a stream drawn
    from a shorter interval tens. `stochastic_rounding=false`:
    `nearest_miss` counts the rows not rounded half away from zero.

Then, with those integers and exactly (an int8 summed in float64 is exact
below 2**53):

  * `count_mismatch`: leaf and node counts by plain traversal (exact);
  * `leaf_value_gap`: every leaf's output
    `-(Gq * gs) / (Hq * hs + lambda_l2) * learning_rate` from the integer
    sums `Gq`, `Hq` (from the float64 sums of the true gradients where
    `quant_train_renew_leaf`);
  * `split_gain_gap`: every split's gain from the integer sums of its two
    children through the two scales;
  * `split_shortfall`: no candidate threshold (every threshold the model
    uses anywhere) and no waiting leaf better than the split taken, with
    `min_data_in_leaf` on the exact counts and `min_sum_hessian_in_leaf`
    on `Hq * hs`;
  * `loss_gap`: the log-loss of its own scores against the program's.

Gaps are `gbdt.py`'s: the worst entry against the reference's own value of
that entry or of the median entry, whichever is larger.

Departures from LightGBM's `GradientDiscretizer`, on both sides alike:
row counts are exact, from the pack's third channel, where upstream
estimates a leaf's count from its hessian sum (`cnt_factor`); histograms
are int32 everywhere, where upstream picks 8, 16 or 32 bits a leaf from its
row count; the random numbers are the program's own stream (threefry), so
only their distribution is checked, never their values.

Imports nothing of `lightgbm_tpu`.
"""
from __future__ import annotations

import numpy as np

from modeltext import PlainTree, route
from reference.gbdt import (_child_values, _gain, _node_totals, _sigmoid,
                            _worst_gap, candidate_bins, init_score, logloss)

NEIGHBOUR_SLACK = 1e-4   # of one integer step: float32 against float64
FRACTION_BUCKETS = 10
MIN_BUCKET_VARIANCE = 25.0  # rows * p * (1 - p) under which z says nothing
READINGS = ("count_mismatch", "scale_gap", "quant_outside", "rounding_z",
            "nearest_miss", "leaf_value_gap", "split_gain_gap",
            "split_shortfall", "loss_gap")


def _worst(so_far: float, value: float) -> float:
    """The larger of the two, and NaN if either is: a reading that could not
    be computed (an empty leaf's 0 / 0) stays NaN, which the harness counts
    as not correct, where Python's `max` would drop it."""
    value = float(value)
    if so_far != so_far or value != value:
        return float("nan")
    return max(so_far, value)


def expected_scales(g: np.ndarray, h: np.ndarray, bins: int) -> tuple:
    """(grad_scale, hess_scale, whether every hessian is the same)."""
    max_g, max_h = float(np.max(np.abs(g))), float(np.max(np.abs(h)))
    constant = max_h - float(np.min(h)) <= 1e-12 * max_h
    return max_g / (bins // 2), max_h / bins, constant


def scale_gap(g: np.ndarray, h: np.ndarray, bins: int, gs: float,
              hs: float) -> float:
    want_g, want_h, constant = expected_scales(g, h, bins)
    gap_h = abs(hs / want_h - 1.0)
    if constant:  # integer 1 at scale max|h| is integer `bins` at max|h|/bins
        gap_h = min(gap_h, abs(hs / (want_h * bins) - 1.0))
    return max(abs(gs / want_g - 1.0), gap_h)


def outside_neighbours(x: np.ndarray, q: np.ndarray) -> int:
    """Rows whose integer is neither floor(x) nor ceil(x)."""
    return int(np.sum((q < np.floor(x - NEIGHBOUR_SLACK))
                      | (q > np.ceil(x + NEIGHBOUR_SLACK))))


def rounding_z(x: np.ndarray, q: np.ndarray) -> float:
    """Stochastic rounding of |x| to |q|: by bucket of the fractional part,
    the share rounded away from zero against the mean fraction, in standard
    deviations of that share; the worst bucket."""
    mag = np.abs(x)
    low = np.floor(mag)
    frac = mag - low
    up = (np.abs(q) > low).astype(np.float64)
    bucket = np.minimum((frac * FRACTION_BUCKETS).astype(np.int64),
                        FRACTION_BUCKETS - 1)
    rows = np.bincount(bucket, minlength=FRACTION_BUCKETS)
    went_up = np.bincount(bucket, weights=up, minlength=FRACTION_BUCKETS)
    mean_frac = np.bincount(bucket, weights=frac,
                            minlength=FRACTION_BUCKETS)
    worst = 0.0
    for n, k, s in zip(rows, went_up, mean_frac):
        if n == 0:
            continue
        p = s / n
        variance = n * p * (1.0 - p)
        if variance < MIN_BUCKET_VARIANCE:
            continue
        worst = max(worst, abs(k - s) / np.sqrt(variance))
    return float(worst)


def nearest_miss(x: np.ndarray, q: np.ndarray) -> int:
    """Rows not rounded half away from zero (those within the slack of a
    half are either way)."""
    mag = np.abs(x)
    frac = mag - np.floor(mag)
    want = np.sign(x) * np.floor(mag + 0.5)
    return int(np.sum((q != want)
                      & (np.abs(frac - 0.5) > NEIGHBOUR_SLACK)))


def best_candidate_gain(tree: PlainTree, leaf: np.ndarray, q: np.ndarray,
                        gs: float, hs: float, bins: list, cands: list,
                        l2: float, min_data: int,
                        min_hess: float) -> np.ndarray:
    """`gbdt.best_candidate_gain` from integer histograms: for every
    internal node, the best gain over all candidate (feature, threshold)
    pairs that leave both children `min_data` rows (exact) and `min_hess`
    of `Hq * hs` (with a float32's slack, so that a candidate on the edge
    by the program's float32 product is not held against it)."""
    n_leaves, n_int = tree.num_leaves, tree.split_feature.shape[0]
    best = np.full(n_int, -np.inf)
    weights = (np.ones(q.shape[0]), q[:, 0].astype(np.float64),
               q[:, 1].astype(np.float64))
    for f, cand in enumerate(cands):
        if cand.size == 0:
            continue
        n_bins = cand.size + 1
        key = leaf * n_bins + bins[f]
        size = n_leaves * n_bins
        per_leaf = np.stack(
            [np.bincount(key, weights=w, minlength=size).reshape(
                n_leaves, n_bins) for w in weights], axis=-1)
        node = _node_totals(tree, per_leaf)          # [I, bins, 3] integers
        left = np.cumsum(node, axis=1)[:, :-1, :]    # rows with x <= cand k
        right = node.sum(axis=1, keepdims=True) - left
        ok = ((left[..., 0] >= min_data) & (right[..., 0] >= min_data)
              & (left[..., 2] * hs >= min_hess * (1 + 1e-5))
              & (right[..., 2] * hs >= min_hess * (1 + 1e-5)))
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = _gain(left[..., 1] * gs, left[..., 2] * hs,
                         right[..., 1] * gs, right[..., 2] * hs, l2)
        gain = np.where(ok, gain, -np.inf)
        best = np.maximum(best, gain.max(axis=1))
    return best


def follow(X: np.ndarray, y: np.ndarray, trees: list, params: dict,
           program_scores: list, packs: list, n_follow: int) -> dict:
    """Follow the program's first `n_follow` trees. `packs[t]` is
    `(q, scales)`: tree t's integers `[N, 3]` int8 and its float32
    `[grad_scale, hess_scale, ...]`. Returns the readings, each the worst
    (or the sum, for the three counts) over the followed trees."""
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params.get("min_data_in_leaf", 20))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    quant_bins = int(params.get("num_grad_quant_bins", 4))
    stochastic = bool(params.get("stochastic_rounding", True))
    renew = bool(params.get("quant_train_renew_leaf", False))
    n = X.shape[0]
    init = init_score(y)
    score = np.full(n, init, dtype=np.float64)
    cands, bins = candidate_bins(X, trees)
    out = dict.fromkeys(READINGS, 0.0)
    for t in range(n_follow):
        tree = trees[t]
        q, scales = packs[t]
        q = np.asarray(q)
        gs, hs = float(scales[0]), float(scales[1])
        p = _sigmoid(score)
        g, h = p - y, p * (1.0 - p)

        # the quantization itself, against the reference's own gradients
        out["scale_gap"] = _worst(out["scale_gap"],
                                  scale_gap(g, h, quant_bins, gs, hs))
        xg, xh = g / gs, h / hs
        out["quant_outside"] += float(
            outside_neighbours(xg, q[:, 0]) + outside_neighbours(xh, q[:, 1])
            + int(np.sum(q[:, 2] != 1)) + abs(q.shape[0] - n))
        if stochastic:
            out["rounding_z"] = _worst(out["rounding_z"], _worst(
                rounding_z(xg, q[:, 0]), rounding_z(xh, q[:, 1])))
        else:
            out["nearest_miss"] += float(nearest_miss(xg, q[:, 0])
                                         + nearest_miss(xh, q[:, 1]))
        del xg, xh

        # the tree, from the integers
        leaf = route(tree, X)
        n_leaves = tree.num_leaves
        cnt = np.bincount(leaf, minlength=n_leaves)
        Gq = np.bincount(leaf, weights=q[:, 0], minlength=n_leaves)
        Hq = np.bincount(leaf, weights=q[:, 1], minlength=n_leaves)
        out["count_mismatch"] += float(
            np.sum(cnt != tree.leaf_count)
            + np.sum(_node_totals(tree, cnt) != tree.internal_count))
        if renew:
            G = np.bincount(leaf, weights=g, minlength=n_leaves)
            H = np.bincount(leaf, weights=h, minlength=n_leaves)
            want = -G / (H + l2) * lr
        else:
            want = -(Gq * gs) / (Hq * hs + l2) * lr
        got = tree.leaf_value - (init if t == 0 else 0.0)
        out["leaf_value_gap"] = _worst(out["leaf_value_gap"],
                                       _worst_gap(got, want))
        node_G = _node_totals(tree, Gq) * gs
        node_H = _node_totals(tree, Hq) * hs
        gl = _child_values(tree.left_child, node_G, Gq * gs)
        hl = _child_values(tree.left_child, node_H, Hq * hs)
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = _gain(gl, hl, node_G - gl, node_H - hl, l2)
        out["split_gain_gap"] = _worst(out["split_gain_gap"],
                                       _worst_gap(tree.split_gain, gain))
        # the best on offer, and the leaf-wise order
        scale = np.maximum(gain, np.median(gain))
        best = best_candidate_gain(tree, leaf, q, gs, hs, bins, cands, l2,
                                   min_data, min_hess)
        short = float(np.max(np.maximum(best - gain, 0.0) / scale))
        node_parent, _ = tree.parents()
        for i in range(gain.shape[0]):
            # nodes split later whose leaf was already waiting at split i
            later = np.nonzero(node_parent[i + 1:] < i)[0] + i + 1
            if later.size:
                short = _worst(short, float(
                    np.max(np.maximum(gain[later] - gain[i], 0.0))
                    / scale[i]))
        out["split_shortfall"] = _worst(out["split_shortfall"], short)
        score = score + want[leaf]
        ref_loss = logloss(score, y)
        prog_loss = logloss(np.asarray(program_scores[t], dtype=np.float64),
                            y)
        out["loss_gap"] = _worst(out["loss_gap"],
                                 abs(prog_loss - ref_loss) / ref_loss)
    return out
