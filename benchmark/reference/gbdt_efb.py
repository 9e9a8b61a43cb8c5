"""The plain reference for training on a sparse table with Exclusive
Feature Bundling: leaf-wise GBDT on binary log-loss, LightGBM's gain and
leaf-output formulas, numpy float64, from the raw CSC columns.

It follows the program's first trees as `reference/gbdt.py` does (whose
pieces it imports and does not edit). What the program hands over is its
output: the text model, and after each followed tree its training scores,
its test-set scores and the AUC it reported. Beside that the kind hands it
the bundles' membership and order (which columns share a group is the
program's choice to make, not a number to check), the rows the program
counted as overwritten, and each column's bin upper bounds, which are only
compared. It never reads the binned plane, a group bin or a bundle's
offsets. From the raw columns it works out:

  * every column's bin upper bounds, from the rows the binning samples
    (`bin_construct_sample_cnt` of them, drawn with `data_random_seed`, as
    `gbdt_cat.binning_rows` draws them): the source's `BinMapper::FindBin`
    for a column without NaN (`find_bounds`: the sample's distinct values
    with the zeros counted, the negative and the positive values binned
    apart by the greedy rule, zero a bin of its own), so a one-hot column
    is the two bins [1e-35, inf] where the sample holds one of its ones and
    unused where it holds none; they have to be the program's mappers',
    bound for bound (`bin_bound_mismatch`, exact), and every reading below
    is taken with this file's own;
  * the rows a bundle overwrites: where two members of one bundle are
    non-default on a row the plane keeps the LATER member's bin, so the
    earlier member reads as its default there (LightGBM's rule for a
    conflict `max_conflict_rate` admitted); this follower applies the rule
    to the raw entries itself, and its count of such rows has to be the
    program's (`conflict_rows_mismatch`, exact);
  * the gradients, from its own scores (its own leaf values on the
    program's partition);
  * every row's leaf, by plain traversal of the raw values under the rule
    above, and every leaf's and node's rows (`count_mismatch`, exact);
  * every leaf's output (`leaf_value_gap`) and every split's gain
    (`split_gain_gap`);
  * the 255 leaves' histograms of EVERY used column in one bincount over
    the table's non-default entries a tree, keyed by (leaf, column bin);
    a column's default bin is the leaf's total less its other bins, and a
    node's histogram the sum of its leaves'. Whether each split was the
    best on offer over every threshold of every one of the columns, with
    min_data_in_leaf and min_sum_hessian_in_leaf, and whether the
    leaf-wise order held (`split_shortfall`);
  * the log-loss after the tree against the program's (`loss_gap`); the
    program's test-set scores against its own traversal of the same trees
    over the test rows, under the same bundles, with its own leaf values
    (`valid_score_gap`); the AUC of the program's test-set scores in
    float64, ties at half credit, against the value reported (`auc_gap`).

Departures from the source, on purpose: sums are float64 where the source
keeps float32 histograms; no column holds a NaN (the benchmark's data has
none, so every mapper's missing type is None); two sampled values one
float64 step apart, which the source's FindBin takes as one value, are two
here (float32 data has no such pair); the binning's sample is drawn by
numpy (`gbdt_cat.binning_rows`), where the source has its own generator.
Readings are folded so that a NaN stays a NaN (`gbdt_quant._worst`).

Imports nothing of `lightgbm_tpu` or `jax`.
"""
from __future__ import annotations

import mmap
import multiprocessing

import numpy as np

from reference.gbdt import (_child_values, _gain, _node_totals, _sigmoid,
                            _worst_gap, init_score, logloss)
from reference.gbdt_cat import auc, binning_rows
from reference.gbdt_quant import _worst

READINGS = ("bin_bound_mismatch", "conflict_rows_mismatch", "count_mismatch",
            "leaf_value_gap",
            "split_gain_gap", "split_shortfall", "loss_gap",
            "valid_score_gap", "auc_gap")
HESS_SLACK = 1e-4  # a candidate on the min_sum_hessian edge by float32 sums
ZERO = 1e-35  # the source's kZeroThreshold: |v| below it is a zero
CHUNK = 1 << 24  # entries a bincount: its keys and weights stay small


def _up(v: float) -> float:
    return float(np.nextafter(v, np.inf))


def _greedy(values: list, counts: list, max_bin: int, total: int,
            min_data_in_bin: int) -> list:
    """The source's GreedyFindBin over sorted distinct values and their
    counts: upper bounds, the last +inf. Few values: a bound between two
    neighbours once min_data_in_bin rows are in the bin. Many: bins of
    about the mean size, a value holding a mean's worth of rows a bin of
    its own, each bound the midpoint between a bin's last value and the
    next bin's first, rounded up."""
    n = len(values)
    out = []

    def push(v):
        if not out or v > _up(out[-1]):
            out.append(v)
            return True
        return False
    if n <= max_bin:
        held = 0
        for i in range(n - 1):
            held += counts[i]
            if held >= min_data_in_bin and push(_up((values[i]
                                                      + values[i + 1]) / 2)):
                held = 0
        return out + [np.inf]
    if min_data_in_bin > 0:
        max_bin = max(min(max_bin, total // min_data_in_bin), 1)
    big = [c >= total / max_bin for c in counts]
    rest_bins = max_bin - sum(big)
    rest_rows = total - sum(c for c, b in zip(counts, big) if b)
    mean = rest_rows / rest_bins if rest_bins else np.inf
    upper, lower = [], [values[0]]
    held = 0
    for i in range(n - 1):
        if not big[i]:
            rest_rows -= counts[i]
        held += counts[i]
        if big[i] or held >= mean or (big[i + 1]
                                      and held >= max(1.0, mean * 0.5)):
            upper.append(values[i])
            lower.append(values[i + 1])
            if len(upper) >= max_bin - 1:
                break
            held = 0
            if not big[i]:
                rest_bins -= 1
                mean = rest_rows / rest_bins if rest_bins else np.inf
    for hi, lo in zip(upper, lower[1:]):
        push(_up((hi + lo) / 2))
    return out + [np.inf]


def find_bounds(nonzero: np.ndarray, total: int, max_bin: int,
                min_data_in_bin: int) -> np.ndarray:
    """A column's bin upper bounds from its sampled non-zero values, the
    other `total - nonzero.size` sampled rows zeros: the source's FindBin
    for a numerical column without NaN (FindBinWithZeroAsOneBin): the
    negative values in bins by share of the rows, ending at -1e-35, zero a
    bin of its own, the positive values after 1e-35. One bin: unused."""
    values, counts = np.unique(np.asarray(nonzero, np.float64),
                               return_counts=True)
    zeros = int(total - nonzero.size)
    neg = values < 0
    # the zeros as a value of their own between the negative and the
    # positive values (where both are there even when no zero was drawn)
    if zeros or (neg.any() and (~neg).any()) or values.size == 0:
        at = int(neg.sum())
        values = np.insert(values, at, 0.0)
        counts = np.insert(counts, at, zeros)
    values, counts = values.tolist(), counts.tolist()
    left = sum(1 for v in values if v <= -ZERO)
    left_rows = sum(c for v, c in zip(values, counts) if v <= -ZERO)
    zero_rows = sum(c for v, c in zip(values, counts) if -ZERO < v <= ZERO)
    bounds = []
    if left > 0 and max_bin > 1:
        share = (int(left_rows / (total - zero_rows) * (max_bin - 1))
                 if total - zero_rows else 1)
        bounds = _greedy(values[:left], counts[:left], max(1, share),
                         left_rows, min_data_in_bin)
        bounds[-1] = -ZERO
    right = next((i for i in range(left, len(values)) if values[i] > ZERO),
                 None)
    room = max_bin - 1 - len(bounds)
    if right is not None and room > 0:
        bounds += [ZERO] + _greedy(
            values[right:], counts[right:], room,
            sum(counts[right:]), min_data_in_bin)
    else:
        bounds.append(np.inf)
    return np.asarray(bounds, np.float64)


def _no_split(values: np.ndarray, total: int, bounds: np.ndarray,
              min_rows: int) -> bool:
    """The source's NeedFilter: no threshold leaves min_rows sampled rows
    on both sides."""
    counts = np.bincount(np.searchsorted(bounds, values, side="left"),
                         minlength=bounds.size)
    counts[np.searchsorted(bounds, 0.0, side="left")] += total - values.size
    left = np.cumsum(counts)[:-1]
    return not np.any((left >= min_rows) & (total - left >= min_rows))


def own_bounds(X, params: dict) -> list:
    """Every column's bin upper bounds from the binning's sampled rows of
    the raw matrix; None for a column not used: one bin, or (under
    feature_pre_filter) no threshold that leaves min_data_in_leaf sampled
    rows on each side."""
    rows = binning_rows(X.shape[0], params)
    sample = X.tocsr()[rows].tocsc()
    sample.sum_duplicates()
    max_bin = int(params.get("max_bin", 255))
    min_in_bin = int(params.get("min_data_in_bin", 3))
    min_rows = int(params.get("min_data_in_leaf", 20))
    pre_filter = bool(params.get("feature_pre_filter", True))
    out = []
    for f in range(X.shape[1]):
        vals = sample.data[sample.indptr[f]:sample.indptr[f + 1]]
        vals = vals[vals != 0].astype(np.float64)
        b = find_bounds(vals, rows.size, max_bin, min_in_bin)
        unused = b.size <= 1 or (pre_filter and _no_split(vals, rows.size, b,
                                                          min_rows))
        out.append(None if unused else b)
    return out


def bound_mismatch(own: list, theirs: list) -> int:
    """Bounds that differ between two lists of columns' bounds: a column
    one side uses and the other does not counts its bins, a longer list
    its extra bounds."""
    gap = 0
    for a, b in zip(own, theirs):
        if a is None or b is None:
            gap += 0 if a is None and b is None else \
                (a if b is None else b).size
            continue
        n = min(a.size, b.size)
        gap += int(np.sum(a[:n] != b[:n])) + abs(a.size - b.size)
    return gap + abs(len(own) - len(theirs))


def bin_values(bounds: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """A column's bins under its upper bounds: the first bound >= v."""
    return np.minimum(np.searchsorted(bounds, vals, side="left"),
                      bounds.size - 1).astype(np.int32)


class Table:
    """One set's raw entries under the bundles' rule: the non-default
    entries that survive, each used column's in one slice (`slices`) of
    `rows` (ascending), `vals` and `flat` (the entry's bin on one axis of
    all used columns' bins, column `used[k]`'s from `offset[k]`), and the
    columns also stored as a dense value array where most rows hold a
    value."""

    def __init__(self, X, bounds: list, bundles: list) -> None:
        csc = X.tocsc()
        if not csc.has_canonical_format:
            csc = csc.copy()
            csc.sum_duplicates()
        self.n = X.shape[0]
        self.bounds = bounds
        self.used = [f for f, b in enumerate(bounds) if b is not None]
        sizes = np.array([bounds[f].size for f in self.used], np.int64)
        self.offset = np.concatenate([[0], np.cumsum(sizes)])
        self.default = np.array([bin_values(bounds[f], np.zeros(1))[0]
                                 for f in self.used], np.int64)
        self._at = dict(zip(self.used, self.offset[:-1].tolist()))
        # every entry survives at most once: the stored entries bound them
        self.rows = np.empty(csc.nnz, np.int32)
        self.vals = np.empty(csc.nnz, csc.dtype)
        self.flat = np.empty(csc.nnz, np.int32)
        self.size = 0
        self.slices, self.dense = {}, {}
        self.conflict_rows = 0
        member = {f for b in bundles for f in b}
        for f in self.used:
            if f not in member:
                self._keep(f, *self._column(csc, f))
        winner = np.full(self.n, -1, np.int32)
        for bundle in bundles:
            # a member this file leaves unused holds no entry here
            bundle = [f for f in bundle if bounds[f] is not None]
            cols = [self._column(csc, f) for f in bundle]
            for mi, (rows, _, _) in enumerate(cols):
                winner[rows] = mi  # later members overwrite
            for mi, (f, (rows, vals, bins)) in enumerate(zip(bundle, cols)):
                # a row with k members non-default: k - 1 overwrites
                won = winner[rows] == mi
                self.conflict_rows += int(rows.size - np.count_nonzero(won))
                self._keep(f, rows[won], vals[won], bins[won])
            for rows, _, _ in cols:
                winner[rows] = -1
        self.rows, self.vals, self.flat = (a[:self.size] for a in
                                           (self.rows, self.vals, self.flat))

    def _column(self, csc, f: int) -> tuple:
        """(rows, values, bins) of column f's non-default entries."""
        s, e = csc.indptr[f], csc.indptr[f + 1]
        rows, vals = csc.indices[s:e], csc.data[s:e]
        bins = bin_values(self.bounds[f], vals.astype(np.float64))
        keep = bins != bin_values(self.bounds[f], np.zeros(1))[0]
        return rows[keep], vals[keep], bins[keep]

    def _keep(self, f: int, rows, vals, bins) -> None:
        s, e = self.size, self.size + rows.size
        self.rows[s:e], self.vals[s:e] = rows, vals
        self.flat[s:e] = bins + self._at[f]
        self.slices[f] = slice(s, e)
        self.size = e
        if rows.size > self.n // 2:
            dense = np.zeros(self.n, np.float64)
            dense[rows] = vals
            self.dense[f] = dense

    def route(self, tree) -> np.ndarray:
        """Leaf index of every row: node by node in creation order over the
        node's rows; a row a column holds no entry for reads 0."""
        leaf = np.zeros(self.n, np.int32)
        if tree.num_leaves <= 1:
            return leaf
        mark = np.zeros(self.n, bool)
        value = np.zeros(self.n, np.float64)
        pending = {0: np.arange(self.n)}
        for i in range(tree.split_feature.shape[0]):
            rows = pending.pop(i)
            f, thr = int(tree.split_feature[i]), float(tree.threshold[i])
            if f in self.dense:
                go = self.dense[f][rows] <= thr
            else:
                go = np.full(rows.size, 0.0 <= thr)
                at = self.slices.get(f, slice(0, 0))
                er = self.rows[at]
                mark[er] = True
                value[er] = self.vals[at]
                held = mark[rows]
                go[held] = value[rows[held]] <= thr
                mark[er] = False
            for child, part in ((tree.left_child[i], rows[go]),
                                (tree.right_child[i], rows[~go])):
                if child >= 0:
                    pending[int(child)] = part
                else:
                    leaf[part] = ~child
        return leaf


_SHARED = {}  # what a forked worker reads (or this process, with one)


def _shared_zeros(shape: tuple, dtype) -> np.ndarray:
    """Zeros in memory that the processes forked after this call share
    with it (an anonymous mapping): what a worker writes there, this
    process reads, with nothing pickled."""
    size = int(np.prod(shape))
    buf = mmap.mmap(-1, max(1, size * np.dtype(dtype).itemsize))
    return np.frombuffer(buf, dtype, count=size).reshape(shape)


def _fork_map(fn, n: int, workers: int) -> None:
    """fn(0), ..., fn(n - 1), by `workers` processes forked from this one
    where more than one (this process has to hold no thread that a fork
    would strand: `follow_efb.py`'s holds none), else here in turn."""
    if workers > 1 and n > 1:
        with multiprocessing.get_context("fork").Pool(min(workers, n)) as pool:
            pool.map(fn, range(n))
    else:
        for i in range(n):
            fn(i)


def _route(t: int) -> None:
    """Tree t's leaf of every training row and every test row, into its
    rows of the shared arrays."""
    follower, tree = _SHARED["follower"], _SHARED["trees"][t]
    _SHARED["train"][t] = follower.train.route(tree)
    _SHARED["test"][t] = follower.test.route(tree)


def _routes(follower, trees: list, workers: int) -> list:
    """(training leaves, test leaves) of every tree: a tree's route needs
    no score, so the trees are routed at once."""
    shape = (len(trees), follower.train.n), (len(trees), follower.test.n)
    _SHARED.update(follower=follower, trees=trees,
                   train=_shared_zeros(shape[0], np.int32),
                   test=_shared_zeros(shape[1], np.int32))
    try:
        _fork_map(_route, len(trees), workers)
        return list(zip(_SHARED["train"], _SHARED["test"]))
    finally:
        _SHARED.clear()


def _entry_hists(i: int) -> None:
    """Add range i's entries of the training table, by (leaf, flat bin),
    into its row of the shared parts: [3, L * TB] (count, G, H), CHUNK
    entries a bincount."""
    table, leaf, g, h, L = (_SHARED[k] for k in ("table", "leaf", "g", "h",
                                                 "L"))
    out, start, stop = _SHARED["parts"][i], *_SHARED["cuts"][i:i + 2]
    TB = int(table.offset[-1])
    for s in range(start, stop, CHUNK):
        e = min(s + CHUNK, stop)
        r = table.rows[s:e]
        key = leaf[r].astype(np.intp) * TB + table.flat[s:e]
        for k, w in enumerate((None, g[r], h[r])):
            out[k] += np.bincount(key, weights=w, minlength=L * TB)


def _leaf_hists(tree, leaf, g, h, table: Table,
                workers: int = 1) -> np.ndarray:
    """[L, TB, 3] (count, G, H) of every leaf over every used column's
    bins: one bincount over the non-default entries, in `workers` ranges
    added in their order, the default bins from the leaf totals."""
    L, TB = tree.num_leaves, int(table.offset[-1])
    cuts = np.linspace(0, table.size, workers + 1).astype(np.int64).tolist()
    _SHARED.update(table=table, leaf=leaf, g=g, h=h, L=L, cuts=cuts,
                   parts=_shared_zeros((workers, 3, L * TB), np.float64))
    try:
        _fork_map(_entry_hists, workers, workers)
        parts = _SHARED["parts"]
        out = parts[0].copy()
        for part in parts[1:]:
            out += part
    finally:
        _SHARED.clear()
    out = np.ascontiguousarray(np.moveaxis(out.reshape(3, L, TB), 0, -1))
    totals = np.stack([np.bincount(leaf, weights=w, minlength=L)
                       for w in (None, g, h)], axis=-1)  # [L, 3]
    starts = table.offset[:-1]
    seg = np.add.reduceat(out, starts, axis=1)  # [L, F, 3]
    out[:, starts + table.default, :] += totals[:, None, :] - seg
    return out


def _best_on_offer(node, offset, p) -> np.ndarray:
    """Every node's best gain over every threshold t <= num_bin - 2 of
    every used column: left = bins 0..t."""
    cum = np.cumsum(node, axis=1)
    base = np.concatenate([np.zeros_like(cum[:, :1]), cum[:, :-1]], axis=1)
    base = np.repeat(base[:, offset[:-1], :], np.diff(offset), axis=1)
    left = cum - base
    right = np.repeat(np.add.reduceat(node, offset[:-1], axis=1),
                      np.diff(offset), axis=1) - left
    pos = np.arange(int(offset[-1])) - np.repeat(offset[:-1], np.diff(offset))
    last = np.repeat(np.diff(offset) - 1, np.diff(offset))
    ok = ((pos < last)[None, :]
          & (left[..., 0] >= p["min_data"]) & (right[..., 0] >= p["min_data"])
          & (left[..., 2] >= p["min_hess"] * (1 + HESS_SLACK))
          & (right[..., 2] >= p["min_hess"] * (1 + HESS_SLACK)))
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = _gain(left[..., 1], left[..., 2], right[..., 1],
                     right[..., 2], p["l2"])
    return np.where(ok, gain, -np.inf).max(axis=1)


class Follower:
    """What following the program's trees needs before the first of them,
    made once: this file's own bin bounds, both sets' tables under the
    bundles' rule, and the two readings that need no tree.

    sets     {"train": (X, y), "test": (X, y)}, X a scipy sparse matrix
    layout   {"bounds": [each column's bin upper bounds in the program's
              mappers, None where the column is not used: compared only],
              "bundles": [each bundle's columns in order],
              "conflict_rows": the program's count for the training set}
    bounds   this file's `own_bounds(X, params)`, where already made
    """

    def __init__(self, sets: dict, layout: dict, params: dict,
                 bounds: list = None) -> None:
        X, y = sets["train"]
        Xt, yt = sets["test"]
        self.params = params
        self.bounds = own_bounds(X, params) if bounds is None else bounds
        theirs = [None if b is None else np.asarray(b, np.float64)
                  for b in layout["bounds"]]
        self.train = Table(X, self.bounds, layout["bundles"])
        self.test = Table(Xt, self.bounds, layout["bundles"])
        self.y = np.asarray(y, np.float64)
        self.yt = np.asarray(yt, np.float64)
        self.base = {
            "bin_bound_mismatch": float(bound_mismatch(self.bounds, theirs)),
            "conflict_rows_mismatch": float(abs(
                self.train.conflict_rows - int(layout["conflict_rows"])))}

    def follow(self, trees: list, program: dict, n_follow: int,
               workers: int = 1) -> dict:
        """Follow the program's first `n_follow` trees; the readings, each
        the worst over the followed trees. `workers`: `_leaf_hists`'.

        program  {"train_scores": [scores after tree t],
                  "test_scores": [scores after tree t], "auc": [reported]}
        """
        params, table, y = self.params, self.train, self.y
        p = {"l2": float(params.get("lambda_l2", 0.0)),
             "min_data": int(params.get("min_data_in_leaf", 20)),
             "min_hess": float(params.get("min_sum_hessian_in_leaf", 1e-3))}
        lr = float(params["learning_rate"])
        init = init_score(y)
        score = np.full(table.n, init)
        test_score = np.full(self.test.n, init)
        out = dict.fromkeys(READINGS, 0.0)
        out.update(self.base)
        routes = _routes(self, trees[:n_follow], workers)
        for t in range(n_follow):
            tree = trees[t]
            pr = _sigmoid(score)
            g, h = pr - y, pr * (1.0 - pr)
            leaf, test_leaf = routes[t]
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
                want = -G / (H + p["l2"]) * lr
                got = tree.leaf_value - (init if t == 0 else 0.0)
                out["leaf_value_gap"] = _worst(out["leaf_value_gap"],
                                               _worst_gap(got, want))
                gl = _child_values(tree.left_child, node_G, G)
                hl = _child_values(tree.left_child, node_H, H)
                gain = _gain(gl, hl, node_G - gl, node_H - hl, p["l2"])
                out["split_gain_gap"] = _worst(
                    out["split_gain_gap"], _worst_gap(tree.split_gain, gain))
                hists = _leaf_hists(tree, leaf, g, h, table, workers)
                best = _best_on_offer(_node_totals(tree, hists),
                                      table.offset, p)
                del hists
                scale = np.maximum(gain, np.median(gain))
                short = float(np.max(np.maximum(best - gain, 0.0) / scale))
                node_parent, _ = tree.parents()
                for i in range(gain.shape[0]):
                    later = np.nonzero(node_parent[i + 1:] < i)[0] + i + 1
                    if later.size:
                        short = _worst(short, float(
                            np.max(np.maximum(gain[later] - gain[i], 0.0))
                            / scale[i]))
            out["split_shortfall"] = _worst(out["split_shortfall"], short)
            score = score + want[leaf]
            out["loss_gap"] = _worst(out["loss_gap"], abs(
                logloss(np.asarray(program["train_scores"][t], np.float64), y)
                - logloss(score, y)) / logloss(score, y))
            test_score = test_score + want[test_leaf]
            theirs = np.asarray(program["test_scores"][t], dtype=np.float64)
            out["valid_score_gap"] = _worst(
                out["valid_score_gap"],
                float(np.max(np.abs(theirs - test_score))
                      / max(float(np.max(np.abs(test_score))), 1e-300)))
            out["auc_gap"] = _worst(out["auc_gap"], abs(
                float(program["auc"][t]) - auc(theirs, self.yt)))
        return out


def follow(sets: dict, trees: list, layout: dict, params: dict,
           program: dict, n_follow: int) -> dict:
    """`Follower(sets, layout, params).follow(trees, program, n_follow)`."""
    return Follower(sets, layout, params).follow(trees, program, n_follow)
