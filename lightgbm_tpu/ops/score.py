"""Train-time score updates in bin space.

Counterpart of Tree::AddPredictionToScore over a binned data set
(include/LightGBM/tree.h:104-132, the path that decides by
threshold_in_bin and cat_threshold_inner) and CUDAScoreUpdater:

  * `add_tree_to_score`: bagged training's out-of-bag rows never enter the
    leaf partition, so their new-tree contribution is computed by traversing
    the tree directly over the training plane (exactly the decisions the
    partition made for in-bag rows — threshold_in_bin comparisons, EFB
    group-bin translation, missing direction);
  * `replay_tree_to_score`: a validation set binned with the training set's
    mappers (ScoreUpdater::AddScore, gbdt.cpp UpdateScore) takes each new
    tree by replaying its splits in creation order over the set's plane,
    rows on the lanes: one elementwise pass a split, no per-row gather.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from ..utils.timer import SCOPE_VALID_SCORE
from .partition import split_decision_bins


# A split's row of the bin-space table (split_table): split_decision_bins'
# eight decision fields at columns 1-8, then whether the split is
# categorical and the leaf it split (which keeps its id on the left; the
# right takes leaf i + 1, Tree.split), then the node's bin-space bitset
# words.
SPLIT_TABLE_FIELDS = ("group", "threshold", "default_left", "missing_type",
                      "default_bin", "nbins", "efb_lo", "efb_hi", "is_efb",
                      "is_cat", "split_leaf")
_CAT_WORD_BITS = 32  # Tree.cat_threshold_inner's words (bins_to_bitset)


def feature_split_fields(dataset) -> np.ndarray:
    """[num_total_features, 6] int32 per real feature: its group row in the
    plane, default bin, bin count, EFB group-bin range lo / hi and whether
    its group is a bundle (binned_tree_arrays' fields; zeros for a feature
    no group holds)."""
    out = np.zeros((dataset.num_total_features, 6), dtype=np.int32)
    for f, (g, mi) in dataset.feature_to_group.items():
        fg = dataset.groups[g]
        lo, hi, _ = fg.feature_bin_range(mi)
        m = dataset.mappers[f]
        out[f] = (g, m.default_bin, m.num_bin, lo, hi, fg.is_multi)
    return out


def cat_table_words(dataset) -> int:
    """Bitset words a split's row holds: enough for the largest group's
    bins, so one table shape, and one program, serve a run."""
    most = max((g.num_total_bin for g in dataset.groups), default=1)
    return -(-most // _CAT_WORD_BITS)


def split_table(tree, fields: np.ndarray, n_words: int,
                n_rows: int) -> np.ndarray:
    """[max(n_rows, splits), SPLIT_TABLE_FIELDS + n_words] int32: row i is
    node i of `tree` (creation order) in bin space, from the tree's arrays
    (`fields` as feature_split_fields gives them). The tree's bin fields
    must be its own (Tree.has_bin_thresholds)."""
    ni = tree.num_leaves - 1
    k = len(SPLIT_TABLE_FIELDS)
    table = np.zeros((max(n_rows, ni, 1), k + n_words), dtype=np.int32)
    if ni <= 0:
        return table
    f = fields[tree.split_feature[:ni]]
    dt = tree.decision_type[:ni].astype(np.int32) & 0xFF
    # the leaf node i split keeps its id down the left edge of i's subtree
    node = np.arange(ni)
    while (node >= 0).any():
        node = np.where(node >= 0, tree.left_child[np.maximum(node, 0)], node)
    table[:ni, :k] = np.column_stack([
        f[:, 0], tree.threshold_in_bin[:ni], (dt >> 1) & 1, (dt >> 2) & 3,
        f[:, 1], f[:, 2], f[:, 3], f[:, 4], f[:, 5], dt & 1, ~node])
    cat = np.nonzero(dt & 1)[0]
    if cat.size:
        bounds = np.asarray(tree.cat_boundaries_inner, dtype=np.int64)
        words = np.asarray(tree.cat_threshold_inner,
                           dtype=np.uint32).view(np.int32)
        sizes = np.diff(bounds)
        owner = np.repeat(np.arange(sizes.size), sizes)
        col = np.arange(words.size) - np.repeat(bounds[:-1], sizes)
        # a word past the plane's bins holds no bin a row can have
        keep = col < n_words
        by_cat = np.zeros((sizes.size, n_words), dtype=np.int32)
        by_cat[owner[keep], col[keep]] = words[keep]
        table[cat, k:] = by_cat[tree.threshold_in_bin[cat]]
    return table


class BinnedTreeArrays(NamedTuple):
    """Per-internal-node decision fields in bin space + leaf values."""

    group: jax.Array  # [I] int32 feature-group row in the bin matrix
    threshold: jax.Array  # [I] int32 bin threshold
    default_left: jax.Array  # [I] bool
    missing_type: jax.Array  # [I] int32
    default_bin: jax.Array  # [I] int32 (feature-bin space)
    nbins: jax.Array  # [I] int32
    efb_lo: jax.Array  # [I] int32 group-bin range for EFB members
    efb_hi: jax.Array  # [I] int32
    is_efb: jax.Array  # [I] bool
    is_cat: jax.Array  # [I] bool
    cat_words: jax.Array  # [I, W] int32 the left set's bins, 32 a word
    left_child: jax.Array  # [I] int32
    right_child: jax.Array  # [I] int32
    leaf_value: jax.Array  # [L] float32


def binned_tree_arrays(tree, dataset) -> BinnedTreeArrays:
    """Host-side packing of a trained tree's decisions into bin space:
    split_table's columns, node by node."""
    ni = max(tree.num_leaves - 1, 1)
    t = split_table(tree, feature_split_fields(dataset),
                    cat_table_words(dataset), ni)[:ni]
    k = len(SPLIT_TABLE_FIELDS)
    i32 = [jnp.asarray(t[:, j], dtype=jnp.int32) for j in range(k)]
    flag = [jnp.asarray(t[:, j] != 0, dtype=jnp.bool_) for j in range(k)]
    return BinnedTreeArrays(
        group=i32[0], threshold=i32[1], default_left=flag[2],
        missing_type=i32[3], default_bin=i32[4], nbins=i32[5],
        efb_lo=i32[6], efb_hi=i32[7], is_efb=flag[8], is_cat=flag[9],
        cat_words=jnp.asarray(t[:, k:], dtype=jnp.int32),
        left_child=jnp.asarray(tree.left_child[:ni], dtype=jnp.int32),
        right_child=jnp.asarray(tree.right_child[:ni], dtype=jnp.int32),
        leaf_value=jnp.asarray(tree.leaf_value[: tree.num_leaves],
                               dtype=jnp.float32),
    )


@partial(jax.jit, static_argnames=("max_depth",))
def binned_leaf_index(ta: BinnedTreeArrays, bins: jax.Array, row_idx: jax.Array,
                      num_data: int, max_depth: int) -> jax.Array:
    """Leaf index [P] for padded row indices (sentinel num_data -> clamped
    gather; caller drops its scatter)."""
    rows = jnp.minimum(row_idx, num_data - 1)

    def body(_, node):
        active = node >= 0
        nd = jnp.maximum(node, 0)
        gb = bins[ta.group[nd], rows].astype(jnp.int32)
        # EFB translation: group bin -> natural feature bin (split_decision_bins)
        in_range = (gb >= ta.efb_lo[nd]) & (gb < ta.efb_hi[nd])
        shifted = gb - ta.efb_lo[nd]
        natural = shifted + (shifted >= ta.default_bin[nd]).astype(jnp.int32)
        fbin = jnp.where(ta.is_efb[nd],
                         jnp.where(in_range, natural, ta.default_bin[nd]), gb)
        mt = ta.missing_type[nd]
        is_missing = jnp.where(
            mt == MISSING_NAN, fbin == ta.nbins[nd] - 1,
            jnp.where(mt == MISSING_ZERO, fbin == ta.default_bin[nd], False))
        go_left = jnp.where(is_missing, ta.default_left[nd],
                            fbin <= ta.threshold[nd])
        # a categorical node: the bit of the row's bin in its left set
        # (categorical features are never bundled: the group bin is the bin)
        words = ta.cat_words[nd]
        word = jnp.take_along_axis(
            words, jnp.minimum(gb // _CAT_WORD_BITS,
                               words.shape[1] - 1)[:, None], axis=1)[:, 0]
        in_set = (jnp.right_shift(word, gb % _CAT_WORD_BITS) & 1) > 0
        go_left = jnp.where(ta.is_cat[nd], in_set, go_left)
        nxt = jnp.where(go_left, ta.left_child[nd], ta.right_child[nd])
        return jnp.where(active, nxt, node)

    node0 = jnp.zeros(row_idx.shape[0], dtype=jnp.int32)
    node = jax.lax.fori_loop(0, max_depth, body, node0)
    return ~node


def add_tree_to_score(tree, dataset, bins_dev: jax.Array, score: jax.Array,
                      row_idx: jax.Array, num_data: int,
                      max_depth: int = 0) -> jax.Array:
    """score[row] += tree.leaf_value[leaf(row)] for the given padded rows.

    max_depth should be a CONFIG-derived bound, not the tree's actual depth —
    per-tree depths would recompile the traversal for every distinct value.
    Extra iterations freeze at the leaf, so over-bounding is free.
    """
    if tree.num_leaves <= 1:
        return score.at[row_idx].add(float(tree.leaf_value[0]), mode="drop")
    ta = binned_tree_arrays(tree, dataset)
    bound = max_depth if max_depth > 0 else int(tree.max_depth)
    leaf = binned_leaf_index(ta, bins_dev, row_idx, num_data, bound)
    return score.at[row_idx].add(ta.leaf_value[leaf], mode="drop")


# ------------------------------------------------------ validation replay


@partial(jax.jit, static_argnames=("class_id",), donate_argnums=(0,))
def replay_tree_to_score(score: jax.Array, plane: jax.Array,
                         table: jax.Array, n_splits: jax.Array,
                         leaf_values: jax.Array, class_id: int) -> jax.Array:
    """score [C, Nv] plus one tree's leaf values at row class_id, the tree
    replayed over a validation set's plane [G, Nv] (rows on the lanes):

      leaf = 0 for every row; for split i (table row i, creation order),
      the rows in the split leaf that go right move to leaf i + 1

    A split reads its table row as scalars, its group's bin row by a
    dynamic row index, and decides as the learner did: numerically by
    split_decision_bins (EFB translation, the missing bin), categorically
    by the bit of the row's bin in the node's words, the word picked by
    compare-selects (bin 0 is in no left set: NaN, negative, unseen and
    rare codes go right). Each row's value is picked from leaf_values
    (padded to num_leaves: one program a run) by a chain of compare-selects
    and added in float32, the add the traversal's output takes. The score
    is donated."""
    with jax.named_scope(SCOPE_VALID_SCORE):
        k = len(SPLIT_TABLE_FIELDS)
        n_words = table.shape[1] - k

        def split(i, leaf):
            row = table[i]
            gb = jax.lax.dynamic_index_in_dim(plane, row[0], axis=0,
                                              keepdims=False).astype(jnp.int32)
            go_left = split_decision_bins(gb, row[1:9])
            hi = gb // _CAT_WORD_BITS
            word = jnp.zeros_like(gb)
            for w in range(n_words):
                word = jnp.where(hi == w, row[k + w], word)
            in_set = (jnp.right_shift(word, gb % _CAT_WORD_BITS) & 1) > 0
            go_left = jnp.where(row[9] > 0, in_set, go_left)
            return jnp.where((leaf == row[10]) & ~go_left, i + 1, leaf)

        leaf = jax.lax.fori_loop(0, n_splits, split,
                                 jnp.zeros(plane.shape[1], jnp.int32))
        value = jnp.zeros(plane.shape[1], leaf_values.dtype)
        for l in range(leaf_values.shape[0]):
            value = jnp.where(leaf == l, leaf_values[l], value)
        return score.at[class_id].add(value)
