"""Vectorized tree-ensemble inference on TPU.

TPU-native replacement for the reference's per-row recursive traversal
(Tree::Predict / NumericalDecision, include/LightGBM/tree.h:338-420, and
GBDT::PredictRaw, src/boosting/gbdt_prediction.cpp:15-56). All trees are
packed into padded [T, nodes] SoA tensors (`pack_ensemble`), and the pack
says which of two programs scores it (`PackedEnsemble.dense`, chosen from
what the pack can see; `fused_program`):

  * the DENSE evaluation (`_predict_raw_dense`), the TPU's program for
    numerical forests: no gather. Every node of every tree decides for
    every row (`numerical_go_left` on whole rows of X^T selected by the
    nodes' feature ids, bit-exact), and a batched product with the
    constant path matrix of `path_tables` on the MXU finds the one leaf
    whose path agrees with all the decisions: exact, and independent of
    depth. Rows and trees are blocked inside the one program.
  * the gather TRAVERSAL (`_predict_raw_fused`), the plain version: ONE
    level-synchronous gather loop over the whole forest, every (row, tree)
    pair advancing one level per step, rows that reached a leaf (negative
    node id) freezing, each level issuing a single X gather for all T
    trees. It scores categorical forests (a bitset lookup is a gather by
    nature), linear-leaf forests, float64 packs, forests whose path tables
    would pass DENSE_PATH_BYTES_MAX, everything off the TPU (a gather is
    cheap there, L * I multiply-adds a (row, tree) are not), and it is
    what `predict_leaf_indices` and early stopping walk.

Both state the numerical decision once (`numerical_go_left`). Scores
accumulate in-register: the [T, N] per-tree score matrix is never
materialized.

Serving-path machinery on top of the traversal:

  * `PredictorCache` — packs the ensemble once per (model version, tree
    slice, dtype) and keeps it device-resident across Booster.predict
    calls; training/refit/rollback/model-load invalidate it.
  * `predict_raw_streamed` — power-of-two row chunks with
    copy_to_host_async double buffering for large N.
  * `predict_raw_early_stop` — device-resident: scores and the active-row
    mask stay on device; the only per-block host sync is one scalar.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict, deque
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry, tracing
from ..common import MISSING_NAN, MISSING_ZERO, K_ZERO_THRESHOLD
from ..models.tree import Tree
from ..utils.backend import on_tpu
from ..utils.log import Log
from ..utils.timer import (SCOPE_ACCUMULATE, SCOPE_DECIDE,
                           SCOPE_FEATURE_GATHER, SCOPE_LEAF_VALUES,
                           SCOPE_NODE_GATHER, SCOPE_PATH_MATCH,
                           SPAN_PREDICT_CHUNK,
                           SPAN_PREDICT_FETCH, SPAN_PREDICT_TRAVERSE,
                           SPAN_PREDICT_UPLOAD, global_timer)

_EPS = K_ZERO_THRESHOLD


@dataclass
class PackedEnsemble:
    """Device-resident padded arrays for a list of trees.

    Shapes: T = number of trees, I = max internal nodes, L = max leaves,
    W = total categorical bitset words (>=1).
    """

    split_feature: jax.Array  # [T, I] int32
    threshold: jax.Array  # [T, I] float
    decision_type: jax.Array  # [T, I] int32
    left_child: jax.Array  # [T, I] int32
    right_child: jax.Array  # [T, I] int32
    leaf_value: jax.Array  # [T, L] float
    cat_words: jax.Array  # [W] uint32 bitset words (real-value space)
    cat_offset: jax.Array  # [T, I] int32 word offset for categorical nodes
    cat_n_words: jax.Array  # [T, I] int32
    num_leaves: jax.Array  # [T] int32
    max_depth: int
    num_trees: int
    # linear-tree per-leaf models (tree.h leaf_const_/leaf_coeff_/leaf_features_)
    linear: bool = False  # static: gates the linear output path
    lin_const: Optional[jax.Array] = None  # [T, L] (leaf_value for non-linear trees)
    lin_feat: Optional[jax.Array] = None  # [T, L, K] int32, -1 padding
    lin_coeff: Optional[jax.Array] = None  # [T, L, K]
    # the dense evaluation's constants (path_tables); None on a gather pack
    dense: bool = False  # static: which program predict_raw dispatches
    path: Optional[jax.Array] = None  # [T, Lp, Ip] int8 in {+1, -1, 0}
    path_depth: Optional[jax.Array] = None  # [T, Lp] float32, +inf padding

    def tree_slice(self, start: int, end: int) -> "PackedEnsemble":
        return PackedEnsemble(
            split_feature=self.split_feature[start:end],
            threshold=self.threshold[start:end],
            decision_type=self.decision_type[start:end],
            left_child=self.left_child[start:end],
            right_child=self.right_child[start:end],
            leaf_value=self.leaf_value[start:end],
            cat_words=self.cat_words,
            cat_offset=self.cat_offset[start:end],
            cat_n_words=self.cat_n_words[start:end],
            num_leaves=self.num_leaves[start:end],
            max_depth=self.max_depth,
            num_trees=end - start,
            linear=self.linear,
            lin_const=self.lin_const[start:end] if self.linear else None,
            lin_feat=self.lin_feat[start:end] if self.linear else None,
            lin_coeff=self.lin_coeff[start:end] if self.linear else None,
            dense=self.dense,
            path=self.path[start:end] if self.dense else None,
            path_depth=self.path_depth[start:end] if self.dense else None,
        )


jax.tree_util.register_pytree_node(
    PackedEnsemble,
    lambda p: ((p.split_feature, p.threshold, p.decision_type, p.left_child,
                p.right_child, p.leaf_value, p.cat_words, p.cat_offset,
                p.cat_n_words, p.num_leaves, p.lin_const, p.lin_feat,
                p.lin_coeff, p.path, p.path_depth),
               (p.max_depth, p.num_trees, p.linear, p.dense)),
    lambda aux, ch: PackedEnsemble(
        *ch[:10], max_depth=aux[0], num_trees=aux[1], linear=aux[2],
        lin_const=ch[10], lin_feat=ch[11], lin_coeff=ch[12], dense=aux[3],
        path=ch[13], path_depth=ch[14]),
)


# The dense evaluation (_predict_raw_dense) keeps T * Lp * Ip bytes of path
# constants on the device where the traversal keeps T * I words, and pays
# O(L * I) a (row, tree) where the traversal pays O(depth). The benchmark's
# 500 trees of 255 leaves need 32.8 MB and score 50x and more faster dense
# (PERF.md section 6, PR 27); trees of 4,095 leaves would need 16.8 MB EACH.
# A forest over this many bytes keeps the gather traversal.
DENSE_PATH_BYTES_MAX = 256 << 20
_DENSE_PAD = 32  # Lp, Ip: multiples of the int8 sublane tile


def path_tables(left_child: np.ndarray, right_child: np.ndarray,
                num_leaves: np.ndarray, Lp: int, Ip: int):
    """The constants of the dense evaluation, from the padded [T, I] child
    tables (internal node j >= 0, leaf l as ~l):

      path[t, l, i]  +1 where leaf l lies under the LEFT child of node i,
                     -1 under the right, 0 where i is not on l's path
      depth[t, l]    the number of nodes on l's path: with d[i] = +1 for
                     "row goes left at i" and -1 for right, the row is in
                     leaf l iff sum_i path[l, i] * d[i] == depth[l]. 0 for
                     a stump's one leaf (the empty sum matches); +inf, which
                     no sum reaches, for the leaves a tree does not have.

    Every leaf of every tree climbs one level per pass, so the host work is
    max_depth vectorised passes over [T, L]."""
    T, I = left_child.shape
    L = I + 1
    node_up = np.full((T, I), -1, dtype=np.int64)  # parent of an internal node
    node_side = np.zeros((T, I), dtype=np.int8)
    leaf_up = np.full((T, L), -1, dtype=np.int64)
    leaf_side = np.zeros((T, L), dtype=np.int8)
    real = np.arange(I)[None, :] < (num_leaves[:, None] - 1)
    for side, child in ((1, left_child), (-1, right_child)):
        t, i = np.nonzero(real & (child < 0))
        leaf_up[t, ~child[t, i]] = i
        leaf_side[t, ~child[t, i]] = side
        t, i = np.nonzero(real & (child >= 0))
        node_up[t, child[t, i]] = i
        node_side[t, child[t, i]] = side
    path = np.zeros((T, Lp, Ip), dtype=np.int8)
    depth = np.zeros((T, L), dtype=np.int64)
    at, side = leaf_up, leaf_side
    for _ in range(I):  # a path holds at most I nodes: a cyclic table ends
        on = at >= 0
        if not on.any():
            break
        t, l = np.nonzero(on)
        path[t, l, at[t, l]] = side[t, l]
        depth += on
        up = np.where(on, at, 0)
        side = np.take_along_axis(node_side, up, axis=1)
        at = np.where(on, np.take_along_axis(node_up, up, axis=1), -1)
    depth_p = np.full((T, Lp), np.inf, dtype=np.float32)
    has = np.arange(L)[None, :] < num_leaves[:, None]
    depth_p[:, :L][has] = depth[has]
    return path, depth_p


def pack_ensemble(trees: Sequence[Tree], dtype=jnp.float32,
                  fixed_leaves: int = 0, fixed_depth: int = 0,
                  fixed_cat_words: int = 0) -> PackedEnsemble:
    """Pack host Tree objects into padded device tensors.

    fixed_leaves / fixed_depth / fixed_cat_words force the padded node
    count, traversal depth and categorical bitset length, keeping shapes
    stable across repeated packs (per-iteration validation scoring) so jit
    caches are reused.
    """
    with global_timer.scope("predict_pack"):
        T = max(len(trees), 1)
        I = max(max((t.num_leaves - 1 for t in trees), default=1), 1,
                fixed_leaves - 1)
        L = max(max((t.num_leaves for t in trees), default=1), 1, fixed_leaves)
        sf = np.zeros((T, I), dtype=np.int32)
        th = np.zeros((T, I), dtype=np.float64)
        dt = np.zeros((T, I), dtype=np.int32)
        lc = np.full((T, I), -1, dtype=np.int32)
        rc = np.full((T, I), -1, dtype=np.int32)
        lv = np.zeros((T, L), dtype=np.float64)
        nl = np.ones(T, dtype=np.int32)
        co = np.zeros((T, I), dtype=np.int32)
        cw_n = np.zeros((T, I), dtype=np.int32)
        cat_words: List[int] = []
        max_depth = 1
        for k, tree in enumerate(trees):
            ni = tree.num_leaves - 1
            nl[k] = tree.num_leaves
            if ni > 0:
                sf[k, :ni] = tree.split_feature[:ni]
                th[k, :ni] = tree.threshold[:ni]
                dt[k, :ni] = tree.decision_type[:ni].astype(np.int32) & 0xFF
                lc[k, :ni] = tree.left_child[:ni]
                rc[k, :ni] = tree.right_child[:ni]
                max_depth = max(max_depth, tree.max_depth)
                for node in range(ni):
                    if dt[k, node] & 1:  # categorical
                        cat_idx = int(tree.threshold[node])
                        lo, hi = tree.cat_boundaries[cat_idx], tree.cat_boundaries[cat_idx + 1]
                        co[k, node] = len(cat_words)
                        cw_n[k, node] = hi - lo
                        cat_words.extend(tree.cat_threshold[lo:hi])
            lv[k, : tree.num_leaves] = tree.leaf_value[: tree.num_leaves]
        any_linear = any(t.is_linear for t in trees)
        lin_const = lin_feat = lin_coeff = None
        if any_linear:
            K = max((len(t.leaf_features[i]) for t in trees if t.is_linear
                     for i in range(t.num_leaves)), default=0)
            lin_const = lv.copy()  # non-linear trees fall through to leaf_value
            lin_feat = np.full((T, L, K), -1, dtype=np.int32)
            lin_coeff = np.zeros((T, L, K), dtype=np.float64)
            for k, tree in enumerate(trees):
                if not tree.is_linear or tree.leaf_const is None:
                    continue
                lin_const[k, : tree.num_leaves] = tree.leaf_const[: tree.num_leaves]
                for i in range(tree.num_leaves):
                    nf = len(tree.leaf_features[i])
                    if nf:
                        lin_feat[k, i, :nf] = tree.leaf_features[i]
                        lin_coeff[k, i, :nf] = tree.leaf_coeff[i]
        cat_words += [0] * (max(fixed_cat_words, 1) - len(cat_words))
        # float64 thresholds only take effect with jax x64 enabled; otherwise
        # jnp.asarray would silently round-to-nearest down to f32, so route through
        # the decision-preserving round-toward--inf downcast instead.
        f64_effective = dtype == jnp.float64 and jax.config.jax_enable_x64
        if not f64_effective:
            # Round thresholds toward -inf when downcasting: for any float32 x,
            # (x <= t64) == (x <= rounddown32(t64)), so device decisions over
            # float32 inputs exactly match the float64 reference semantics.
            th32 = th.astype(np.float32)
            over = th32.astype(np.float64) > th
            th32[over] = np.nextafter(th32[over], -np.inf)
            th = th32
        # which program scores this pack, from what the pack can see: a
        # categorical node is a bitset lookup (a gather by nature), linear
        # leaves keep eager score math, a float64 pack keeps its width, and
        # off the TPU a gather is cheap where L * I multiply-adds a (row,
        # tree) are not
        Lp, Ip = (-(-d // _DENSE_PAD) * _DENSE_PAD for d in (L, I))
        dense = (len(trees) > 0 and not any_linear
                 and np.dtype(dtype) == np.float32 and not (dt & 1).any()
                 and T * Lp * Ip <= DENSE_PATH_BYTES_MAX and on_tpu())
        path = path_depth = None
        if dense:
            path, path_depth = path_tables(lc, rc, nl, Lp, Ip)
            path = jnp.asarray(path, dtype=jnp.int8)
            path_depth = jnp.asarray(path_depth, dtype=jnp.float32)
        return PackedEnsemble(
            split_feature=jnp.asarray(sf, dtype=jnp.int32),
            threshold=jnp.asarray(th, dtype=jnp.float64 if f64_effective else jnp.float32),
            decision_type=jnp.asarray(dt, dtype=jnp.int32),
            left_child=jnp.asarray(lc, dtype=jnp.int32),
            right_child=jnp.asarray(rc, dtype=jnp.int32),
            leaf_value=jnp.asarray(lv, dtype=dtype),
            cat_words=jnp.asarray(np.array(cat_words, dtype=np.uint32),
                                  dtype=jnp.uint32),
            cat_offset=jnp.asarray(co, dtype=jnp.int32),
            cat_n_words=jnp.asarray(cw_n, dtype=jnp.int32),
            num_leaves=jnp.asarray(nl, dtype=jnp.int32),
            max_depth=max(int(max_depth), fixed_depth),
            num_trees=len(trees),
            linear=any_linear,
            lin_const=jnp.asarray(lin_const, dtype=dtype) if any_linear else None,
            lin_feat=jnp.asarray(lin_feat, dtype=jnp.int32) if any_linear else None,
            lin_coeff=jnp.asarray(lin_coeff, dtype=dtype) if any_linear else None,
            dense=dense, path=path, path_depth=path_depth,
        )


def predict_dtype(X):
    """Device dtype for a predict input: f64 inputs keep f64 when jax x64
    is enabled (models whose thresholds need the full mantissa); everything
    else runs f32 — safe because pack_ensemble's round-toward--inf
    threshold downcast keeps f32 decisions identical to the f64 reference."""
    if getattr(X, "dtype", None) == np.float64 and jax.config.jax_enable_x64:
        return jnp.float64
    return jnp.float32


# --------------------------------------------------------------- traversal


def numerical_go_left(fval: jax.Array, thr: jax.Array,
                      dt: jax.Array) -> jax.Array:
    """NumericalDecision (tree.h:338-355), the one statement of the rule:
    True where a row holding `fval` goes LEFT at a numerical node with
    threshold `thr` and decision type `dt` (bit 1: default_left, bits 2-3:
    missing_type). The gather traversal hands it gathered [N, T] node
    fields, the dense evaluation [.., I, 1] node constants that broadcast
    along the rows."""
    default_left = (dt & 2) > 0
    missing_type = (dt >> 2) & 3
    is_nan = jnp.isnan(fval)
    fval_num = jnp.where(is_nan & (missing_type != MISSING_NAN), 0.0, fval)
    is_missing = ((missing_type == MISSING_ZERO)
                  & (jnp.abs(fval_num) <= _EPS)) | (
        (missing_type == MISSING_NAN) & jnp.isnan(fval_num))
    return jnp.where(is_missing, default_left, fval_num <= thr)


def forest_level_step(X: jax.Array, node: jax.Array, sf: jax.Array,
                      th: jax.Array, dt: jax.Array, lc: jax.Array,
                      rc: jax.Array, co: jax.Array, cn: jax.Array,
                      cat_words: jax.Array) -> jax.Array:
    """Advance every (row, tree) pair one level: node [N, T] -> [N, T].

    Node attributes for ALL T trees' current nodes gather from the
    flattened [T*I] tables in one shot, and the feature values for the
    whole forest come from ONE take_along_axis over X — the per-tree
    formulation issued T X-gathers per level."""
    I = sf.shape[1]
    T = sf.shape[0]
    with jax.named_scope(SCOPE_NODE_GATHER):
        tree_base = jnp.arange(T, dtype=jnp.int32)[None, :] * I
        nd = tree_base + jnp.maximum(node, 0)  # flat [N, T] into [T*I] tables
        feat = sf.reshape(-1)[nd]
        d = dt.reshape(-1)[nd]
        thr = th.reshape(-1)[nd]
        n_words = cn.reshape(-1)[nd]
        word_off = co.reshape(-1)[nd]
        left = lc.reshape(-1)[nd]
        right = rc.reshape(-1)[nd]
    with jax.named_scope(SCOPE_FEATURE_GATHER):
        fval = jnp.take_along_axis(X, feat, axis=1)  # ONE X gather per level
    with jax.named_scope(SCOPE_DECIDE):
        active = node >= 0
        is_cat = (d & 1) > 0
        go_left_num = numerical_go_left(fval, thr, d)
        # --- categorical decision (tree.h:375-388)
        int_fval = jnp.where(jnp.isnan(fval), -1, fval.astype(jnp.int32))
        word_idx = jnp.clip(int_fval, 0, None) // 32
        bit_idx = jnp.clip(int_fval, 0, None) % 32
        in_range = (int_fval >= 0) & (word_idx < n_words)
        with jax.named_scope(SCOPE_NODE_GATHER):
            word = cat_words[jnp.clip(word_off + word_idx, 0,
                                      cat_words.shape[0] - 1)]
        go_left_cat = in_range & (
            ((word >> bit_idx.astype(jnp.uint32)) & 1) > 0)
        go_left = jnp.where(is_cat, go_left_cat, go_left_num)
        nxt = jnp.where(go_left, left, right)
        return jnp.where(active, nxt, node)


def _traverse_leaves(packed: PackedEnsemble, X: jax.Array) -> jax.Array:
    """[N, T] leaf index per row per tree, level-synchronous over the
    whole forest."""
    n = X.shape[0]
    T = packed.split_feature.shape[0]
    with jax.named_scope(SCOPE_DECIDE):
        node0 = jnp.zeros((n, T), dtype=jnp.int32)

    def body(carry):
        level, node = carry
        return level + 1, forest_level_step(
            X, node, packed.split_feature, packed.threshold,
            packed.decision_type, packed.left_child, packed.right_child,
            packed.cat_offset, packed.cat_n_words, packed.cat_words)

    def cond(carry):
        # the static depth bound, or sooner once every row sits at a leaf
        # (a padded bound of num_leaves - 1 levels would walk ~10x the
        # levels a 255-leaf tree has)
        level, node = carry
        return (level < packed.max_depth) & jnp.any(node >= 0)

    _, node = jax.lax.while_loop(cond, body, (jnp.int32(0), node0))
    # a leaf id is the bitwise complement of the (negative) frozen node;
    # single-leaf (constant) trees sit at leaf 0
    with jax.named_scope(SCOPE_LEAF_VALUES):
        return jnp.where(packed.num_leaves[None, :] <= 1, 0, ~node)


@jax.named_scope(SCOPE_LEAF_VALUES)
def _leaf_scores(packed: PackedEnsemble, X: jax.Array,
                 leaf: jax.Array) -> jax.Array:
    """Per-(row, tree) scores [N, T] from leaf assignments. Linear-tree
    ensembles evaluate const + coeffs . raw features, falling back to the
    constant leaf value when any model feature is NaN/inf
    (Tree::PredictByMap linear path, src/io/tree.cpp) — vectorized across
    trees with one [N, T*K] X gather."""
    T, L = packed.leaf_value.shape
    flat = jnp.arange(T, dtype=jnp.int32)[None, :] * L + leaf  # [N, T]
    base = packed.leaf_value.reshape(-1)[flat]
    if not packed.linear:
        return base
    n = X.shape[0]
    K = packed.lin_feat.shape[2]
    feats = packed.lin_feat.reshape(T * L, K)[flat]  # [N, T, K]
    used = feats >= 0
    fv = jnp.take_along_axis(
        X, jnp.clip(feats, 0, X.shape[1] - 1).reshape(n, T * K),
        axis=1).reshape(n, T, K)
    bad = (used & ~jnp.isfinite(fv)).any(axis=2)
    fv = jnp.where(used, fv, 0.0)
    lin = packed.lin_const.reshape(-1)[flat] + jnp.where(
        used, packed.lin_coeff.reshape(T * L, K)[flat] * fv, 0.0).sum(axis=2)
    return jnp.where(bad, base, lin)


@partial(jax.jit, static_argnames=("num_tree_per_iteration",))
def _predict_raw_fused(packed: PackedEnsemble, X: jax.Array,
                       num_tree_per_iteration: int) -> jax.Array:
    """Fused traverse + score + per-class accumulate: [N, C] without ever
    materializing the [T, N] per-tree score matrix."""
    leaf = _traverse_leaves(packed, X)
    vals = _leaf_scores(packed, X, leaf)
    n, T = vals.shape
    with jax.named_scope(SCOPE_ACCUMULATE):
        return vals.reshape(n, T // num_tree_per_iteration,
                            num_tree_per_iteration).sum(axis=1)


# ------------------------------------------------------------------- dense
#
# The TPU's program for numerical forests (PackedEnsemble.dense): no gather.
# EVERY node of every tree decides for every row, rows on the lanes
# ([nodes, rows], so node constants broadcast and no array has a tiny minor
# dimension), and the row's leaf is the one whose path constants agree with
# all its decisions: one batched [L, I] @ [I, rows] product a tree on the
# MXU. Cost does not depend on depth. One (tree block, row chunk) step of
# the loops below holds _DENSE_STEP_ELEMS node x row elements at a time, so
# the [T*I, N] intermediates never exist whole.

# Block sizes read on the chip at the benchmark's 500 x 255 forest, 131,072
# rows (PERF.md section 6, PR 27): 2,048-row chunks of 2^24-element steps
# score a call in 0.175 s; 8,192 rows or 2^25 elements take 0.21-0.28 s.
_DENSE_ROW_CHUNK = 2048
_DENSE_STEP_ELEMS = 1 << 24


def _dense_leaf_match(xt: jax.Array, sf: jax.Array, thr: jax.Array,
                      dt: jax.Array, path: jax.Array,
                      depth: jax.Array) -> jax.Array:
    """match[t, l, r]: row r of the chunk xt [F, rows] is in leaf l of tree
    t, for a block of trees given by their [tb, Ip] node constants and
    their path_tables. Exactly one real leaf of a tree matches a row."""
    tb, Ip = sf.shape
    with jax.named_scope(SCOPE_FEATURE_GATHER):
        # whole rows of the chunk's X^T by the nodes' feature ids: each
        # gathered element is `rows` contiguous floats, copied, so fval
        # holds the input's bits (an id past the last feature reads NaN,
        # as the traversal's gather does)
        fval = jnp.take(xt, sf.reshape(-1), axis=0).reshape(
            tb, Ip, xt.shape[1])
    with jax.named_scope(SCOPE_DECIDE):
        d = jnp.where(numerical_go_left(fval, thr[:, :, None],
                                        dt[:, :, None]), 1, -1
                      ).astype(jnp.bfloat16)
    with jax.named_scope(SCOPE_PATH_MATCH):
        # +-1/0 operands are exact in bfloat16 and the sums are integers of
        # at most Ip terms accumulated in float32: the match is exact
        s = jnp.einsum("tli,tir->tlr", path.astype(jnp.bfloat16), d,
                       preferred_element_type=jnp.float32)
    with jax.named_scope(SCOPE_LEAF_VALUES):
        return s == depth[:, :, None]


@partial(jax.jit, static_argnames=("num_tree_per_iteration",))
def _predict_raw_dense(packed: PackedEnsemble, X: jax.Array,
                       num_tree_per_iteration: int) -> jax.Array:
    """[N, C] raw scores of a dense pack, gather-free (above); the pack's
    trees must cover whole iterations."""
    C = num_tree_per_iteration
    n = X.shape[0]
    T, Lp, Ip = packed.path.shape
    rows = min(_DENSE_ROW_CHUNK, -(-max(n, 1) // 128) * 128)
    n_chunks = -(-n // rows)
    # whole iterations a block, so a block's trees fold onto the classes
    tb = min(C * max(1, _DENSE_STEP_ELEMS // (Ip * rows * C)), T)
    n_blocks = -(-T // tb)

    def blocks(a, to, fill=0):
        """[T, k, ..] -> [n_blocks, tb, to, ..]: padded nodes and leaves
        are off every path, padded trees have no leaf to match."""
        pad = [(0, n_blocks * tb - T), (0, to - a.shape[1])] \
            + [(0, 0)] * (a.ndim - 2)
        a = jnp.pad(a, pad, constant_values=fill)
        return a.reshape((n_blocks, tb) + a.shape[1:])

    with jax.named_scope(SCOPE_FEATURE_GATHER):
        xt = jnp.pad(X.T, ((0, 0), (0, n_chunks * rows - n)))
        xt = xt.reshape(X.shape[1], n_chunks, rows).transpose(1, 0, 2)
    tables = (blocks(packed.split_feature, Ip), blocks(packed.threshold, Ip),
              blocks(packed.decision_type, Ip),
              blocks(packed.path, Lp),
              blocks(packed.path_depth, Lp, jnp.inf),
              blocks(packed.leaf_value, Lp))

    def chunk(xt_c):
        def block(acc, tab):
            *node_tables, lv = tab
            match = _dense_leaf_match(xt_c, *node_tables)
            with jax.named_scope(SCOPE_LEAF_VALUES):
                # one leaf of a tree matches: the sum has one term
                vals = jnp.where(match, lv[:, :, None], 0).sum(axis=1)
            with jax.named_scope(SCOPE_ACCUMULATE):
                return acc + vals.reshape(tb // C, C, rows).sum(axis=0), None

        with jax.named_scope(SCOPE_ACCUMULATE):
            acc0 = jnp.zeros((C, rows), dtype=packed.leaf_value.dtype)
        return jax.lax.scan(block, acc0, tables)[0]

    out = jax.lax.map(chunk, xt)  # [n_chunks, C, rows]
    with jax.named_scope(SCOPE_ACCUMULATE):
        return out.transpose(0, 2, 1).reshape(n_chunks * rows, C)[:n]


def fused_program(packed: PackedEnsemble):
    """The jitted program that scores this pack: the pack says which."""
    return _predict_raw_dense if packed.dense else _predict_raw_fused


_leaf_indices_fused = jax.jit(_traverse_leaves)


def predict_leaf_indices(packed: PackedEnsemble, X: jax.Array) -> jax.Array:
    """[N, T] leaf index per row per tree."""
    if packed.num_trees == 0:
        return jnp.zeros((X.shape[0], 0), dtype=jnp.int32)
    with global_timer.scope(SPAN_PREDICT_TRAVERSE):
        return _leaf_indices_fused(packed, X)


def validate_tree_count(packed: PackedEnsemble,
                        num_tree_per_iteration: int) -> None:
    """The packed tree count must cover whole iterations: a ragged slice
    would mis-assign trees to classes in the per-class accumulate."""
    if num_tree_per_iteration > 0 \
            and packed.num_trees % num_tree_per_iteration != 0:
        Log.fatal(
            "Cannot predict with %d trees grouped %d per iteration: the "
            "slice does not cover whole iterations (check num_iteration / "
            "start_iteration against the model's tree count)",
            packed.num_trees, num_tree_per_iteration)


def predict_raw(packed: PackedEnsemble, X: jax.Array,
                num_tree_per_iteration: int = 1) -> jax.Array:
    """Raw scores [N, num_tree_per_iteration] summed over iterations. The
    one boundary of the `predict_traverse` span in this module: whichever
    program scores, its dispatch is inside it once, and one
    `predict_traverse` flight note and one counter say which it was."""
    T = packed.num_trees
    if T == 0:
        return jnp.zeros((X.shape[0], num_tree_per_iteration), dtype=X.dtype)
    validate_tree_count(packed, num_tree_per_iteration)
    tracing.note(SPAN_PREDICT_TRAVERSE, dense=int(packed.dense),
                 rows=int(X.shape[0]), trees=T)
    global_timer.add_count(
        "predict_dense_calls" if packed.dense else "predict_gather_calls", 1)
    with global_timer.scope(SPAN_PREDICT_TRAVERSE):
        if packed.linear:
            # under jit XLA contracts the linear mul+sum into fmas, a 1-ulp
            # drift vs the eager reference arithmetic; keep the score math
            # eager (the traversal is integer-only and stays jitted)
            leaf = _leaf_indices_fused(packed, X)
            vals = _leaf_scores(packed, X, leaf)
            n, T = vals.shape
            return vals.reshape(n, T // num_tree_per_iteration,
                                num_tree_per_iteration).sum(axis=1)
        return fused_program(packed)(packed, X, num_tree_per_iteration)


# --------------------------------------------------------------------- aot
#
# Ahead-of-time compiled predict executables for serving warm start.
# A warm writer lowers + compiles the fused traversal for each micro-batch
# bucket shape, serializes the executables (jax.experimental.
# serialize_executable), and the bundle persists next to the model
# checkpoint (checkpoint.write_aot_sidecar). A cold replica deserializes
# in milliseconds instead of paying one XLA compile per bucket before its
# first answer. Safety: an executable is specialized on SHAPES only — the
# packed ensemble is a runtime argument — so a loaded executable can never
# produce a wrong answer for a key-matched call; staleness is an
# ENVIRONMENT property (jax/jaxlib build, backend, device kind), checked
# against the bundle's fingerprint at load, and any mismatch falls back
# to a fresh compile with a warning.

AOT_FORMAT_VERSION = 1


def aot_environment() -> dict:
    """The environment fingerprint an AOT bundle is valid for. XLA
    executables are build- and target-specific: every field here must
    match between writer and loader or deserialization is refused."""
    import jaxlib

    try:
        dev = jax.devices()[0]
        kind, platform = str(dev.device_kind), str(dev.platform)
    except Exception:  # noqa: BLE001 - no backend: still fingerprintable
        kind, platform = "", ""
    return {
        "format": AOT_FORMAT_VERSION,
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib.version, "__version__", ""),
        "backend": jax.default_backend(),
        "platform": platform,
        "device_kind": kind,
    }


def aot_call_key(packed: PackedEnsemble, n_rows: int, n_cols: int,
                 num_tree_per_iteration: int, x_dtype) -> tuple:
    """Exact dispatch key: every packed leaf's (shape, dtype) plus the
    input block shape/dtype and the static tree grouping. Matching this
    key guarantees the executable's input avals match the call."""
    leaves = jax.tree_util.tree_leaves(packed)
    return (tuple((tuple(int(s) for s in leaf.shape), str(leaf.dtype))
                  for leaf in leaves),
            (int(n_rows), int(n_cols)), np.dtype(x_dtype).name,
            int(num_tree_per_iteration))


def aot_compile(packed: PackedEnsemble, n_rows: int, n_cols: int,
                num_tree_per_iteration: int, x_dtype=np.float32):
    """Lower + compile the fused traversal for one bucket shape without
    touching (or populating) the jit dispatch cache."""
    xs = jax.ShapeDtypeStruct((int(n_rows), int(n_cols)),
                              np.dtype(x_dtype))
    return fused_program(packed).lower(
        packed, xs, num_tree_per_iteration=num_tree_per_iteration).compile()


def aot_serialize_bundle(packed: PackedEnsemble, n_cols: int,
                         num_tree_per_iteration: int,
                         buckets: Sequence[int], x_dtype=np.float32,
                         model_sha256: str = "") -> bytes:
    """Compile and serialize one executable per bucket row count into a
    self-describing bundle (environment fingerprint + model hash +
    keyed payloads). Linear packs are refused: their score math runs
    eagerly for bit-stability (see predict_raw), so there is no single
    executable to persist."""
    import pickle

    from jax.experimental.serialize_executable import serialize

    if packed.linear:
        raise ValueError("AOT bundles cover the fused traversal only; "
                         "linear-tree ensembles keep eager score math")
    entries = []
    with global_timer.scope("predict_aot_export"):
        for rows in buckets:
            compiled = aot_compile(packed, rows, n_cols,
                                   num_tree_per_iteration, x_dtype)
            payload, in_tree, out_tree = serialize(compiled)
            entries.append({
                "key": aot_call_key(packed, rows, n_cols,
                                    num_tree_per_iteration, x_dtype),
                "rows": int(rows),
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            })
    return pickle.dumps({
        "environment": aot_environment(),
        "model_sha256": model_sha256,
        "entries": entries,
    }, protocol=pickle.HIGHEST_PROTOCOL)


def aot_load_bundle(blob: bytes, model_sha256: Optional[str] = None):
    """Deserialize a bundle into {call_key: loaded_executable}.

    Returns (executables, problems). A non-empty `problems` list means the
    bundle was REFUSED (environment fingerprint mismatch, wrong model
    hash, damaged payload) and the mapping is empty — the caller logs the
    reasons and falls back to fresh compilation; a stale bundle can cost a
    compile, never a wrong answer."""
    import pickle

    from jax.experimental.serialize_executable import deserialize_and_load

    problems: List[str] = []
    try:
        obj = pickle.loads(blob)
    except Exception as exc:  # noqa: BLE001 - any damage -> refuse
        return {}, [f"undecodable AOT bundle: {exc!r}"]
    env, want = aot_environment(), obj.get("environment")
    if want != env:
        diff = sorted(k for k in set(env) | set(want or {})
                      if (want or {}).get(k) != env.get(k))
        problems.append(
            "environment fingerprint mismatch on "
            + ", ".join(f"{k}: bundle {((want or {}).get(k))!r} != "
                        f"here {env.get(k)!r}" for k in diff))
    if model_sha256 and obj.get("model_sha256") \
            and obj["model_sha256"] != model_sha256:
        problems.append(
            f"bundle was exported for model sha "
            f"{str(obj['model_sha256'])[:12]}.., loading {model_sha256[:12]}..")
    if problems:
        return {}, problems
    out = {}
    with global_timer.scope("predict_aot_load"):
        for ent in obj.get("entries", ()):
            try:
                # aot_compile lowers for the default device alone; without
                # execution_devices the loader binds the executable to
                # EVERY local device and the first call fails on its shard
                # count
                out[ent["key"]] = deserialize_and_load(
                    ent["payload"], ent["in_tree"], ent["out_tree"],
                    execution_devices=jax.devices()[:1])
            except Exception as exc:  # noqa: BLE001 - refuse the bundle
                return {}, [f"executable for {ent.get('rows')} rows failed "
                            f"to deserialize: {exc!r}"]
    return out, []


# ------------------------------------------------------------------- cache


class PredictorCache:
    """Device-resident PackedEnsemble cache for the serving path.

    Repeated Booster.predict calls reuse the packed arrays already on
    device instead of re-packing and re-uploading the ensemble per call.
    Keys are (model version, tree slice, dtype); any mutation of the model
    list — training an iteration, refit, rollback, loading a model — must
    call invalidate(), which bumps the version and drops every entry. A
    small LRU bound keeps sliced predicts (num_iteration / staged CV
    evaluation) from pinning unbounded HBM.

    Thread safety: the serving layer hammers `get` from batcher threads
    while hot-swap / training calls `invalidate` — both mutate the
    OrderedDict (move_to_end, insert, popitem), so every access holds one
    lock. The version snapshot is taken INSIDE the lock: a get racing an
    invalidate either sees the old version's entry (still bit-correct for
    the tree list it was packed from) or packs fresh under the new version,
    never a half-evicted entry. Packing on a miss happens under the lock
    too — concurrent misses for one key must not upload the ensemble
    twice."""

    def __init__(self, capacity: int = 4) -> None:
        self.capacity = capacity
        self._version = 0
        self._entries: "OrderedDict[tuple, PackedEnsemble]" = OrderedDict()
        # AOT warm-start executables (aot_load_bundle), keyed by the exact
        # aot_call_key. Shape-specialized, value-free: any key-matched call
        # is correct by construction. Dropped on invalidate with the packs
        # — a mutated model changes pack shapes, so stale keys would only
        # miss, but holding dead executables pins memory for nothing.
        self._aot: dict = {}
        self._lock = threading.Lock()

    def invalidate(self) -> None:
        with self._lock:
            self._version += 1
            self._entries.clear()
            self._aot.clear()

    # ------------------------------------------------------------- aot

    def install_aot(self, executables: dict) -> int:
        """Install {aot_call_key: loaded_executable} (serving warm start).
        Returns the number now installed."""
        with self._lock:
            self._aot.update(executables)
            return len(self._aot)

    def aot_get(self, packed: PackedEnsemble, n_rows: int, n_cols: int,
                num_tree_per_iteration: int, x_dtype):
        """The installed executable exactly matching this dispatch, or
        None (caller falls through to the jit path)."""
        if not self._aot:
            return None
        key = aot_call_key(packed, n_rows, n_cols,
                           num_tree_per_iteration, x_dtype)
        with self._lock:
            fn = self._aot.get(key)
        if fn is not None:
            global_timer.add_count("predict_aot_hits", 1)
        return fn

    def aot_rows(self) -> List[int]:
        """Row counts (bucket sizes) with an installed executable."""
        with self._lock:
            return sorted({key[1][0] for key in self._aot})

    def get(self, trees: Sequence[Tree], start: int, end: int,
            dtype=jnp.float32) -> PackedEnsemble:
        with self._lock:
            key = (self._version, start, end, np.dtype(dtype).name)
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                global_timer.add_count("predict_pack_hits", 1)
                return hit
            packed = pack_ensemble(trees[start:end], dtype=dtype)
            self._entries[key] = packed
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
            return packed


# --------------------------------------------------------------- streaming

_CHUNK_ENV = "LGBM_TPU_PREDICT_CHUNK"
_AUTO_CHUNK_ROWS = 1 << 18       # 256k-row device chunks
_AUTO_STREAM_MIN_ROWS = 1 << 19  # stream once the batch is >= two chunks


def stream_chunk_rows(n_rows: int, requested: Optional[int] = None) -> int:
    """Row-chunk size for streamed predict; 0 means run single-shot.

    `requested` (the pred_chunk_rows param) wins; then the
    LGBM_TPU_PREDICT_CHUNK env var; then auto (256k chunks once the batch
    is at least two of them). Chunks round up to a power of two
    (ops/partition.bucket_size) so the jit cache holds one traversal per
    bucket, not one per batch size."""
    from .partition import bucket_size

    chunk = requested
    if chunk is None:
        env = os.environ.get(_CHUNK_ENV, "")
        if env:
            try:
                chunk = int(env)
            except ValueError:
                chunk = None
        if chunk is None:
            chunk = _AUTO_CHUNK_ROWS if n_rows >= _AUTO_STREAM_MIN_ROWS else 0
    if chunk <= 0 or n_rows <= chunk:
        return 0
    return bucket_size(chunk, 256)


def predict_raw_streamed(packed: PackedEnsemble, X: np.ndarray,
                         num_tree_per_iteration: int, chunk: int,
                         dtype) -> np.ndarray:
    """Chunked double-buffered raw predict for large N, on host arrays.

    Each chunk uploads, traverses, and starts its device->host copy
    (copy_to_host_async) before the next chunk is touched, so H2D,
    compute, and D2H overlap; the host blocks only when more than two
    results are in flight. The tail chunk pads to its own power-of-two
    bucket (bounded jit cache). Returns a host [N, C] array."""
    from .partition import bucket_size

    validate_tree_count(packed, num_tree_per_iteration)
    n = X.shape[0]
    n_chunks = -(-n // chunk)
    out_parts: List[Optional[np.ndarray]] = [None] * n_chunks
    inflight: deque = deque()

    def fetch(keep: int) -> None:
        with global_timer.scope(SPAN_PREDICT_FETCH):
            while len(inflight) > keep:
                j, r, y = inflight.popleft()
                out_parts[j] = np.asarray(y)[:r]

    with global_timer.scope("predict_stream"):
        for i in range(n_chunks):
            with global_timer.scope(SPAN_PREDICT_CHUNK):
                start = i * chunk
                stop = min(start + chunk, n)
                rows = stop - start
                xc = X[start:stop]
                pad = chunk if rows == chunk else bucket_size(rows, 256)
                if rows < pad:  # tail chunk: pad to its own bucket
                    xc = np.concatenate([xc, np.zeros(
                        (pad - rows, X.shape[1]), dtype=X.dtype)])
                with global_timer.scope(SPAN_PREDICT_UPLOAD):
                    xd = jnp.asarray(xc, dtype=dtype)
                yd = predict_raw(packed, xd, num_tree_per_iteration)
                yd.copy_to_host_async()
                if telemetry.enabled():
                    telemetry.emit("predict_chunk", index=i, rows=rows,
                                   pad=pad)
                inflight.append((i, rows, yd))
                fetch(keep=2)
        fetch(keep=0)
        global_timer.add_count("predict_stream_chunks", n_chunks)
    return np.concatenate(out_parts, axis=0)


# -------------------------------------------------------------- early stop


@partial(jax.jit, static_argnames=("bucket",))
def _compact_active(active: jax.Array, bucket: int) -> jax.Array:
    """Indices of active rows first (stable argsort over the 2-way key —
    the ops/partition compaction idiom), truncated to `bucket`."""
    key = jnp.where(active, 0, 1).astype(jnp.int32)
    return jnp.argsort(key).astype(jnp.int32)[:bucket]


@partial(jax.jit, static_argnames=("num_tree_per_iteration",))
def _early_stop_block(packed_sl: PackedEnsemble, X: jax.Array,
                      scores: jax.Array, active: jax.Array, idx: jax.Array,
                      cnt: jax.Array, margin: jax.Array,
                      num_tree_per_iteration: int):
    """One tree block of device-resident early stopping: gather the
    still-active rows, add the block's raw scores, and deactivate rows
    whose margin clears the threshold — all without leaving the device."""
    C = num_tree_per_iteration
    P = idx.shape[0]
    valid = jnp.arange(P, dtype=jnp.int32) < cnt  # rows past cnt are padding
    Xa = X[idx]
    leaf = _traverse_leaves(packed_sl, Xa)
    delta = _leaf_scores(packed_sl, Xa, leaf).reshape(P, -1, C).sum(axis=1)
    scores = scores.at[idx].add(
        jnp.where(valid[:, None], delta, jnp.zeros((), delta.dtype)))
    sc = scores[idx]
    if C == 1:
        # binary margin is 2*|pred| (prediction_early_stop.cpp:65)
        margin_val = 2.0 * jnp.abs(sc[:, 0])
    else:
        top2 = jax.lax.top_k(sc, 2)[0]
        margin_val = top2[:, 0] - top2[:, 1]
    stop = (margin_val > margin) & valid
    active = active.at[idx].set(active[idx] & ~stop)
    return scores, active


def predict_raw_early_stop(packed: PackedEnsemble, X: jax.Array,
                           num_tree_per_iteration: int, round_period: int,
                           margin_threshold: float) -> np.ndarray:
    """Raw scores with prediction early stopping
    (src/boosting/prediction_early_stop.cpp): every `round_period`
    iterations, rows whose margin — |score| for binary, top-2 class gap for
    multiclass — exceeds `margin_threshold` stop traversing further trees.

    Device-resident formulation: the score matrix and the active-row mask
    live on device; per block the still-active rows are compacted by a
    stable argsort (power-of-two padded so jit caches stay bounded) and
    only they evaluate the next tree block. The ONLY host sync per block
    is the active-count scalar that picks the bucket size — the previous
    implementation pulled the whole per-block delta matrix through
    np.asarray and recomputed the compaction with np.nonzero on host.
    """
    from .partition import bucket_size

    C = num_tree_per_iteration
    T = packed.num_trees
    validate_tree_count(packed, C)
    N = X.shape[0]
    # graftlint: disable=implicit-dtype -- X keeps its caller dtype (f32 or f64)
    X_dev = jnp.asarray(X)
    scores = jnp.zeros((N, C), dtype=packed.leaf_value.dtype)
    active = jnp.ones(N, dtype=jnp.bool_)
    block = max(round_period, 1) * C
    with global_timer.scope("predict_early_stop"):
        for start in range(0, T, block):
            # the one intended sync per block: a scalar count picks the
            # power-of-two bucket, keeping compiled shapes bounded
            cnt_dev = jnp.sum(active, dtype=jnp.int32)
            cnt = int(cnt_dev)
            if cnt == 0:
                break
            bucket = min(bucket_size(cnt, 256), N)
            idx = _compact_active(active, bucket)
            sl = packed.tree_slice(start, min(start + block, T))
            scores, active = _early_stop_block(
                sl, X_dev, scores, active, idx, cnt_dev, margin_threshold, C)
    return np.asarray(scores, dtype=np.float64)
