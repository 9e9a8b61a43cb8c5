"""A LightGBM text model read into plain numpy arrays.

The benchmark's own reader of what the program produces (`model_to_string`)
and of what `data.make_forest` writes: the plain references and the
work-count functions walk these arrays and import nothing of the program.
Numerical splits only (the benchmark's configurations have no categorical
feature); a categorical node is an error, not a guess.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class PlainTree:
    """One tree. Internal node i was made by the tree's i-th split; a child
    pointer c >= 0 is an internal node, c < 0 is leaf ~c."""

    split_feature: np.ndarray   # [I] int64
    threshold: np.ndarray       # [I] float64: row goes left when x <= t
    left_child: np.ndarray      # [I] int64
    right_child: np.ndarray     # [I] int64
    split_gain: np.ndarray      # [I] float64
    internal_count: np.ndarray  # [I] int64
    leaf_value: np.ndarray      # [L] float64
    leaf_count: np.ndarray      # [L] int64

    @property
    def num_leaves(self) -> int:
        return int(self.leaf_value.shape[0])

    def parents(self) -> tuple:
        """(parent internal node of each internal node, -1 for the root;
        parent internal node of each leaf)."""
        n_int = self.split_feature.shape[0]
        node_parent = np.full(n_int, -1, dtype=np.int64)
        leaf_parent = np.full(self.num_leaves, -1, dtype=np.int64)
        for child in (self.left_child, self.right_child):
            inner = child >= 0
            node_parent[child[inner]] = np.nonzero(inner)[0]
            leaf_parent[~child[~inner]] = np.nonzero(~inner)[0]
        return node_parent, leaf_parent

    def depths(self) -> tuple:
        """(depth of each internal node, root = 0; depth of each leaf = the
        number of nodes visited on the way to it)."""
        node_parent, leaf_parent = self.parents()
        node_depth = np.zeros(self.split_feature.shape[0], dtype=np.int64)
        for i in range(1, node_depth.shape[0]):  # a parent precedes its child
            node_depth[i] = node_depth[node_parent[i]] + 1
        if self.num_leaves <= 1:
            return node_depth, np.zeros(self.num_leaves, dtype=np.int64)
        return node_depth, node_depth[leaf_parent] + 1

    def child_count(self, child: np.ndarray) -> np.ndarray:
        """Rows under each of the given child pointers."""
        out = np.empty(child.shape[0], dtype=np.int64)
        inner = child >= 0
        out[inner] = self.internal_count[child[inner]]
        out[~inner] = self.leaf_count[~child[~inner]]
        return out


def _section(block: str) -> dict:
    kv = {}
    for line in block.splitlines():
        key, sep, val = line.partition("=")
        if sep:
            kv[key.strip()] = val.strip()
    return kv


def _ints(kv: dict, key: str, n: int) -> np.ndarray:
    vals = kv.get(key, "").split()
    if len(vals) < n:
        raise ValueError(f"model text: {key} has {len(vals)} values, "
                         f"{n} expected")
    return np.array([int(v) for v in vals[:n]], dtype=np.int64)


def _floats(kv: dict, key: str, n: int) -> np.ndarray:
    vals = kv.get(key, "").split()
    if len(vals) < n:
        raise ValueError(f"model text: {key} has {len(vals)} values, "
                         f"{n} expected")
    return np.array([float(v) for v in vals[:n]], dtype=np.float64)


def parse_model(text: str) -> list:
    """Every tree of a text model, in boosting order."""
    body = text.split("end of trees")[0]
    blocks = body.split("\nTree=")[1:]
    trees = []
    for block in blocks:
        kv = _section(block)
        n_leaves = int(kv["num_leaves"])
        if int(kv.get("num_cat", "0")) != 0:
            raise ValueError("model text: categorical splits are not read "
                             "by the benchmark's plain reference")
        if int(kv.get("is_linear", "0")) != 0:
            raise ValueError("model text: linear leaves are not read by the "
                             "benchmark's plain reference")
        n_int = max(n_leaves - 1, 0)
        decision = _ints(kv, "decision_type", n_int)
        if np.any(decision & 1):
            raise ValueError("model text: categorical decision_type")
        trees.append(PlainTree(
            split_feature=_ints(kv, "split_feature", n_int),
            threshold=_floats(kv, "threshold", n_int),
            left_child=_ints(kv, "left_child", n_int),
            right_child=_ints(kv, "right_child", n_int),
            split_gain=_floats(kv, "split_gain", n_int),
            internal_count=_ints(kv, "internal_count", n_int),
            leaf_value=_floats(kv, "leaf_value", n_leaves),
            leaf_count=_ints(kv, "leaf_count", n_leaves)))
    return trees


def route(tree: PlainTree, X: np.ndarray) -> np.ndarray:
    """Leaf index of every row of X [n, F]: the plain traversal, one level
    of the tree at a time over the rows still inside it. X holds no NaN
    (the benchmark's data has none), so missing-value routing never
    applies."""
    n = X.shape[0]
    if tree.num_leaves <= 1:
        return np.zeros(n, dtype=np.int64)
    node = np.zeros(n, dtype=np.int64)
    rows = np.arange(n)
    while rows.size:
        at = node[rows]
        go_left = X[rows, tree.split_feature[at]] <= tree.threshold[at]
        nxt = np.where(go_left, tree.left_child[at], tree.right_child[at])
        node[rows] = nxt
        rows = rows[nxt >= 0]
    return ~node
