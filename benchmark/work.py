"""The work the algorithm needs, whatever implements it, and the least time
the chip could take for it.

Counted from the model and the shapes alone, never from what the program
moved: a later PR that halves the program's traffic does not halve these.
A share of a peak is least time over measured time, and cannot pass 100 %
while the counts are right.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Work:
    bytes: float
    ops: float
    operand: str  # key of the peaks table's ops_per_s


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json: add it with its source")
    return table[device_kind]


def least_seconds(work: Work, device_kind: str) -> tuple:
    """(least seconds, which bound applied: "bytes" or "ops")."""
    peaks = peaks_for(device_kind)
    by_bytes = work.bytes / peaks["hbm_bytes_per_s"]
    by_ops = work.ops / peaks["ops_per_s"][work.operand]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")


def tree_hist_rows(tree) -> int:
    """Rows that must be histogrammed to grow this tree under histogram
    subtraction: the root's rows, and at every split the smaller child's
    (its sibling's histogram is the parent's minus it)."""
    if tree.num_leaves <= 1:
        return 0
    smaller = np.minimum(tree.child_count(tree.left_child),
                         tree.child_count(tree.right_child))
    return int(tree.internal_count[0] + smaller.sum())


def tree_routed_rows(tree) -> int:
    """Rows that must be routed: one pass over each split parent's rows."""
    return int(tree.internal_count.sum()) if tree.num_leaves > 1 else 0


def hist_work(hist_rows: int, n_features: int, bin_bytes: int,
              gh_bytes: int, operand: str) -> Work:
    """Histogramming `hist_rows` rows: each costs its bin bytes and its
    gradient pair, and one accumulation of each per feature. With the
    program's own count of rows this is the histogram kernel's work (the
    speculation it chose to do is its cost)."""
    return Work(bytes=float(hist_rows * (n_features * bin_bytes
                                         + 2 * gh_bytes)),
                ops=float(hist_rows * n_features * 2), operand=operand)


def train_tree_work(tree, n_features: int, bin_bytes: int, gh_bytes: int,
                    operand: str) -> Work:
    """One tree: the rows it must histogram, and for every routed row the
    split feature's bin and its new leaf id (4 bytes)."""
    hist = hist_work(tree_hist_rows(tree), n_features, bin_bytes, gh_bytes,
                     operand)
    routed = tree_routed_rows(tree)
    return Work(bytes=hist.bytes + routed * (bin_bytes + 4),
                ops=hist.ops + routed, operand=operand)


NODE_RECORD_BYTES = 16  # feature id, threshold, two children: 4 bytes each
FEATURE_VALUE_BYTES = 4


def forest_visits_per_row(trees) -> float:
    """Expected node visits to score one row: over the trees, the mean
    depth of the leaf reached, weighted by the model's own leaf counts."""
    visits = 0.0
    for tree in trees:
        if tree.num_leaves <= 1:
            continue
        _, leaf_depth = tree.depths()
        visits += float((tree.leaf_count * leaf_depth).sum()
                        / tree.leaf_count.sum())
    return visits


def predict_work(trees, rows: int, n_features: int) -> Work:
    """`rows` rows through the forest: each node visit reads one node
    record and one feature value and makes one compare; each tree adds one
    leaf value (4 bytes, one add); each row is read once and answered once."""
    visits = forest_visits_per_row(trees) * rows
    per_tree = float(len(trees)) * rows
    return Work(
        bytes=visits * (NODE_RECORD_BYTES + FEATURE_VALUE_BYTES)
        + per_tree * 4 + rows * (n_features * 4 + 4),
        ops=visits + per_tree, operand="bf16")
