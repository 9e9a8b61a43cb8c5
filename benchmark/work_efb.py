"""The work a bundled tree needs beside what `work.py` counts, whatever
implements it: the split scan over the group histograms of a bundled
plane, and an iteration's evaluation of the test set on its binned plane.
`work.py`'s rule holds: counted from the layout, the model and the shapes
alone (never from what the program moved).
"""
from __future__ import annotations

import work

# A scan reads every bin of the leaf's [G, B, 3] float32 group histogram
# once and writes one record of 14 float32 fields a feature
# (ops/split.py SPLIT_FIELDS).
HIST_CHANNELS = 3
RECORD_FIELDS = 14
FLOAT_BYTES = 4
# One test row's evaluation beside its node visits: its score read and
# written (8 bytes) and its score and label read by the AUC's one sort (8).
ROW_BYTES = 16


def bundle_scan_work(n_groups: int, group_bins: int, n_features: int,
                     wave_k: int, waves: int, trees: int) -> work.Work:
    """The split scans of `trees` trees grown in `waves` waves of `wave_k`
    candidate splits: the root's scan a tree and the 2K children's a wave,
    each reading the [G, B, 3] group histogram once and writing a record
    a feature; an operation a histogram value (the prefix sum's add)."""
    scans = trees + 2 * wave_k * waves
    hist = n_groups * group_bins * HIST_CHANNELS
    return work.Work(
        bytes=float(scans * (hist + n_features * RECORD_FIELDS)
                    * FLOAT_BYTES),
        ops=float(scans * hist), operand="bf16")


def eval_work(tree, test_rows: int, n_groups: int,
              bin_bytes: int) -> work.Work:
    """One iteration's evaluation on the test set's plane: every node a row
    visits reads its node record and the row's bin of the node's group and
    makes one compare; each row's score is updated and read by the AUC."""
    visits = work.forest_visits_per_row([tree]) * test_rows
    return work.Work(
        bytes=visits * (work.NODE_RECORD_BYTES + bin_bytes)
        + test_rows * ROW_BYTES,
        ops=visits + test_rows, operand="bf16")
