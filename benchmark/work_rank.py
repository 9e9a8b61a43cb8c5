"""The work a lambdarank iteration needs beside growing its tree, whatever
implements it: the gradient pass, from the query sizes alone, and the
evaluation of the validation sets. `work.py`'s rule holds: counted from the
shapes and the model, never from what the program moved or evaluated.
"""
from __future__ import annotations

import numpy as np

import work

# One pair position (i < min(T, n), j > i) of LambdarankNDCG::
# GetGradientsForOneQuery, counted on its arithmetic: the score difference
# (1), the gain gap and the discount gap with their absolute values (4),
# their product over maxDCG (2), the norm's 0.01 + |delta| and division (3),
# the sigmoid's product, exp, add and divide (4), lambda (2), the hessian's
# rho * (1 - rho) and its products (4), the four accumulations into the two
# documents and the norm's sum (5). Which positions differ in grade depends
# on the order of the scores, so every position is counted.
PAIR_OPS = 25
# One pass over each document: its score and grade read, its lambda and
# hessian written, 4 bytes each.
DOCUMENT_BYTES = 16


def pair_positions(sizes, truncation: int) -> int:
    """Per query of n documents, m = min(T, n): m * n - m * (m + 1) / 2."""
    n = np.asarray(sizes, dtype=np.int64)
    m = np.minimum(int(truncation), n)
    return int(np.sum(m * n - m * (m + 1) // 2))


def gradient_work(sizes, truncation: int) -> work.Work:
    """One lambdarank gradient pass over the queries of these sizes."""
    rows = int(np.sum(np.asarray(sizes, dtype=np.int64)))
    return work.Work(bytes=float(rows * DOCUMENT_BYTES),
                     ops=float(pair_positions(sizes, truncation) * PAIR_OPS),
                     operand="bf16")  # float32, held to the bf16 peak


def eval_work(tree, valid_rows: int, n_features: int) -> work.Work:
    """One iteration's evaluation: the new tree over the validation rows
    (`work.predict_work`: rows x node visits), and one pass of the metric
    over each row's score and grade (8 bytes; the per-query sort is not
    counted: it needs no more than the scores it reads)."""
    scored = work.predict_work([tree], valid_rows, n_features)
    return work.Work(bytes=scored.bytes + valid_rows * 8.0, ops=scored.ops,
                     operand="bf16")
