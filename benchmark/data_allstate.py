"""The seeded stand-in for the Allstate Claim Prediction Challenge table as
LightGBM's Comparison Experiment uses it (`configs/allstate_efb.json`): the
categorical columns one-hot encoded beside the numerical ones, handed over
as a scipy CSR matrix, label `claim` (a claim was paid).

Columns, in this order (the configuration's `numerical` and `categorical`):

  the numerical columns (Var1-Var8, NVVar1-NVVar4)   dense: every row holds
                                                     a value; Var a normal,
                                                     NVVar a lognormal less
                                                     its median, rounded to
                                                     1e-4
  one one-hot column a level of each categorical     exactly one set a row
  column, in the configuration's order               and column

Each categorical column's levels are Zipf (k + 1)^-s with the column's
exponent, so the low-cardinality columns have levels on more than 20 % of
the rows (kept in a group of their own) and the vehicle columns thousands
of levels rare enough to bundle. A row holds its numerical values and one
1.0 a categorical column: `len(numerical) + len(categorical)` stored
entries, column indices ascending (canonical CSR).

The label is Bernoulli(sigmoid(logit)): an effect for every level of every
categorical column (normal, the column's sd: a level of any frequency
carries signal), a linear effect of each numerical column, and an
intercept for a claim rate of `positive_share`. The test set is drawn from
the same law.

Everything comes from the configuration's `data_seed` but the columns'
order: `--seed` permutes the one-hot columns within each categorical
column (the same levels, rows and label; another column order, so other
bundles and other inputs of the same size, and the same trees: a seed that
drew other rows would grow other trees, whose waves and time a tree differ
by a few per cent). A negative seed is read modulo 2**64.

The draws are taken from one generator in one order; the levels' lookups
and the matrix's fill run on `WORKERS` threads, and the effects are added
in the columns' order, so the table is the same bit for bit on any number
of threads.
"""
from __future__ import annotations

import os
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

WORKERS = min(8, os.cpu_count() or 1)  # threads of the lookups and the fill


def _zipf(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def _law(cfg: dict, rng) -> dict:
    """The table's fixed parts: level weights, effects, numerical slopes."""
    shape = cfg["shape"]
    cats = cfg["categorical"]
    return {
        "levels": [_zipf(int(c[1]), float(c[2])) for c in cats],
        "eff": [rng.normal(0.0, float(c[3]), int(c[1])) for c in cats],
        "slope": rng.normal(0.0, float(shape["numerical_sd"]),
                            len(cfg["numerical"])),
    }


def _rows(law: dict, cfg: dict, n: int, rng, pool) -> tuple:
    """([n, numerical] float32 values, one int32 [n] array of levels a
    categorical column, the logit without the intercept). The draws are
    taken from `rng` in one order; the levels are looked up in `pool`."""
    n_num = len(cfg["numerical"])
    num = np.empty((n, n_num), np.float32)
    logit = np.zeros(n)
    for j in range(n_num):
        if cfg["numerical"][j].startswith("NV"):
            v = np.round(rng.lognormal(0.0, 0.8, n) - 1.0, 4)
        else:
            v = rng.normal(0.0, 1.0, n)
        v[v == 0] = 1e-4  # a stored value, never an explicit zero
        num[:, j] = v
        logit += law["slope"][j] * v

    def levels(cum, u, eff):
        lev = np.searchsorted(cum, u).astype(np.int32)
        return lev, eff[lev]

    def take_oldest() -> None:  # its effect added in the columns' order
        column, effect = pending.popleft().result()
        lev.append(column)
        logit[:] += effect
    lev, pending = [], deque()
    for p, eff in zip(law["levels"], law["eff"]):
        pending.append(pool.submit(levels, np.cumsum(p),
                                   rng.random(n) * (1 - 1e-12), eff))
        if len(pending) > WORKERS:  # the draws held at once
            take_oldest()
    while pending:
        take_oldest()
    return num, lev, logit


def offsets(cfg: dict) -> np.ndarray:
    """The first one-hot column of each categorical column, and the width."""
    cards = [int(c[1]) for c in cfg["categorical"]]
    return len(cfg["numerical"]) + np.concatenate([[0], np.cumsum(cards)])


def _csr(num, lev, perm, offs, n_cols: int, pool):
    import scipy.sparse as sp

    n, n_num = num.shape
    width = n_num + len(lev)
    indices = np.empty((n, width), np.int32)
    data = np.empty((n, width), np.float32)

    def fill(a: int, b: int) -> None:  # rows a..b, every column
        indices[a:b, :n_num] = np.arange(n_num, dtype=np.int32)
        data[a:b, :n_num] = num[a:b]
        for c, column in enumerate(lev):
            indices[a:b, n_num + c] = offs[c] + perm[c][column[a:b]]
        data[a:b, n_num:] = 1.0
    step = -(-n // 64)
    list(pool.map(lambda a: fill(a, min(a + step, n)), range(0, n, step)))
    indptr = np.arange(0, n * width + 1, width, dtype=np.int64)
    return sp.csr_matrix((data.ravel(), indices.ravel(), indptr),
                         shape=(n, n_cols))


def make_allstate_data(cfg: dict, seed: int) -> dict:
    """{"train": (X, y), "test": (X, y)}: X a float32 CSR matrix of the
    configuration's `features` columns, y float64 in {0, 1}."""
    rng = np.random.default_rng(int(cfg["data_seed"]))
    law = _law(cfg, rng)
    offs = offsets(cfg)
    n_cols = int(cfg["features"])
    assert offs[-1] == n_cols, (offs[-1], n_cols)
    # --seed: the order of each categorical column's one-hot columns
    prng = np.random.default_rng([int(seed) % 2 ** 64, 40])
    perm = [prng.permutation(int(c[1])).astype(np.int32)
            for c in cfg["categorical"]]
    out, intercept = {}, None
    pool = ThreadPoolExecutor(WORKERS)
    for name, n in (("train", int(cfg["rows"])),
                    ("test", int(cfg["test_rows"]))):
        num, lev, logit = _rows(law, cfg, n, rng, pool)
        if intercept is None:
            lo, hi = -15.0, 5.0
            target = float(cfg["shape"]["positive_share"])
            probe = logit[:200000]
            for _ in range(60):  # the intercept, by bisection
                mid = 0.5 * (lo + hi)
                share = np.mean(1.0 / (1.0 + np.exp(-(probe + mid))))
                lo, hi = (mid, hi) if share < target else (lo, mid)
            intercept = 0.5 * (lo + hi)
        p = 1.0 / (1.0 + np.exp(-(logit + intercept)))
        y = (rng.random(n) < p).astype(np.float64)
        out[name] = (_csr(num, lev, perm, offs, n_cols, pool), y)
        del num, lev, logit, p
    pool.shutdown()
    return out
