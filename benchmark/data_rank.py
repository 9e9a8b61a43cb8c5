"""Seeded learning-to-rank tables at MSLR-WEB30K's published shape: query
sizes, 136 float32 feature columns, grades 0-4. Data only: the data set
itself is not in the container and is never fetched.

What is assumed (configs/mslr_lambdarank.json lists the same under
`assumed`), since nothing here was read from the data set:

  * query sizes: lognormal (sigma 0.75) around the published mean of 120
    documents, clipped to the published 1..1,251, then moved by single
    documents until the published row count is met exactly; every set
    holds at least one query of 1 document and one of 1,251;
  * columns, by `column_kinds`: continuous scores (BM25, language-model and
    TF-IDF values: standard normal, or lognormal for the heavy-tailed
    ones), small non-negative integer counts (covered query terms, URL
    slashes, stream lengths: the floor of an exponential
    with a column's own mean of 1..40, clipped to 0..200 so that a column
    has under 255 distinct values and bins one value a bin), and columns
    that are zero for most rows (anchor-text and click features: 80 % or
    95 % zeros, a small count elsewhere: the binner orders such sparse
    columns last in the plane, the sparsest in its last group block). Bin
    counts so differ by column, as the data set's do;
  * grades: a seeded utility (a sparse linear form of the standardised
    columns, a per-query offset, unit noise) cut at the quantiles of the
    whole set that give 51 / 33 / 13 / 2 / 1 % of grades 0..4: the per-query
    offset leaves some queries, the small ones first, with one grade only.

All of it comes from the configuration's `data_seed`, the same in every run;
`seed` draws the order of the columns after the first, as `data.make_data`
does: other inputs, the same work.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SIZE_LO, SIZE_HI = 1, 1251
SIZE_SIGMA = 0.75
GRADE_SHARES = (0.51, 0.33, 0.13, 0.02, 0.01)
KINDS = ("normal", "lognormal", "count", "sparse80", "sparse95")
# of every 8 columns: 3 normal, 1 lognormal, 2 counts, 1 + 1 sparse
KIND_CYCLE = (0, 2, 0, 3, 1, 2, 0, 4)
COUNT_MAX = 200      # a count column has under 255 distinct values
QUERY_SD = 1.2       # the per-query offset of the utility
LINEAR_SD = 1.2      # the linear form's
BLOCK_ROWS = 8192    # rows made from one stream


def query_sizes(n_rows: int, n_queries: int, rng) -> np.ndarray:
    """`n_queries` sizes in SIZE_LO..SIZE_HI that sum to `n_rows`, skewed,
    with one query of each extreme."""
    if not (n_queries * SIZE_LO <= n_rows <= n_queries * SIZE_HI
            and n_queries >= 2):
        raise ValueError(f"{n_rows} rows do not fit {n_queries} queries of "
                         f"{SIZE_LO}..{SIZE_HI} documents")
    mean = n_rows / n_queries
    mu = np.log(mean) - 0.5 * SIZE_SIGMA ** 2
    sizes = np.clip(np.rint(rng.lognormal(mu, SIZE_SIGMA, n_queries)),
                    SIZE_LO, SIZE_HI).astype(np.int64)
    sizes[0], sizes[1] = SIZE_LO, SIZE_HI  # kept while the rest is moved
    diff = int(n_rows - sizes.sum())
    while diff:
        step = 1 if diff > 0 else -1
        free = 2 + np.nonzero((sizes[2:] + step >= SIZE_LO)
                              & (sizes[2:] + step <= SIZE_HI))[0]
        take = rng.choice(free, size=min(abs(diff), free.size),
                          replace=False)
        sizes[take] += step
        diff -= step * take.size
    return sizes[rng.permutation(n_queries)]


def column_kinds(n_features: int) -> list:
    return [KINDS[KIND_CYCLE[j % len(KIND_CYCLE)]] for j in range(n_features)]


def column_params(n_features: int, data_seed: int) -> list:
    """[(kind, parameter)] a column, the same for the three sets: a count
    column's mean, a continuous column's nothing."""
    rng = np.random.default_rng([data_seed, 1])
    return [(kind, float(rng.uniform(1.0, 40.0)) if kind == "count" else 0.0)
            for kind in column_kinds(n_features)]


def _column(kind: str, param: float, n: int, rng) -> np.ndarray:
    if kind == "normal":
        return rng.standard_normal(n, dtype=np.float32)
    if kind == "lognormal":
        return np.exp(rng.standard_normal(n, dtype=np.float32))
    if kind == "count":  # geometric-like: many small counts, a long tail
        return np.minimum(np.floor(
            np.float32(param) * rng.standard_exponential(n, dtype=np.float32)),
            np.float32(COUNT_MAX))
    # zero for most rows, a small count elsewhere (a term count in a short
    # stream): such a column is sparse to the binner and has few values
    zero_share = 0.8 if kind == "sparse80" else 0.95
    value = np.minimum(1.0 + np.floor(np.float32(2.0) * rng.standard_exponential(
        n, dtype=np.float32)), np.float32(COUNT_MAX))
    return np.where(rng.random(n, dtype=np.float32) < zero_share,
                    np.float32(0.0), value)


def make_set(n_rows: int, n_queries: int, columns: list, data_seed: int,
             which: int, weights: np.ndarray, order: np.ndarray) -> tuple:
    """One set (`which`: 0 train, 1 vali, 2 test): X [n, F] float32 with
    its columns in `order`, the utility [n] and the sizes [Q]. `weights`
    [F] is the utility's linear form, the same for the three sets. Made in
    blocks of BLOCK_ROWS rows, each from a stream of its own (so the
    threads that make them change nothing)."""
    rng = np.random.default_rng([data_seed, 10 + which])
    sizes = query_sizes(n_rows, n_queries, rng)
    offset = rng.standard_normal(n_queries).astype(np.float32) * QUERY_SD
    utility = np.repeat(offset, sizes)
    X = np.empty((n_rows, len(columns)), dtype=np.float32)
    # standardised by the kind's own scale, not by the sample's
    scale = {"normal": 1.0, "lognormal": 2.0, "count": 1.0}

    def block(b: int) -> None:
        lo, hi = b * BLOCK_ROWS, min((b + 1) * BLOCK_ROWS, n_rows)
        brng = np.random.default_rng([data_seed, 10 + which, b])
        tmp = np.empty((len(columns), hi - lo), dtype=np.float32)
        part = brng.standard_normal(hi - lo, dtype=np.float32)
        for j, (kind, param) in enumerate(columns):
            tmp[j] = _column(kind, param, hi - lo, brng)
            if weights[j] != 0.0:
                by = scale.get(kind, 1.0) * (param if kind == "count" else 1)
                part += np.float32(weights[j] / by) * tmp[j]
        utility[lo:hi] += part
        X[lo:hi] = tmp[order].T

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(block, range(-(-n_rows // BLOCK_ROWS))))
    return X, utility, sizes


def grade_cuts(utility: np.ndarray) -> np.ndarray:
    """The four utility values between grades 0..4 at GRADE_SHARES."""
    return np.quantile(utility, np.cumsum(GRADE_SHARES)[:-1])


def make_rank_data(cfg: dict, seed: int) -> dict:
    """{"train" | "vali" | "test": (X [n, F] float32 C-ordered, grades [n]
    float64 in 0..4, sizes [Q] int64)} at the configuration's shape. The
    grades of all three sets are cut at the training set's quantiles."""
    n_features = int(cfg["features"])
    data_seed = int(cfg["data_seed"])
    rng = np.random.default_rng([data_seed, 0])
    weights = rng.standard_normal(n_features)
    weights[rng.random(n_features) < 0.6] = 0.0   # a sparse linear form
    weights /= np.sqrt(max(float((weights ** 2).sum()), 1e-12))
    weights *= LINEAR_SD
    tail = 1 + np.random.default_rng([seed, 0]).permutation(n_features - 1)
    order = np.concatenate([[0], tail])
    shapes = {"train": (int(cfg["rows"]), int(cfg["queries"]))}
    for name, v in cfg.get("valid", {}).items():
        shapes[name] = (int(v["rows"]), int(v["queries"]))
    columns = column_params(n_features, data_seed)
    out, cuts = {}, None
    for which, (name, (rows, queries)) in enumerate(shapes.items()):
        X, utility, sizes = make_set(rows, queries, columns, data_seed,
                                     which, weights, order)
        if cuts is None:
            cuts = grade_cuts(utility)
        grades = np.searchsorted(cuts, utility).astype(np.float64)
        out[name] = (X, grades, sizes)
    return out
