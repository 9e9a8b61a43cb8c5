"""Traffic kind `predict_batch`: repeated `Booster.predict` of a host array
through a seeded forest, until the window's seconds have run out.

The traffic file's parameters:
  rows_per_call  rows of every call (a host float32 array)
  pool_extra     the calls are windows into one seeded pool of
                 rows_per_call + pool_extra rows, each at an offset drawn
                 from the seed, so no two calls score the same rows
  warmup_calls   calls made before the window opens
  sample_rows    rows of every call whose answers are kept for the check
  check_calls    calls checked against the reference once the window has
                 closed: the first, the last, and others drawn from the seed
  trace_seconds  the window's length in a traced run
  limits         the limit of each number that decides `correct`
"""
from __future__ import annotations

import gc
import time

import numpy as np

import data
import modeltext
import work
from harness import Refused
from reference import forest as reference

MAX_CALLS = 1 << 20


def run(ctx) -> None:
    import lightgbm_tpu as lgb

    cfg, traffic = ctx.config, ctx.traffic
    n_feat = int(cfg["features"])
    rows = int(traffic["rows_per_call"])
    extra = int(traffic["pool_extra"])
    t0 = time.perf_counter()
    with ctx.span("forest_build"):
        model_text = data.make_forest(
            ctx.seed, int(cfg["num_trees"]), int(cfg["num_leaves"]), n_feat,
            float(cfg["leaf_scale"]), int(cfg["max_depth"]))
        bst = lgb.Booster(model_str=model_text)
    ctx.counts["forest_build_s"] = time.perf_counter() - t0
    pool = data.make_rows(rows + extra, n_feat, ctx.seed)
    rng = np.random.default_rng([ctx.seed, 3])
    offsets = rng.integers(0, extra + 1, size=4096)
    # the rows kept of every call: drawn from the seed, with the first and
    # last row and both sides of every 2^18-row chunk edge among them
    edges = [r for k in range(0, rows + 1, 1 << 18) for r in (k - 1, k)
             if 0 <= r < rows]
    keep = np.unique(np.concatenate([
        rng.choice(rows, size=min(int(traffic["sample_rows"]), rows),
                   replace=False), np.array(edges, dtype=np.int64)]))

    def call(i: int) -> np.ndarray:
        off = int(offsets[i % offsets.shape[0]])
        with ctx.span(f"predict_call_{i}"):
            return bst.predict(pool[off:off + rows])

    for i in range(int(traffic["warmup_calls"])):
        call(i)
    kept, n_calls = [], 0
    opened = ctx.open_window()
    while n_calls < MAX_CALLS:
        out = call(n_calls)  # host values in hand: the call's work is done
        now = time.perf_counter()
        if out.shape != (rows,):
            raise Refused(5, f"predict returned shape {out.shape}")
        kept.append(np.asarray(out[keep], dtype=np.float64))
        n_calls += 1
        if now - opened >= ctx.window_limit():
            break
    window_s = now - opened
    ctx.close_window()
    trees = modeltext.parse_model(model_text)
    ctx.least_s = work.least_seconds(
        work.predict_work(trees, rows * n_calls, n_feat),
        ctx.device["kind"]) if not ctx.rehearsal else None
    ctx.counts.update(window_calls=n_calls, window_rows=rows * n_calls,
                      window_s=window_s)
    ctx.e2e["predict_rows_per_s"] = rows * n_calls / window_s
    ctx.attempted, ctx.failed = n_calls, 0

    del bst
    gc.collect()
    n_check = min(int(traffic["check_calls"]), n_calls)
    picks = {0, n_calls - 1}
    while len(picks) < n_check:
        picks.add(int(rng.integers(n_calls)))
    picks = sorted(picks)
    X = np.concatenate([pool[int(offsets[i % offsets.shape[0]]) + keep]
                        for i in picks])
    want = reference.predict_proba(trees, X)
    got = np.concatenate([kept[i] for i in picks])
    readings = {"prob_gap": float(np.max(np.abs(got - want))),
                "answers_not_finite": float(np.sum(~np.isfinite(got)))}
    for name, limit in traffic["limits"].items():
        ctx.compare(name, readings[name], limit)
