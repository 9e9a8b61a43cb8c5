"""Traffic kind `train_window`: one `lgb.train` call on seeded data, its
first trees untimed (warm-up, and what the reference follows), then trees
until the window's seconds have run out.

The traffic file's parameters:
  warmup_trees   trees grown before the window opens; the reference follows
                 exactly these, through the same call and the same booster
                 that the window then drives
  trace_seconds  the window's length in a traced run
  limits         the limit of each number that decides `correct`
"""
from __future__ import annotations

import gc
import re
import time

import numpy as np

import data
import modeltext
import work
from harness import EXIT_NOT_DEVICE_PATH, Refused
from reference import gbdt as reference

MAX_TREES = 100000


def mosaic_kernels(lowered_text: str) -> list:
    """The jitted kernel wrappers whose pallas_call a lowered program hands
    to Mosaic (each is a private function of the module, holding one
    tpu_custom_call). An interpreted kernel, or the XLA body in its place,
    leaves no tpu_custom_call behind. (chip_smoke.py's check, copied.)"""
    names, func = set(), "main"
    for line in lowered_text.splitlines():
        m = re.search(r"func\.func (?:\w+ )?@([\w.]+)\(", line)
        if m:
            func = m.group(1)
        if "@tpu_custom_call" in line:
            names.add(func)
    return sorted(names)


def lower_whole_tree(learner):
    """The whole-tree program, lowered with the learner's own arguments."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.treelearner.device import grow_tree_on_device

    n = learner.num_data
    shape = jax.ShapeDtypeStruct
    gh_dtype = jnp.int8 if learner.quantized else jnp.float32
    return grow_tree_on_device.lower(
        shape(learner.bins_dev.shape, learner.bins_dev.dtype),
        shape((n, 3), gh_dtype), shape((n,), jnp.int32), learner.meta,
        learner.tables, learner.params_dev,
        shape((len(learner.meta.real_feature),), jnp.bool_),
        learner.config.num_leaves, learner.group_bin_padded,
        learner.config.max_depth, quantized=learner.quantized,
        scale_vec=learner._scale_vec, batch=learner.wave_k, bagged=False)


def run(ctx) -> None:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.callback import EarlyStopException
    from lightgbm_tpu.treelearner.device import grow_tree_on_device
    from lightgbm_tpu.utils.timer import global_timer

    cfg, traffic = ctx.config, ctx.traffic
    warmup = int(traffic["warmup_trees"])
    X, y = data.make_data(int(cfg["rows"]), int(cfg["features"]), ctx.seed,
                          int(cfg["data_seed"]))
    t0 = time.perf_counter()
    with ctx.span("dataset_construct"):
        ds = lgb.Dataset(X, label=y).construct()
    ctx.counts["dataset_construct_s"] = time.perf_counter() - t0

    snapshots, stamps, marks = [], [], {}
    tree_span = [None]

    def counters() -> tuple:
        return (int(global_timer.counters.get("device_hist_rows", 0)),
                grow_tree_on_device._cache_size())

    def before(env) -> None:
        if ctx.window_open_at is not None:
            tree_span[0] = ctx.span(f"tree_{env.iteration}")
            tree_span[0].__enter__()
    before.before_iteration = True

    def after(env) -> None:
        g = env.model._gbdt
        jax.block_until_ready(g.score)  # the tree's work is done
        now = time.perf_counter()
        if tree_span[0] is not None:
            tree_span[0].__exit__(None, None, None)
            tree_span[0] = None
        if env.iteration < warmup:
            snapshots.append(np.asarray(g.score[0]))
            if env.iteration == warmup - 1:
                # counters lag one tree under the async pipeline (a tree's
                # split log is replayed during the next): the lag is the
                # same at both ends of the window
                marks["open"] = counters()
                ctx.open_window()
            return
        stamps.append(now)
        if now - ctx.window_open_at >= ctx.window_limit():
            marks["close"] = counters()
            ctx.close_window()
            raise EarlyStopException(env.iteration, [])

    params = dict(cfg["params"], verbosity=-1)
    bst = lgb.train(params, ds, num_boost_round=warmup + MAX_TREES,
                    callbacks=[before, after])
    if "close" not in marks:
        raise Refused(5, "training stopped by itself before the window "
                         "closed (no more splits)")
    learner = bst._gbdt.tree_learner
    n_trees = len(stamps)
    window_s = stamps[-1] - ctx.window_open_at
    hist_rows, programs = (b - a for a, b in zip(marks["open"],
                                                 marks["close"]))
    if type(learner).__name__ != "DeviceTreeLearner":
        raise Refused(EXIT_NOT_DEVICE_PATH, "trees were grown by "
                      f"{type(learner).__name__}, not DeviceTreeLearner")
    if hist_rows <= 0:
        raise Refused(EXIT_NOT_DEVICE_PATH, "device_hist_rows did not move: "
                      "the device histogram path never ran")
    kernels = mosaic_kernels(lower_whole_tree(learner).as_text())
    if not ctx.rehearsal and not (any("compact" in k for k in kernels)
                                  and any("histogram" in k for k in kernels)):
        raise Refused(EXIT_NOT_DEVICE_PATH, "the whole-tree program's Mosaic "
                      f"kernels are {kernels}: the Pallas histogram and "
                      "compaction did not both reach Mosaic")

    model_text = bst.model_to_string()
    trees = modeltext.parse_model(model_text)
    if len(trees) < warmup + n_trees:
        raise Refused(5, f"the model holds {len(trees)} trees, the run "
                         f"counted {warmup + n_trees}")
    window_trees = trees[warmup:warmup + n_trees]
    quantized = bool(learner.quantized)
    gh_bytes, operand = (1, "int8") if quantized else (4, "bf16")
    n_feat = int(cfg["features"])
    needed = [work.train_tree_work(t, n_feat, 1, gh_bytes, operand)
              for t in window_trees]
    total = work.Work(sum(w.bytes for w in needed),
                      sum(w.ops for w in needed), operand)
    ctx.least_s = work.least_seconds(total, ctx.device["kind"]) \
        if not ctx.rehearsal else None
    ctx.counts.update(
        window_trees=n_trees, window_s=window_s, hist_rows=hist_rows,
        programs_compiled=programs, features=n_feat, bin_bytes=1,
        gh_bytes=gh_bytes, operand=operand)
    ctx.e2e["train_s_per_tree"] = window_s / n_trees
    ctx.attempted, ctx.failed = n_trees, 0

    del bst, ds, learner
    gc.collect()
    readings = reference.follow(X, y, trees, cfg["params"], snapshots,
                                warmup)
    for name, limit in traffic["limits"].items():
        ctx.compare(name, readings[name], limit)
