"""Traffic kind `train_window_quant`: `train_window`'s closed loop (one
`lgb.Dataset(X, label=y).construct()`, one `lgb.train` call, its first
trees untimed and followed by the reference, then trees until the window's
seconds have run out, each closed by `block_until_ready` on the training
scores) for a configuration that states `use_quantized_grad`: every tree is
quantized, histogrammed in integers and scanned through the two scales.
`train_window` stays as it is: it calls the float reference by name. The
window loop is that file's, copied (as `train_window_sharded` copies it:
PERF.md section 7 asks the next `benchmark` issue to fold the three).

After each WARM-UP tree, and never inside the window, the kind pulls what
the plain quantized reference (`reference/gbdt_quant.py`) is handed and
does not trust: the training scores and the tree's integer pack `[N, 3]`
int8 with its two float32 scales (`learner.quant_pack()`).

The traffic file's parameters are `train_window`'s:
  warmup_trees   trees grown before the window opens; the reference follows
                 exactly these, through the same call and the same booster
                 that the window then drives
  trace_seconds  the window's length in a traced run
  limits         the limit of each number that decides `correct`

The run ends non-zero and prints no line (harness.Refused):
  3  (the harness's) JAX's default device is not the cell's platform;
  4  the program's learners keep no integer pack (a program from before
     `quant_pack`: said at once, before any data is made); the trees were
     not grown by `DeviceTreeLearner`; the learner is not `quantized`; the
     `device_hist_rows` counter did not move in the window; the `tree_wave`
     notes of the window's trees do not all say `hist_int` 1; the learner's
     lowered whole-tree program lacks (on the chip) one of the two Mosaic
     kernels;
  5  training stopped by itself inside the window, or the model holds fewer
     trees than the run counted.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import data
import modeltext
import work
from harness import EXIT_NOT_DEVICE_PATH, Refused, load_module
from reference import gbdt_quant as reference

MAX_TREES = 100000
LEARNER = "DeviceTreeLearner"


def check_quantized_path(ctx, learner, hist_rows: int, share,
                         mosaic_kernels_of) -> None:
    """Every reason for exit 4 but the first: the window's trees went
    through the one-chip device learner's integer path, or no line.
    `mosaic_kernels_of(learner)` lowers the learner's whole-tree program and
    names its Mosaic kernels; it is asked once the learner is known to be
    the one whose program it lowers."""
    if type(learner).__name__ != LEARNER:
        raise Refused(EXIT_NOT_DEVICE_PATH, "trees were grown by "
                      f"{type(learner).__name__}, not {LEARNER}")
    if not getattr(learner, "quantized", False):
        raise Refused(EXIT_NOT_DEVICE_PATH, "the learner is not quantized: "
                      "the configuration's use_quantized_grad did not "
                      "reach it")
    if hist_rows <= 0:
        raise Refused(EXIT_NOT_DEVICE_PATH, "device_hist_rows did not move: "
                      "the device histogram path never ran")
    if share is None or share < 1.0:
        raise Refused(EXIT_NOT_DEVICE_PATH, "the window's tree_wave notes "
                      f"say hist_int 1 for a share of {share}, not 1: the "
                      "histogram operand was not the integer one")
    if ctx.rehearsal:
        return
    kernels = mosaic_kernels_of(learner)
    if not (any("compact" in k for k in kernels)
            and any("histogram" in k for k in kernels)):
        raise Refused(EXIT_NOT_DEVICE_PATH, "the whole-tree program's Mosaic "
                      f"kernels are {kernels}: the Pallas histogram and "
                      "compaction did not both reach Mosaic")


def run(ctx) -> None:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu import tracing
    from lightgbm_tpu.callback import EarlyStopException
    from lightgbm_tpu.treelearner import device as device_mod
    from lightgbm_tpu.utils.timer import global_timer

    if not hasattr(device_mod.DeviceTreeLearner, "quant_pack"):
        raise Refused(EXIT_NOT_DEVICE_PATH, "this program's learners keep no "
                      "integer pack (no DeviceTreeLearner.quant_pack): the "
                      "quantized reference has nothing to check")
    train_window = load_module("kinds", "train_window", ctx.roots)
    grow_tree_on_device = device_mod.grow_tree_on_device
    cfg, traffic = ctx.config, ctx.traffic
    warmup = int(traffic["warmup_trees"])
    rows = int(cfg["rows"])
    X, y = data.make_data(rows, int(cfg["features"]), ctx.seed,
                          int(cfg["data_seed"]))
    t0 = time.perf_counter()
    with ctx.span("dataset_construct"):
        ds = lgb.Dataset(X, label=y).construct()
    ctx.counts["dataset_construct_s"] = time.perf_counter() - t0

    snapshots, packs, stamps, marks = [], [], [], {}
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
            handed = g.tree_learner.quant_pack()
            if handed is None:
                raise Refused(EXIT_NOT_DEVICE_PATH, "the learner handed over "
                              "no integer pack: it is not quantized")
            packs.append((np.asarray(handed[0])[:rows],
                          np.asarray(handed[1])))
            if env.iteration == warmup - 1:
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
    # the reading of `train_quant.int_hist_tree_share`, by its own reader
    said = load_module("readers", "flight_notes", ctx.roots).notes_sum(
        tracing.recorder().snapshot(), "tree_wave", "hist_int",
        ctx.window_open_at, ctx.window_open_at + window_s)
    share = None if said is None else said / n_trees
    check_quantized_path(
        ctx, learner, hist_rows, share,
        lambda lrn: train_window.mosaic_kernels(
            train_window.lower_whole_tree(lrn).as_text()))

    model_text = bst.model_to_string()
    trees = modeltext.parse_model(model_text)
    if len(trees) < warmup + n_trees:
        raise Refused(5, f"the model holds {len(trees)} trees, the run "
                         f"counted {warmup + n_trees}")
    window_trees = trees[warmup:warmup + n_trees]
    gh_bytes, operand = 1, "int8"
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
                                packs, warmup)
    for name, limit in traffic["limits"].items():
        ctx.compare(name, readings[name], limit)
