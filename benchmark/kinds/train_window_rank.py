"""Traffic kind `train_window_rank`: `train_window`'s closed loop for a
configuration that states `objective=lambdarank` with validation in the
loop. One client:

    ds = lgb.Dataset(X, label=grades, group=sizes).construct()
    valid = [lgb.Dataset(Xv, label=..., group=..., reference=ds), ...]
    lgb.train(params, ds, valid_sets=valid, callbacks=[record_evaluation, ..])

The first `warmup_trees` iterations are untimed and are what the plain
reference (`reference/lambdarank.py`) follows: after each of them, and never
inside the window, the kind pulls the training scores, both validation
sets' scores and the NDCG values the program reported. Then iterations
until the window's seconds have run out. An iteration is closed by
`block_until_ready` on the training scores in a callback that `lgb.train`
calls AFTER that iteration's evaluation of both sets has returned its host
values: `train_s_per_tree` is what the user waits for a tree: gradients,
growth, score update, two validation updates, eight NDCG values.
`train_window` stays as it is (it calls the log-loss reference by name);
the window loop is that file's, copied (PERF.md section 7 asks the next
`benchmark` issue to fold the copies).

The traffic file's parameters are `train_window`'s:
  warmup_trees   iterations before the window opens, followed by the reference
  trace_seconds  the window's length in a traced run
  limits         the limit of each number that decides `correct`

The run ends non-zero and prints no line (harness.Refused):
  3  (the harness's) JAX's default device is not the cell's platform;
  4  the program has no `lgbm.rank_pairs` scope (a program from before the
     one-program gradient pass: said at once, before any data is made); the
     booster's objective is not lambdarank; the trees were not grown by
     `DeviceTreeLearner`; `device_hist_rows` did not move in the window; the
     learner's plane has fewer groups than the configuration has features
     (bundling merged the shape away); its lowered whole-tree program lacks
     (on the chip) one of the two Mosaic kernels; a window iteration has no
     `rank_gradients` note, or a note whose `pair_positions` is not what
     `work_rank.py` counts from the data; a window iteration did not
     evaluate every `eval_at` of both sets;
  5  training stopped by itself inside the window, or the model holds fewer
     trees than the run counted.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

import data_rank
import modeltext
import work
import work_rank
from harness import EXIT_NOT_DEVICE_PATH, Refused, load_module
from reference import lambdarank as reference

MAX_TREES = 100000
LEARNER = "DeviceTreeLearner"


def check_rank_path(ctx, bst, learner, hist_rows: int, n_features: int,
                    notes: list, n_trees: int, needed_pairs: int,
                    evaluations: list, n_values: int,
                    mosaic_kernels_of) -> None:
    """Every reason for exit 4 but the first."""
    said = bst._gbdt.objective.to_string() if bst._gbdt.objective else None
    if said != "lambdarank":
        raise Refused(EXIT_NOT_DEVICE_PATH, f"the booster's objective is "
                      f"{said!r}, not 'lambdarank'")
    if type(learner).__name__ != LEARNER:
        raise Refused(EXIT_NOT_DEVICE_PATH, "trees were grown by "
                      f"{type(learner).__name__}, not {LEARNER}")
    if hist_rows <= 0:
        raise Refused(EXIT_NOT_DEVICE_PATH, "device_hist_rows did not move: "
                      "the device histogram path never ran")
    groups = int(learner.bins_dev.shape[0])
    if groups < n_features:
        raise Refused(EXIT_NOT_DEVICE_PATH, f"the learner's plane has "
                      f"{groups} groups for {n_features} features: bundling "
                      "merged the configuration's shape away")
    if len(notes) < n_trees:
        raise Refused(EXIT_NOT_DEVICE_PATH, f"{len(notes)} rank_gradients "
                      f"notes for the window's {n_trees} iterations: the "
                      "gradient pass was not the one program that notes it")
    wrong = [n["pair_positions"] for n in notes
             if n.get("pair_positions") != needed_pairs]
    if wrong:
        raise Refused(EXIT_NOT_DEVICE_PATH, "a rank_gradients note says "
                      f"pair_positions {wrong[0]}, the data's query sizes "
                      f"give {needed_pairs}")
    if any(n != n_values for n in evaluations):
        raise Refused(EXIT_NOT_DEVICE_PATH, "a window iteration evaluated "
                      f"{min(evaluations)} values, not every eval_at of "
                      f"both sets ({n_values})")
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
    from lightgbm_tpu.treelearner.device import grow_tree_on_device
    from lightgbm_tpu.utils import timer
    from lightgbm_tpu.utils.timer import global_timer

    if not hasattr(timer, "SCOPE_RANK_PAIRS"):
        raise Refused(EXIT_NOT_DEVICE_PATH, "this program has no "
                      "lgbm.rank_pairs scope (utils/timer.py): its "
                      "lambdarank gradient pass is not the one device "
                      "program this cell measures")
    train_window = load_module("kinds", "train_window", ctx.roots)
    cfg, traffic = ctx.config, ctx.traffic
    warmup = int(traffic["warmup_trees"])
    params = dict(cfg["params"], verbosity=-1)
    eval_at = [int(k) for k in params["eval_at"]]
    t0 = time.perf_counter()
    sets = data_rank.make_rank_data(cfg, ctx.seed)
    ctx.counts["data_s"] = time.perf_counter() - t0
    valid_names = [name for name in sets if name != "train"]
    X, grades, sizes = sets["train"]
    t0 = time.perf_counter()
    with ctx.span("dataset_construct"):
        ds = lgb.Dataset(X, label=grades, group=sizes).construct()
    ctx.counts["dataset_construct_s"] = time.perf_counter() - t0
    valid = [lgb.Dataset(sets[name][0], label=sets[name][1],
                         group=sets[name][2], reference=ds)
             for name in valid_names]

    handed = {"train_scores": [], "ndcg": {},
              "valid_scores": {name: [] for name in valid_names}}
    stamps, evaluations, marks = [], [], {}
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
        # the iteration's evaluation has returned its host values already
        g = env.model._gbdt
        jax.block_until_ready(g.score)  # the iteration's work is done
        now = time.perf_counter()
        if tree_span[0] is not None:
            tree_span[0].__exit__(None, None, None)
            tree_span[0] = None
        if env.iteration < warmup:
            handed["train_scores"].append(np.asarray(g.score[0]))
            for name, vd in zip(valid_names, g.valid_sets):
                handed["valid_scores"][name].append(np.asarray(vd.score[0]))
            for name, metric, value, _ in env.evaluation_result_list:
                handed["ndcg"].setdefault(name, {}).setdefault(
                    metric, []).append(float(value))
            if env.iteration == warmup - 1:
                marks["open"] = counters()
                ctx.open_window()
            return
        stamps.append(now)
        evaluations.append(len(env.evaluation_result_list))
        if now - ctx.window_open_at >= ctx.window_limit():
            marks["close"] = counters()
            ctx.close_window()
            raise EarlyStopException(env.iteration, [])
    after.order = 100  # after every other callback of the iteration

    bst = lgb.train(params, ds, num_boost_round=warmup + MAX_TREES,
                    valid_sets=valid, valid_names=valid_names,
                    callbacks=[before, after])
    if "close" not in marks:
        raise Refused(5, "training stopped by itself before the window "
                         "closed (no more splits)")
    learner = bst._gbdt.tree_learner
    n_trees = len(stamps)
    window_s = stamps[-1] - ctx.window_open_at
    hist_rows, programs = (b - a for a, b in zip(marks["open"],
                                                 marks["close"]))
    truncation = int(params["lambdarank_truncation_level"])
    needed_pairs = work_rank.pair_positions(sizes, truncation)
    notes = [n for n in tracing.recorder().snapshot()
             if n["kind"] == "rank_gradients"
             and ctx.window_open_at <= n["t"] < ctx.window_open_at + window_s]
    n_feat = int(cfg["features"])
    check_rank_path(
        ctx, bst, learner, hist_rows, n_feat, notes, n_trees, needed_pairs,
        evaluations, len(valid_names) * len(eval_at),
        lambda lrn: train_window.mosaic_kernels(
            train_window.lower_whole_tree(lrn).as_text()))

    model_text = bst.model_to_string()
    trees = modeltext.parse_model(model_text)
    if len(trees) < warmup + n_trees:
        raise Refused(5, f"the model holds {len(trees)} trees, the run "
                         f"counted {warmup + n_trees}")
    window_trees = trees[warmup:warmup + n_trees]
    gh_bytes, operand = 4, "bf16"
    valid_rows = sum(int(sets[name][0].shape[0]) for name in valid_names)
    pass_work = work_rank.gradient_work(sizes, truncation)
    needed = [work.train_tree_work(t, n_feat, 1, gh_bytes, operand)
              for t in window_trees]
    needed += [work_rank.eval_work(t, valid_rows, n_feat)
               for t in window_trees]
    needed += [pass_work] * n_trees
    total = work.Work(sum(w.bytes for w in needed),
                      sum(w.ops for w in needed), operand)
    ctx.least_s = work.least_seconds(total, ctx.device["kind"]) \
        if not ctx.rehearsal else None
    ctx.counts.update(
        window_trees=n_trees, window_s=window_s, hist_rows=hist_rows,
        programs_compiled=programs, features=n_feat, bin_bytes=1,
        gh_bytes=gh_bytes, operand=operand,
        rank_pair_work=(pass_work.bytes * n_trees, pass_work.ops * n_trees,
                        pass_work.operand))
    ctx.device_extra["plane_groups"] = int(learner.bins_dev.shape[0])
    ctx.e2e["train_s_per_tree"] = window_s / n_trees
    ctx.attempted, ctx.failed = n_trees, 0

    del bst, ds, valid, learner
    gc.collect()
    t0 = time.perf_counter()
    readings = reference.follow(sets, trees, cfg["params"], handed, warmup)
    print(f"train_window_rank: data {ctx.counts['data_s']:.1f} s, "
          f"Dataset.construct {ctx.counts['dataset_construct_s']:.1f} s, "
          f"set-up {ctx.setup_s:.1f} s, reference "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    for name, limit in traffic["limits"].items():
        ctx.compare(name, readings[name], limit)
