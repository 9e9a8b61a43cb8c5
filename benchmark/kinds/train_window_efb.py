"""Traffic kind `train_window_efb`: `train_window`'s closed loop for a
configuration handed over as a scipy CSR matrix whose one-hot columns
Exclusive Feature Bundling packs, with a test set evaluated every tree.
One client:

    ds = lgb.Dataset(X_csr, label=y).construct()
    test = lgb.Dataset(Xt_csr, label=yt, reference=ds)
    lgb.train(params, ds, valid_sets=[test], callbacks=[...])

The first `warmup_trees` trees are untimed and are what the plain EFB
reference (`reference/gbdt_efb.py`) follows: after each of them, and never
inside the window, the kind pulls the training scores, the test scores and
the AUC the program reported. The reference runs in a process of its own
(`follow_efb.py`), started first thing: it makes the same table from the
seed and its tables while the program makes its own and compiles, is
handed the warm-up trees once the window has closed, and follows them on
every core but one while the kind checks the program's path. Then trees until the window's seconds have
run out, each closed by `block_until_ready` on the training scores in a
callback that `lgb.train` calls AFTER that tree's test-set AUC has
returned its host value. The window's pieces are `train_window.py`'s and
`train_window_cat.py`'s, imported (`mosaic_kernels`, `lower_whole_tree`,
`MAX_TREES`, `LEARNER`).

The traffic file's parameters are `train_window`'s:
  warmup_trees   trees before the window opens, followed by the reference
  trace_seconds  the window's length in a traced run
  limits         the limit of each number that decides `correct`

The run ends non-zero and prints no line (harness.Refused):
  3  (the harness's) JAX's default device is not the cell's platform;
  4  the program has no `lgbm.bundle_scan` scope (a program from before the
     split scan in width classes, whose scan pads every one-hot column to
     the widest feature's bins: said at once, before any data is made);
     the trees are about to be grown by another learner than
     `DeviceTreeLearner` (said before the first tree); the data set has no
     bundle; the window's `tree_wave` notes count no split on a bundle
     member; `device_hist_rows` did not move in the window; the lowered
     whole-tree program lacks (on the chip) one of the two Mosaic kernels;
  5  training stopped by itself inside the window, or the model holds fewer
     trees than the run counted.
"""
from __future__ import annotations

import json
import os
import pickle
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

import data_allstate
import modeltext
import work
import work_efb
from harness import EXIT_NOT_DEVICE_PATH, Refused, load_module

FOLLOWER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "follow_efb.py")
FOLLOW_TIMEOUT_S = 1800  # the longest wait for the reference's readings


class Follower:
    """The reference's process (`follow_efb.py`): started with the
    configuration and the seed, handed the layout and then the warm-up
    trees through files in a directory of its own, each announced by a
    line on its standard input."""

    def __init__(self, cfg: dict, seed: int) -> None:
        self.dir = tempfile.mkdtemp(prefix="efb_follow_")
        self.proc = subprocess.Popen([sys.executable, FOLLOWER],
                                     stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True)
        self._say(json.dumps({"config": cfg, "seed": int(seed),
                              "dir": self.dir, "parent": os.getpid()}))

    def _say(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def hand(self, name: str, obj) -> None:
        path = os.path.join(self.dir, name + ".pkl")
        with open(path + ".part", "wb") as fh:
            pickle.dump(obj, fh, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(path + ".part", path)
        self._say(name)

    def result(self) -> dict:
        """{"readings": ..., "seconds": ...}, once the process is done."""
        out, _ = self.proc.communicate(timeout=FOLLOW_TIMEOUT_S)
        if self.proc.returncode != 0:
            raise RuntimeError(f"the reference's process exited "
                               f"{self.proc.returncode}")
        return json.loads(out.strip().splitlines()[-1])

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        shutil.rmtree(self.dir, ignore_errors=True)


def check_efb_path(ctx, learner, hist_rows: int, notes: list,
                   mosaic_kernels_of, learner_name: str) -> None:
    """Every reason for exit 4 that is known only after the window."""
    if type(learner).__name__ != learner_name:
        raise Refused(EXIT_NOT_DEVICE_PATH, "trees were grown by "
                      f"{type(learner).__name__}, not {learner_name}")
    if getattr(learner.meta, "width_classes", None) is None:
        raise Refused(EXIT_NOT_DEVICE_PATH, "the data set has no bundle: "
                      "the split scan gathered every feature to [F, Bmax]")
    if not sum(n.get("efb_splits", 0) for n in notes):
        raise Refused(EXIT_NOT_DEVICE_PATH, "the window's tree_wave notes "
                      "count no split on a bundle member")
    if hist_rows <= 0:
        raise Refused(EXIT_NOT_DEVICE_PATH, "device_hist_rows did not move: "
                      "the device histogram path never ran")
    if ctx.rehearsal:
        return
    kernels = mosaic_kernels_of(learner)
    if not (any("compact" in k for k in kernels)
            and any("histogram" in k for k in kernels)):
        raise Refused(EXIT_NOT_DEVICE_PATH, "the whole-tree program's Mosaic "
                      f"kernels are {kernels}: the Pallas histogram and "
                      "compaction did not both reach Mosaic")


def layout_of(core) -> dict:
    """What the reference is handed of the construction: the bundles'
    columns in order, the rows counted as overwritten, and each column's
    bin upper bounds (None where the column is not used), which it only
    compares with its own; never a bin or an offset."""
    used = set(core.used_features)
    return {
        "bounds": [np.asarray(m.bin_upper_bound, np.float64) if f in used
                   else None for f, m in enumerate(core.mappers)],
        "bundles": [list(g.feature_indices) for g in core.groups
                    if g.is_multi],
        "conflict_rows": int(core.conflict_rows),
    }


def run(ctx) -> None:
    from lightgbm_tpu.utils import timer

    if not hasattr(timer, "SCOPE_BUNDLE_SCAN"):
        raise Refused(EXIT_NOT_DEVICE_PATH, "this program has no "
                      "lgbm.bundle_scan scope (utils/timer.py): its split "
                      "scan gathers every feature to [F, Bmax], a bundled "
                      "table's 4,228 features padded to 255 bins")
    follower = Follower(ctx.config, ctx.seed)
    try:
        _run(ctx, follower)
    finally:
        follower.close()


def _run(ctx, follower: Follower) -> None:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu import tracing
    from lightgbm_tpu.callback import EarlyStopException
    from lightgbm_tpu.treelearner.device import grow_tree_on_device
    from lightgbm_tpu.utils.timer import global_timer
    train_window = load_module("kinds", "train_window", ctx.roots)
    learner_name = load_module("kinds", "train_window_cat", ctx.roots).LEARNER
    cfg, traffic = ctx.config, ctx.traffic
    warmup = int(traffic["warmup_trees"])
    params = dict(cfg["params"], verbosity=-1)
    t0 = time.perf_counter()
    sets = data_allstate.make_allstate_data(cfg, ctx.seed)
    ctx.counts["data_s"] = time.perf_counter() - t0
    (X, y), (Xt, yt) = sets.pop("train"), sets.pop("test")
    n_test = Xt.shape[0]
    t0 = time.perf_counter()
    with ctx.span("dataset_construct"):
        ds = lgb.Dataset(X, label=y, params=params).construct()
    ctx.counts["dataset_construct_s"] = time.perf_counter() - t0
    del X  # the reference's process holds its own
    test = lgb.Dataset(Xt, label=yt, reference=ds)
    del Xt
    follower.hand("layout", layout_of(ds._handle))

    handed = {"train_scores": [], "test_scores": [], "auc": []}
    program = {"handed": handed, "n_follow": warmup}  # the model text added
    stamps, marks = [], {}
    tree_span = [None]

    def counters() -> tuple:
        return (int(global_timer.counters.get("device_hist_rows", 0)),
                grow_tree_on_device._cache_size())

    def before(env) -> None:
        if env.iteration == 0:
            learner = env.model._gbdt.tree_learner
            if type(learner).__name__ != learner_name:
                raise Refused(EXIT_NOT_DEVICE_PATH, "the trees are about to "
                              f"be grown by {type(learner).__name__}, not "
                              f"{learner_name}: the bundled run left the "
                              "chip's whole-tree program")
        if ctx.window_open_at is not None:
            tree_span[0] = ctx.span(f"tree_{env.iteration}")
            tree_span[0].__enter__()
    before.before_iteration = True

    def after(env) -> None:
        # the tree's test-set AUC has returned its host value already
        g = env.model._gbdt
        jax.block_until_ready(g.score)  # the tree's work is done
        now = time.perf_counter()
        if tree_span[0] is not None:
            tree_span[0].__exit__(None, None, None)
            tree_span[0] = None
        if env.iteration < warmup:
            handed["train_scores"].append(np.asarray(g.score[0]))
            handed["test_scores"].append(np.asarray(g.valid_sets[0].score[0]))
            handed["auc"].append(float(env.evaluation_result_list[0][2]))
            if env.iteration == warmup - 1:
                program["model"] = env.model.model_to_string()
                marks["open"] = counters()
                ctx.open_window()
            return
        stamps.append(now)
        if now - ctx.window_open_at >= ctx.window_limit():
            marks["close"] = counters()
            ctx.close_window()
            follower.hand("program", program)
            raise EarlyStopException(env.iteration, [])
    after.order = 100  # after every other callback of the iteration

    bst = lgb.train(params, ds, num_boost_round=warmup + train_window.MAX_TREES,
                    valid_sets=[test], valid_names=["test"],
                    callbacks=[before, after])
    if "close" not in marks:
        raise Refused(5, "training stopped by itself before the window "
                         "closed (no more splits)")
    learner = bst._gbdt.tree_learner
    n_trees = len(stamps)
    window_s = stamps[-1] - ctx.window_open_at
    hist_rows, programs = (b - a for a, b in zip(marks["open"],
                                                 marks["close"]))
    notes = [n for n in tracing.recorder().snapshot()
             if n["kind"] == "tree_wave"
             and ctx.window_open_at <= n["t"] < ctx.window_open_at + window_s]
    check_efb_path(ctx, learner, hist_rows, notes,
                   lambda lrn: train_window.mosaic_kernels(
                       train_window.lower_whole_tree(lrn).as_text()),
                   learner_name)

    trees = modeltext.parse_model(bst.model_to_string())
    if len(trees) < warmup + n_trees:
        raise Refused(5, f"the model holds {len(trees)} trees, the run "
                         f"counted {warmup + n_trees}")
    window_trees = trees[warmup:warmup + n_trees]
    core = bst._gbdt.train_set
    n_groups = core.num_groups
    bin_bytes = learner.bins_dev.dtype.itemsize
    gh_bytes, operand = 4, "bf16"
    waves = sum(int(n.get("waves", 0)) for n in notes)
    scan_work = work_efb.bundle_scan_work(
        n_groups, learner.group_bin_padded, len(core.used_features),
        learner.wave_k, waves, n_trees)
    needed = [work.train_tree_work(t, n_groups, bin_bytes, gh_bytes, operand)
              for t in window_trees]
    needed += [work_efb.eval_work(t, n_test, n_groups, bin_bytes)
               for t in window_trees]
    total = work.Work(sum(w.bytes for w in needed),
                      sum(w.ops for w in needed), operand)
    ctx.least_s = work.least_seconds(total, ctx.device["kind"]) \
        if not ctx.rehearsal else None
    # a row's histogram work is one accumulation a GROUP of the bundled
    # plane (what bundling buys), not one a column: `features` is what
    # train.hist_kernel_roofline divides by
    ctx.counts.update(
        window_trees=n_trees, window_s=window_s, hist_rows=hist_rows,
        programs_compiled=programs, features=n_groups, bin_bytes=bin_bytes,
        gh_bytes=gh_bytes,
        operand=operand, waves=waves,
        bundle_scan_work=(scan_work.bytes, scan_work.ops, scan_work.operand))
    ctx.e2e["train_s_per_tree"] = window_s / n_trees
    ctx.attempted, ctx.failed = n_trees, 0

    t0 = time.perf_counter()
    followed = follower.result()
    took = followed["seconds"]
    print(f"train_window_efb: data {ctx.counts['data_s']:.1f} s, "
          f"Dataset.construct {ctx.counts['dataset_construct_s']:.1f} s, "
          f"set-up {ctx.setup_s:.1f} s, groups {n_groups}; the reference's "
          f"process: data {took['data']:.1f} s, bounds {took['bounds']:.1f}"
          f" s, tables {took['tables']:.1f} s, trees {took['trees']:.1f} s,"
          f" {took['all']:.1f} s in all, waited for "
          f"{time.perf_counter() - t0:.1f} s after the window",
          file=sys.stderr)
    for name, limit in traffic["limits"].items():
        ctx.compare(name, followed["readings"][name], limit)
