"""Traffic kind `train_window_sharded`: `train_window`'s closed loop (one
`lgb.Dataset(X, label=y).construct()`, one `lgb.train` call, its first
trees untimed and followed by the reference, then trees until the window's
seconds have run out, each closed by `block_until_ready` on the training
scores) for a configuration whose parameters ask for the row-sharded device
learner: `tree_learner=data`, `num_machines` = the cell's `chips`, one
process, the in-process mesh. `train_window` stays as it is: it refuses any
learner but the one-chip `DeviceTreeLearner` and lowers the one-chip program.

The traffic file's parameters are `train_window`'s:
  warmup_trees   trees grown before the window opens; the reference follows
                 exactly these, through the same call and the same booster
                 that the window then drives
  trace_seconds  the window's length in a traced run
  limits         the limit of each number that decides `correct`

The run ends non-zero and prints no line (harness.Refused):
  3  (the harness's) JAX's default device is not the cell's platform, or it
     sees fewer devices than the cell's `chips`;
  4  the trees were not grown by `DeviceDataParallelTreeLearner`; its mesh
     does not span exactly `chips` devices; the binned plane is not split
     `chips` ways, one equal `[groups, n_pad / chips]` block a device; the
     `device_hist_rows` counter did not move in the window; the learner's
     lowered whole-tree program lacks one of `reduce_scatter`, `all_gather`,
     `all_reduce`, or (on the chip) one of the two Mosaic kernels;
  5  training stopped by itself inside the window, or the model holds fewer
     trees than the run counted.

What the readers are handed means per `chips` chips what `train_window`'s
counts mean per one: `hist_rows` is the program's global count (the sharded
program sums it over the mesh), `least_s` the least time for the window's
work (benchmark/work.py, from the model and the shapes alone) spread over
`chips` chips' peaks, `programs_compiled` the growth of the learner's own
jit caches (`_grow_fns`) inside the window, `chips` the mesh size. A reader
that sums device time over devices (`trace_ops`) is given its per-chip mean
by the metric file's `scale`.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import data
import modeltext
import work
from harness import EXIT_NOT_DEVICE_PATH, Refused, load_module
from reference import gbdt as reference

LEARNER = "DeviceDataParallelTreeLearner"
COLLECTIVES = ("reduce_scatter", "all_gather", "all_reduce")


def lower_sharded_whole_tree(learner):
    """The learner's sharded whole-tree program (float or quantized, not
    bagged), lowered with its own arguments and shardings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def on(spec, shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(learner.mesh, spec))

    n = learner.n_pad
    gh_dtype = jnp.int8 if learner.quantized else jnp.float32
    return learner._grow_fn(False, False).lower(
        on(P(None, "data"), learner.bins_dev.shape, learner.bins_dev.dtype),
        on(P("data"), (n, 3), gh_dtype), on(P("data"), (n,), jnp.int32),
        learner._gidx_arg, learner._vslot_arg, learner._scan_meta_arg,
        learner._tables_rep, learner._params_rep,
        on(learner._fmask_spec, (learner.f_pad,), jnp.bool_),
        on(P(), (3,), jnp.float32))


def check_sharded_path(ctx, learner, hist_rows: int,
                       mosaic_kernels) -> None:
    """Every reason for exit 4: the work went through the row-sharded
    device path on all of the cell's chips, or there is no line."""
    chips = int(ctx.cell["chips"])
    if type(learner).__name__ != LEARNER:
        raise Refused(EXIT_NOT_DEVICE_PATH, "trees were grown by "
                      f"{type(learner).__name__}, not {LEARNER}")
    if int(learner.mesh.devices.size) != chips:
        raise Refused(EXIT_NOT_DEVICE_PATH, "the learner's mesh spans "
                      f"{learner.mesh.devices.size} devices, the cell's "
                      f"chips are {chips}")
    groups, n_pad = learner.bins_dev.shape
    shards = sorted((str(s.device), tuple(s.data.shape))
                    for s in learner.bins_dev.addressable_shards)
    if (len({d for d, _ in shards}) != chips
            or any(shape != (groups, n_pad // chips) for _, shape in shards)):
        raise Refused(EXIT_NOT_DEVICE_PATH, f"the plane [{groups}, {n_pad}] "
                      f"is not split {chips} ways: {shards}")
    if hist_rows <= 0:
        raise Refused(EXIT_NOT_DEVICE_PATH, "device_hist_rows did not move: "
                      "the device histogram path never ran")
    text = lower_sharded_whole_tree(learner).as_text()
    missing = [op for op in COLLECTIVES if f"stablehlo.{op}" not in text]
    if missing:
        raise Refused(EXIT_NOT_DEVICE_PATH, "the sharded whole-tree program "
                      f"holds no {' / '.join(missing)}")
    kernels = mosaic_kernels(text)
    if not ctx.rehearsal and not (any("compact" in k for k in kernels)
                                  and any("histogram" in k for k in kernels)):
        raise Refused(EXIT_NOT_DEVICE_PATH, "the sharded program's Mosaic "
                      f"kernels are {kernels}: the Pallas histogram and "
                      "compaction did not both reach Mosaic")


def run(ctx) -> None:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.callback import EarlyStopException
    from lightgbm_tpu.utils.timer import global_timer

    cfg, traffic = ctx.config, ctx.traffic
    chips = int(ctx.cell["chips"])
    warmup = int(traffic["warmup_trees"])
    one_chip_kind = load_module("kinds", "train_window", ctx.roots)
    X, y = data.make_data(int(cfg["rows"]), int(cfg["features"]), ctx.seed,
                          int(cfg["data_seed"]))
    t0 = time.perf_counter()
    with ctx.span("dataset_construct"):
        ds = lgb.Dataset(X, label=y).construct()
    ctx.counts["dataset_construct_s"] = time.perf_counter() - t0

    snapshots, stamps, marks = [], [], {}
    tree_span = [None]

    def counters(learner) -> tuple:
        return (int(global_timer.counters.get("device_hist_rows", 0)),
                sum(fn._cache_size()
                    for fn in getattr(learner, "_grow_fns", {}).values()))

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
                marks["open"] = counters(g.tree_learner)
                ctx.open_window()
            return
        stamps.append(now)
        if now - ctx.window_open_at >= ctx.window_limit():
            marks["close"] = counters(g.tree_learner)
            ctx.close_window()
            raise EarlyStopException(env.iteration, [])

    params = dict(cfg["params"], verbosity=-1)
    bst = lgb.train(params, ds,
                    num_boost_round=warmup + one_chip_kind.MAX_TREES,
                    callbacks=[before, after])
    if "close" not in marks:
        raise Refused(5, "training stopped by itself before the window "
                         "closed (no more splits)")
    learner = bst._gbdt.tree_learner
    n_trees = len(stamps)
    window_s = stamps[-1] - ctx.window_open_at
    hist_rows, programs = (b - a for a, b in zip(marks["open"],
                                                 marks["close"]))
    check_sharded_path(ctx, learner, hist_rows,
                       one_chip_kind.mosaic_kernels)

    trees = modeltext.parse_model(bst.model_to_string())
    if len(trees) < warmup + n_trees:
        raise Refused(5, f"the model holds {len(trees)} trees, the run "
                         f"counted {warmup + n_trees}")
    quantized = bool(learner.quantized)
    gh_bytes, operand = (1, "int8") if quantized else (4, "bf16")
    n_feat = int(cfg["features"])
    needed = [work.train_tree_work(t, n_feat, 1, gh_bytes, operand)
              for t in trees[warmup:warmup + n_trees]]
    total = work.Work(sum(w.bytes for w in needed),
                      sum(w.ops for w in needed), operand)
    if not ctx.rehearsal:
        least, bound = work.least_seconds(total, ctx.device["kind"])
        ctx.least_s = (least / chips, bound)
    ctx.counts.update(
        window_trees=n_trees, window_s=window_s, hist_rows=hist_rows,
        programs_compiled=programs, features=n_feat, bin_bytes=1,
        gh_bytes=gh_bytes, operand=operand, chips=chips)
    ctx.e2e["train_s_per_tree"] = window_s / n_trees
    ctx.attempted, ctx.failed = n_trees, 0

    del bst, ds, learner
    gc.collect()
    readings = reference.follow(X, y, trees, cfg["params"], snapshots,
                                warmup)
    for name, limit in traffic["limits"].items():
        ctx.compare(name, readings[name], limit)
