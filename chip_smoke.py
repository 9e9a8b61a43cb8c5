#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # one host's four chips, sharded path only

One process, the normal entry points, HIGGS's widths (28 dense f32 features,
max_bin=255, num_leaves=255, learning_rate=0.1) on the seeded synthetic data
bench.py generates, at a row count the whole-tree program fits in 16 GB
(CHANGES.md PR 22 says why it is not HIGGS's own 10.5M):

  agree   at a small N, the device learner against the host-driven learner
          (device_type=cpu) on the same data and seed, float and quantized:
          holdout AUC within the device-vs-serial tests' tolerance;
  train   lgb.Dataset -> lgb.train (one validation set, metric=auc) ->
          Booster.predict on held-out rows -> save_model / Booster(model_file)
          round trip, float and again with use_quantized_grad, 3 warm-up + 5
          timed trees each.

Every phase prints one JSON line. Any exception, a platform that is not
"tpu", a learner that is not the device learner, a Pallas kernel that did not
reach Mosaic (interpreted, or the XLA body taken instead), or an AUC that is
off ends the run non-zero with no `ok` line. The last line of stdout is
{"ok": true, "device": {"platform", "kind", "count"}} and nothing else.

--chips 4 runs ONLY tree_learner=data num_machines=4 on the in-process mesh
and the one-chip run of the same data and seed it is compared with (identical
trees under use_quantized_grad, predictions within tolerance in float), then
one tree each of voting and feature, and reports "count": 4.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

from bench import _auc as auc, make_data

ROWS = 1 << 21        # training rows, one chip (CHANGES.md PR 22, step 2)
ROWS_4CHIPS = 1 << 20  # training rows across the four-chip mesh
SMALL_ROWS = 1 << 15  # the agreement check's training rows
TREES = 8             # per timed run: WARMUP untimed, the rest timed
WARMUP = 3
AUC_FLOOR = 0.85      # at full N (bench.py's data: 28 features, unit noise)
# device-vs-serial tolerance of tests/test_device_learner.py, and the
# sharded-vs-single one of tests/test_sharded_device.py's ULP fields
RTOL, ATOL = 1e-4, 1e-5
ULP_FIELDS = {"split_gain", "internal_weight", "leaf_weight"}
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "learning_rate": 0.1, "min_data_in_leaf": 100, "metric": "auc",
          "verbosity": 0}


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def fail(why: str) -> None:
    print(f"chip_smoke: FAILED: {why}", file=sys.stderr, flush=True)
    sys.exit(1)


def split_data(n_train: int, n_valid: int, n_hold: int, seed: int):
    X, y = make_data(n_train + n_valid + n_hold, seed)
    a, b = n_train, n_train + n_valid
    return (X[:a], y[:a]), (X[a:b], y[a:b]), (X[b:], y[b:])


def cache_entries() -> int:
    import jax

    d = jax.config.jax_compilation_cache_dir
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


def device_bytes(devices, key: str) -> dict:
    return {str(d): int(d.memory_stats()[key]) for d in devices}


def mosaic_kernels(lowered_text: str) -> list:
    """The jitted kernel wrappers whose pallas_call a lowered program hands
    to Mosaic (each is a private function of the module, holding one
    tpu_custom_call). An interpreted kernel, or the XLA body in its place,
    leaves no tpu_custom_call behind."""
    names, func = set(), "main"
    for line in lowered_text.splitlines():
        m = re.search(r"func\.func (?:\w+ )?@([\w.]+)\(", line)
        if m:
            func = m.group(1)
        if "@tpu_custom_call" in line:
            names.add(func)
    return sorted(names)


def grow_kernels(learner) -> list:
    """Lower (not compile) the whole-tree program with the learner's own
    arguments and read the kernels out of it."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.treelearner.device import grow_tree_on_device

    n = learner.num_data
    shape = jax.ShapeDtypeStruct
    gh_dtype = jnp.int8 if learner.quantized else jnp.float32
    lowered = grow_tree_on_device.lower(
        shape(learner.bins_dev.shape, learner.bins_dev.dtype),
        shape((n, 3), gh_dtype), shape((n,), jnp.int32), learner.meta,
        learner.tables, learner.params_dev,
        shape((len(learner.meta.real_feature),), jnp.bool_),
        learner.config.num_leaves, learner.group_bin_padded,
        learner.config.max_depth, quantized=learner.quantized,
        scale_vec=learner._scale_vec, batch=learner.wave_k, bagged=False)
    return mosaic_kernels(lowered.as_text())


def timed_train(params: dict, train, valid, trees: int):
    """lgb.train with one validation set; every iteration is closed by
    block_until_ready on the train and validation scores before its clock
    is read. Returns (booster, record)."""
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.treelearner.device import grow_tree_on_device
    from lightgbm_tpu.utils.timer import global_timer

    t0 = time.perf_counter()
    ds = lgb.Dataset(train[0], label=train[1]).construct()
    dv = lgb.Dataset(valid[0], label=valid[1], reference=ds).construct()
    bin_s = time.perf_counter() - t0

    stamps, wave_ks, evals = [], [], {}

    def before(env):
        wave_ks.append(getattr(env.model._gbdt.tree_learner, "wave_k", 0))
    before.before_iteration = True

    def after(env):
        g = env.model._gbdt
        jax.block_until_ready([g.score] + [v.score for v in g.valid_sets])
        stamps.append(time.perf_counter())

    hist0 = int(global_timer.counters.get("device_hist_rows", 0))
    programs0 = grow_tree_on_device._cache_size()
    t0 = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=trees, valid_sets=[dv],
                    callbacks=[before, after, lgb.record_evaluation(evals)])
    tree_s = np.diff([t0] + stamps)
    warm = float(np.median(tree_s[WARMUP:])) if trees > WARMUP else None
    rec = {
        "rows": int(train[0].shape[0]), "features": int(train[0].shape[1]),
        "leaves": params["num_leaves"], "bins": params["max_bin"],
        "trees": len(stamps), "bin_s": round(bin_s, 3),
        "tree_s": [round(float(t), 4) for t in tree_s],
        "s_per_tree_warm": warm,
        "compile_s": (round(float(tree_s.sum() - warm * len(tree_s)), 3)
                      if warm is not None else None),
        "whole_tree_programs": grow_tree_on_device._cache_size() - programs0,
        "wave_k": wave_ks,
        "learner": type(bst._gbdt.tree_learner).__name__,
        "device_hist_rows":
            int(global_timer.counters.get("device_hist_rows", 0)) - hist0,
        "valid_auc": float(evals["valid_0"]["auc"][-1]),
    }
    return bst, rec


def check_device_run(rec: dict, learner_name: str = "DeviceTreeLearner"):
    if rec["learner"] != learner_name:
        fail(f"trees were grown by {rec['learner']}, not {learner_name}")
    if rec["device_hist_rows"] <= 0:
        fail("device_hist_rows is 0: the device histogram path never ran")


def predict_and_round_trip(bst, hold) -> dict:
    import lightgbm_tpu as lgb

    t0 = time.perf_counter()
    pred = bst.predict(hold[0])  # returns host values: the work is done
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    pred = bst.predict(hold[0])
    again_s = time.perf_counter() - t0
    if pred.shape != (hold[0].shape[0],) or not np.isfinite(pred).all():
        fail(f"predictions are not finite [{hold[0].shape[0]}]: {pred.shape}")
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "model.txt")
        bst.save_model(path)
        again = lgb.Booster(model_file=path).predict(hold[0])
    np.testing.assert_allclose(again, pred, rtol=1e-5)
    return {"holdout_rows": int(hold[0].shape[0]),
            "holdout_auc": auc(hold[1], pred),
            "predict_first_s": round(first_s, 3),
            "predict_s": round(again_s, 3), "round_trip": "ok"}


def tree_difference(a, b) -> str:
    """tests/test_sharded_device.py _assert_same_trees for two boosters:
    the first field that differs, or "" when the trees are the same."""
    ta, tb = a._gbdt.models, b._gbdt.models
    if len(ta) != len(tb):
        return f"{len(ta)} trees vs {len(tb)}"
    for i, (x, y) in enumerate(zip(ta, tb)):
        for k, va in x.__dict__.items():
            vb = y.__dict__[k]
            if k in ULP_FIELDS:
                same = np.allclose(va, vb, rtol=1e-6, atol=0)
            elif isinstance(va, np.ndarray):
                same = np.array_equal(va, vb)
            else:
                same = va == vb
            if not same:
                return f"tree {i} field {k}"
    return ""


# ------------------------------------------------------------- one chip

def phase_agree(seed: int, quantized: bool) -> None:
    """Small N: device learner vs the host-driven learner, same data, same
    seed, same backend — the oracle of tests/test_device_learner.py."""
    train, valid, hold = split_data(SMALL_ROWS, 4096, 8192, seed)
    params = dict(PARAMS, num_leaves=63, use_quantized_grad=quantized)
    dev, rec = timed_train(params, train, valid, 3)
    check_device_run(rec)
    host, host_rec = timed_train(dict(params, device_type="cpu"), train,
                                 valid, 3)
    if host_rec["learner"] != "SerialTreeLearner":
        fail(f"device_type=cpu grew trees with {host_rec['learner']}")
    p_dev, p_host = dev.predict(hold[0]), host.predict(hold[0])
    a_dev, a_host = auc(hold[1], p_dev), auc(hold[1], p_host)
    emit(phase="agree", quantized=quantized, rows=rec["rows"],
         leaves=params["num_leaves"], trees=rec["trees"],
         auc_device=a_dev, auc_host_learner=a_host,
         pred_max_abs_diff=float(np.max(np.abs(p_dev - p_host))),
         device_hist_rows=rec["device_hist_rows"])
    if abs(a_dev - a_host) > ATOL + RTOL * abs(a_host):
        fail(f"small-N holdout AUC: device {a_dev} vs host learner {a_host}")


def phase_train(seed: int, rows: int, quantized: bool) -> None:
    train, valid, hold = split_data(rows, 100_000, 100_000, seed)
    params = dict(PARAMS, use_quantized_grad=quantized)
    bst, rec = timed_train(params, train, valid, TREES)
    check_device_run(rec)
    kernels = grow_kernels(bst._gbdt.tree_learner)
    rec.update(predict_and_round_trip(bst, hold))
    emit(phase="train", quantized=quantized, kernels=kernels, **rec)
    if not (any("compact" in k for k in kernels)
            and any("histogram" in k for k in kernels)):
        fail(f"the whole-tree program's Mosaic kernels are {kernels}: the "
             "Pallas histogram and compaction did not both reach Mosaic")
    for name in ("valid_auc", "holdout_auc"):
        if not rec[name] > AUC_FLOOR:
            fail(f"{name} {rec[name]} is not above {AUC_FLOOR}")


def run_one_chip(seed: int, rows: int) -> None:
    for quantized in (False, True):
        phase_agree(seed, quantized)
    for quantized in (False, True):
        phase_train(seed, rows, quantized)


# ----------------------------------------------------------- four chips

def sharded_facts(learner) -> dict:
    """Where the plane lives and what the sharded program exchanges, read
    from the learner's own arrays and its lowered whole-tree program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    shards = learner.bins_dev.addressable_shards
    n = learner.n_pad
    gh_dtype = jnp.int8 if learner.quantized else jnp.float32
    grow = learner._grow_fn(False, False)

    def on(spec, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(learner.mesh, spec))

    text = grow.lower(
        on(P(None, "data"), learner.bins_dev.shape, learner.bins_dev.dtype),
        on(P("data"), (n, 3), gh_dtype), on(P("data"), (n,), jnp.int32),
        learner._gidx_arg, learner._vslot_arg, learner._scan_meta_arg,
        learner._tables_rep, learner._params_rep,
        on(learner._fmask_spec, (learner.f_pad,), jnp.bool_),
        on(P(), (3,), jnp.float32)).as_text()
    return {
        "plane_shape": list(learner.bins_dev.shape),
        "plane_shards": sorted((str(s.device), list(s.data.shape))
                               for s in shards),
        "collectives": sorted(op for op in ("reduce_scatter", "all_gather",
                                            "all_reduce")
                              if f"stablehlo.{op}" in text),
        "kernels": mosaic_kernels(text),
        "bytes_in_use": device_bytes(learner.mesh.devices.flat,
                                     "bytes_in_use"),
    }


def run_four_chips(seed: int, rows: int) -> None:
    """Every comparison is made and printed before the first one fails the
    run: a four-chip call costs four times a one-chip one."""
    train, valid, hold = split_data(rows, 50_000, 50_000, seed)
    mesh4 = {"tree_learner": "data", "num_machines": 4}
    wrong = []
    for quantized in (True, False):
        params = dict(PARAMS, use_quantized_grad=quantized)
        one, one_rec = timed_train(params, train, valid, 3)
        check_device_run(one_rec)
        four, rec = timed_train(dict(params, **mesh4), train, valid, 3)
        check_device_run(rec, "DeviceDataParallelTreeLearner")
        facts = sharded_facts(four._gbdt.tree_learner)
        p1, p4 = one.predict(hold[0]), four.predict(hold[0])
        differ = tree_difference(one, four)
        emit(phase="sharded", quantized=quantized, chips=4, rows=rec["rows"],
             tree_s=rec["tree_s"], one_chip_tree_s=one_rec["tree_s"],
             auc=auc(hold[1], p4), one_chip_auc=auc(hold[1], p1),
             pred_max_abs_diff=float(np.max(np.abs(p1 - p4))),
             first_tree_difference=differ,
             device_hist_rows=rec["device_hist_rows"], **facts)
        quarter = [facts["plane_shape"][0], facts["plane_shape"][1] // 4]
        if (len(facts["plane_shards"]) != 4
                or any(s != quarter for _, s in facts["plane_shards"])):
            wrong.append(f"plane not split four ways: {facts['plane_shards']}")
        if not {"reduce_scatter", "all_gather"} <= set(facts["collectives"]):
            wrong.append(f"collectives in the program: {facts['collectives']}")
        if not (any("compact" in k for k in facts["kernels"])
                and any("histogram" in k for k in facts["kernels"])):
            wrong.append(f"sharded program's kernels: {facts['kernels']}")
        if quantized:  # integer histograms: the reduction is exact
            if differ or not np.array_equal(p1, p4):
                wrong.append(f"quantized: four chips != one chip ({differ})")
        elif not np.allclose(p4, p1, rtol=RTOL, atol=ATOL):
            wrong.append("float: four-chip predictions out of tolerance")
    for learner, cls in (("voting", "VotingDataParallelTreeLearner"),
                         ("feature", "DeviceFeatureParallelTreeLearner")):
        bst, rec = timed_train(dict(PARAMS, tree_learner=learner,
                                    num_machines=4), train, valid, 1)
        check_device_run(rec, cls)
        pred = bst.predict(hold[0])
        emit(phase=learner, chips=4, rows=rec["rows"], learner=rec["learner"],
             tree_s=rec["tree_s"], auc=auc(hold[1], pred),
             device_hist_rows=rec["device_hist_rows"])
        if not np.isfinite(pred).all():
            wrong.append(f"tree_learner={learner}: predictions not finite")
    if wrong:
        fail("; ".join(wrong))


# ----------------------------------------------------------------- main

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    import lightgbm_tpu  # noqa: F401 - places the compile cache

    devices = jax.devices()  # a backend that cannot start raises here
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        fail(f"JAX found no TPU (default device: {device})")
    if device["count"] != args.chips:
        fail(f"--chips {args.chips} but JAX sees {device['count']} devices")
    entries0 = cache_entries()
    emit(phase="start", device=device, jax=jax.__version__,
         jaxlib=jaxlib.__version__,
         libtpu=importlib.metadata.version("libtpu"),
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         compile_cache_entries=entries0, seed=args.seed)

    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args.seed, ROWS_4CHIPS)
    else:
        run_one_chip(args.seed, ROWS)
    emit(phase="end", seconds=round(time.perf_counter() - t0, 1),
         peak_bytes_in_use=device_bytes(devices, "peak_bytes_in_use"),
         compile_cache_entries_added=cache_entries() - entries0)
    emit(ok=True, device=device)


if __name__ == "__main__":
    main()
