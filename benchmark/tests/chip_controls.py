#!/usr/bin/env python3
"""The controls and the faults, read on the chip at the cells' own sizes.

    python benchmark/tests/chip_controls.py train  <variant> <seed> [<seed> ...]
    python benchmark/tests/chip_controls.py predict <seed> [<seed> ...]

Not run by the benchmark's own runs. Each training variant goes through the
harness whole (`harness.run`, a one-second window), with the timed path
changed underneath, and prints the numbers compared:

  sound    the program as the configuration states it
  control  the program's own lower-precision path: histogram operands in
           bfloat16 (the configuration's LGBM_TPU_HIST_F32=1 taken away).
           A process of its own: the operand type is baked into the
           whole-tree program's trace.
  half     half of the batch left out: the second half of the rows carries
           zero gradient, hessian and count into every tree
  altered  an answer altered where it is produced: one leaf's output
           changed by 1 % as each tree is made

`predict` reads the predict cell's control: the reference put in the
program's place with its leaf values and running score in bfloat16, on the
cell's own forest and sample size, against the float64 reference.
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

import harness  # noqa: E402

TRAIN_CELL = "higgs_binary.train"
PREDICT_CELL = "forest500x255.predict_batch"


def plant(variant: str) -> None:
    if variant == "control":
        real = harness.load_json

        def without_f32(path):
            out = real(path)
            if "env" in out:
                out = dict(out, env=dict(out["env"], LGBM_TPU_HIST_F32="0"))
            return out

        harness.load_json = without_f32
    elif variant == "half":
        from lightgbm_tpu.treelearner import device

        real_train = device.DeviceTreeLearner.train_async

        def half(self, gh_ext, bag_indices=None):
            n = self.num_data
            return real_train(self, gh_ext.at[n // 2:n].set(0.0),
                              bag_indices)

        device.DeviceTreeLearner.train_async = half
    elif variant == "altered":
        from lightgbm_tpu.treelearner import device

        real_fin = device.DeviceTreeLearner.finalize

        def altered(self, pending):
            tree = real_fin(self, pending)
            tree.leaf_value[1] *= 1.01
            return tree

        device.DeviceTreeLearner.finalize = altered
    elif variant != "sound":
        raise SystemExit(f"unknown variant {variant!r}")


def train(variant: str, seeds: list) -> None:
    plant(variant)
    for seed in seeds:
        line = harness.run(["--workload", TRAIN_CELL, "--seed", str(seed),
                            "--seconds", "1", "--trace", "0"])
        print("CONTROL", json.dumps({
            "cell": TRAIN_CELL, "variant": variant, "seed": seed,
            "correct": line["correct"],
            "compared": {k: v["value"] for k, v in line["compared"].items()},
            "device": line["device"]["kind"]}), flush=True)


def predict(seeds: list) -> None:
    import ml_dtypes

    import data
    import modeltext
    from reference import forest as reference

    index = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    cell = {w["name"]: w for w in index["workloads"]}[PREDICT_CELL]
    cfg = harness.load_json(os.path.join(
        harness.REPO, {c["name"]: c for c in index["configs"]}[
            cell["config"]]["file"]))
    traffic = harness.load_json(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json"))
    n = int(traffic["sample_rows"]) * int(traffic["check_calls"])
    for seed in seeds:
        trees = modeltext.parse_model(data.make_forest(
            seed, int(cfg["num_trees"]), int(cfg["num_leaves"]),
            int(cfg["features"]), float(cfg["leaf_scale"]), int(cfg["max_depth"])))
        X = data.make_rows(n, int(cfg["features"]), seed)
        want = reference.predict_proba(trees, X)
        low = reference.predict_proba(trees, X, dtype=ml_dtypes.bfloat16)
        print("CONTROL", json.dumps({
            "cell": PREDICT_CELL, "variant": "control", "seed": seed,
            "compared": {"prob_gap": float(np.max(np.abs(low - want)))},
            "rows": n}), flush=True)


if __name__ == "__main__":
    if sys.argv[1] == "train":
        train(sys.argv[2], [int(s) for s in sys.argv[3:]])
    else:
        predict([int(s) for s in sys.argv[2:]])
