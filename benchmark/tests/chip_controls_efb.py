#!/usr/bin/env python3
"""The controls of the EFB cell, read on the chip at the cell's own size.

    python benchmark/tests/chip_controls_efb.py <variant>[,<variant>...] <seed>

Not run by the benchmark's own runs. Each variant goes through the harness
whole (`harness.run`, a one-second window: the 13,184,290 training rows,
the 1,000,000 test rows and the plain EFB reference are the cell's), with
the timed path changed underneath, one after the other in this process
(every compiled program dropped between two; the table made once), and
prints the numbers compared. The reference follows ONE warm-up tree here,
where the cell's runs follow three: a reading is the worst over the
followed trees, and the first tree is the same tree in both, so a control
that fails on it fails the cell's three-tree comparison too.
tests/test_efb_device.py reads every variant in tier-1, at 30,000 rows on
the CPU:

  sound           the program as the configuration states it
  bf16            (a) the program's own lower-precision path: histogram
                  operands as one bfloat16 limb (the configuration's
                  LGBM_TPU_HIST_F32=1 taken away)
  shifted         (b) every bundle member's bins gathered one slot late
                  from its group's histogram (the width classes' gather
                  index moved by one)
  no_default      (c) a member's default bin not rebuilt from the leaf
                  totals: it reads empty
  conflict_first  (d) a row on which two members of a bundle are
                  non-default keeps the EARLIER member's bin in the plane
                  (the plane has one cell a row and group, so this is the
                  row counted for the member the reference does not count
                  it for; the reference keeps the later one, as LightGBM)
  auc_ties        the AUC program's tied negatives given full credit, not
                  half (what `auc_gap` alone sees)

Each has to fail at least one limit of `traffic/train_window_efb.json`.
"""
import inspect
import json
import os
import sys
import textwrap
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import data_allstate  # noqa: E402
import harness  # noqa: E402

CELL = "allstate_efb.train"
WARMUP = 1  # followed trees a control run

# (module, function, [(source line, planted line), ...]): the same plants
# as tests/test_efb_device.py's CONTROLS
PLANTS = {
    "shifted": ("lightgbm_tpu.ops.split", "width_classes", [(
        "dtype=v.dtype) for k, v in arrays.items()}",
        "dtype=v.dtype) for k, v in arrays.items()}\n"
        "        sub[\"gather_index\"] = jnp.where(\n"
        "            sub[\"gather_index\"] < hist_rows, sub[\"gather_index\"]\n"
        "            + jnp.asarray(arrays[\"efb_omitted\"][ids])[:, None],\n"
        "            sub[\"gather_index\"])")]),
    "no_default": ("lightgbm_tpu.ops.split", "fix_feature_hist", [(
        "add = missing_mass * efb_omitted[:, None]",
        "add = missing_mass * 0")]),
    "conflict_first": ("lightgbm_tpu.io.dataset", "Dataset._bin_group_sparse", [(
        "acc[rows] = np.where(gb != 0, gb, before)",
        "acc[rows] = np.where(before != 0, before, gb)")]),
    "auc_ties": ("lightgbm_tpu.metrics.binary", "_auc_body", [(
        "credit = neg_before_seg + 0.5 * neg_in_seg",
        "credit = neg_before_seg + neg_in_seg")]),
}


# A planted default bin of 0 leaves a one-hot member no split at all, so the
# kind refuses the run (exit 4: no split on a bundle member) before the
# reference reads it; for this variant the split count is taken as met, so
# that the limits are read too (the refusal alone fails the cell as well).
READ_PAST_THE_REFUSAL = {"no_default"}


def past_the_refusal():
    """harness.load_module with the kind's check of efb_splits relaxed;
    returns the function that undoes it."""
    real = harness.load_module

    def load(kind_dir, name, roots):
        module = real(kind_dir, name, roots)
        if name == "train_window_efb":
            check = module.check_efb_path

            def relaxed(ctx, learner, hist_rows, notes, *rest):
                notes = [dict(n, efb_splits=max(1, n.get("efb_splits", 0)))
                         for n in notes]
                return check(ctx, learner, hist_rows, notes, *rest)
            module.check_efb_path = relaxed
        return module

    harness.load_module = load

    def undo():
        harness.load_module = real
    return undo


def one_followed_tree_and_one_table():
    """The traffic read with WARMUP followed trees, and the table made once
    a seed for every variant; returns the function that undoes both."""
    real_load, real_make = harness.load_json, data_allstate.make_allstate_data
    made = {}

    def load(path):
        out = real_load(path)
        return dict(out, warmup_trees=WARMUP) if "warmup_trees" in out \
            else out

    def make(cfg, seed):
        key = (cfg["name"], seed)
        if key not in made:
            made[key] = real_make(cfg, seed)
        return made[key]

    harness.load_json, data_allstate.make_allstate_data = load, make

    def undo():
        harness.load_json = real_load
        data_allstate.make_allstate_data = real_make
    return undo


def plant(variant: str):
    """Plant the variant's fault; returns the function that takes it out."""
    import importlib

    if variant == "sound":
        return lambda: None
    if variant == "bf16":
        real = harness.load_json

        def without_f32(path):
            out = real(path)
            if "env" in out:
                out = dict(out, env=dict(out["env"], LGBM_TPU_HIST_F32="0"))
            return out

        harness.load_json = without_f32

        def undo():
            harness.load_json = real
        return undo
    if variant not in PLANTS:
        raise SystemExit(f"unknown variant {variant!r}")
    modname, name, replacements = PLANTS[variant]
    module = importlib.import_module(modname)
    owner, attr = module, name
    if "." in name:  # a method: planted on its class
        cls, attr = name.split(".")
        owner = getattr(module, cls)
    real = getattr(owner, attr)
    src = textwrap.dedent(inspect.getsource(real))
    for old, new in replacements:
        assert old in src, old
        src = src.replace(old, new)
    namespace = dict(vars(module))
    exec(compile(src, module.__file__, "exec"), namespace)
    setattr(owner, attr, namespace[attr])
    return lambda: setattr(owner, attr, real)


def main(variants: list, seed: int, cell: str = CELL,
         root: str = harness.REPO) -> None:
    import jax

    restore = one_followed_tree_and_one_table()
    for variant in variants:
        undo = plant(variant)
        if variant in READ_PAST_THE_REFUSAL:
            undo_plant, undo_relax = undo, past_the_refusal()

            def undo():
                undo_relax()
                undo_plant()
        jax.clear_caches()
        try:
            line = harness.run(["--root", root, "--workload", cell, "--seed",
                                str(seed), "--seconds", "1", "--trace", "0"])
        except SystemExit as refused:  # the kind's exit 4 fails the cell too
            print("CONTROL", json.dumps({
                "cell": cell, "variant": variant, "seed": seed,
                "correct": False, "refused": refused.code,
                "wall_s": time.perf_counter() - T0}), flush=True)
            continue
        finally:
            undo()
        print("CONTROL", json.dumps({
            "cell": cell, "variant": variant, "seed": seed,
            "correct": line["correct"],
            "failed_limits": sorted(k for k, v in line["compared"].items()
                                    if not v["value"] <= v["limit"]),
            "compared": {k: v["value"] for k, v in line["compared"].items()},
            "device": line["device"]["kind"],
            "memory_peak_bytes": line["device"]["memory_peak_bytes"],
            "wall_s": time.perf_counter() - T0}), flush=True)
    restore()


if __name__ == "__main__":
    main(sys.argv[1].split(","), int(sys.argv[2]))
