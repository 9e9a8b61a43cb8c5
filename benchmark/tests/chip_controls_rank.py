#!/usr/bin/env python3
"""The controls of the lambdarank cell, read on the chip at the cell's own
size.

    python benchmark/tests/chip_controls_rank.py <variant> <seed> [<seed> ...]

Not run by the benchmark's own runs. Each variant goes through the harness
whole (`harness.run`, a one-second window: the 2,270,296 rows in 18,919
queries, both validation sets, the three warm-up trees and the plain
LambdaRank reference are the cell's), with the timed path changed
underneath, and prints the numbers compared (tests/test_rank_reference.py
reads the same controls in tier-1, at 4,685 rows on the CPU):

  sound       the program as the configuration states it
  bf16        the program's own lower-precision path: histogram operands as
              one bfloat16 limb (the configuration's LGBM_TPU_HIST_F32=1
              taken away)
  truncation  the truncation level ignored up to 200 documents: the pair
              block takes a query's 200 best documents where the
              configuration states 30 (maxDCG and the `rank_gradients` note
              stay at 30: the kind would refuse a note that disagrees with
              the data before any number was compared)
  norm        lambdarank_norm dropped
  discount    the discount off by one position: 1 / log2(3 + rank)
  unstable    equal scores ordered from the last row to the first, where
              the source sorts stably
  dropped     the last group block's eight features (groups 128 to 135 of
              the plane) left out of every histogram, planted around the
              histogram kernel inside the whole-tree program

Each has to fail at least one limit of `traffic/train_window_rank.json`.
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

import harness  # noqa: E402

CELL = "mslr_lambdarank.train"
LAST_BLOCK = 128  # the first group of the plane's fifth 32-group block
IGNORED_UP_TO = 200  # documents a query the `truncation` control pairs


def _stated_params(change: dict) -> None:
    """The program trains under `change`; the reference is told what the
    configuration states."""
    import lightgbm_tpu as lgb

    real = lgb.train

    def train(params, *args, **kw):
        return real(dict(params, **change), *args, **kw)

    lgb.train = train


def plant(variant: str) -> None:
    if variant == "bf16":
        real = harness.load_json

        def without_f32(path):
            out = real(path)
            if "env" in out:
                out = dict(out, env=dict(out["env"], LGBM_TPU_HIST_F32="0"))
            return out

        harness.load_json = without_f32
    elif variant == "truncation":
        from lightgbm_tpu.objectives import rank

        real_init = rank.LambdarankNDCG.init

        def init(self, metadata, num_data):
            real_init(self, metadata, num_data)
            self.truncation_level = IGNORED_UP_TO
            self._program = self._build_program()

        rank.LambdarankNDCG.init = init
    elif variant == "norm":
        _stated_params({"lambdarank_norm": False})
    elif variant == "discount":
        import jax.numpy as jnp
        import numpy as np

        from lightgbm_tpu.objectives import rank

        rank.discounts = lambda n: jnp.asarray(
            1.0 / np.log2(np.arange(n) + 3.0), dtype=jnp.float32)
    elif variant == "unstable":
        import jax
        import jax.numpy as jnp

        from lightgbm_tpu.objectives import rank

        def last_first(key, *carried):
            within = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
            key_s, _, *ordered, order = jax.lax.sort(
                (key, -within) + carried + (within,), dimension=1,
                num_keys=2)
            by_rank = jnp.broadcast_to(
                rank.discounts(key.shape[1])[None, :], key.shape)
            _, ranks, disc = jax.lax.sort((order, within, by_rank),
                                          dimension=1, num_keys=1)
            return (key_s, *ordered, ranks, disc)

        rank.rank_documents = last_first
    elif variant == "dropped":
        # `_grow_impl` takes the kernel from its module as it is traced
        from lightgbm_tpu.ops import hist_pallas

        real_hist = hist_pallas.pallas_histogram_slots_ragged
        hist_pallas.pallas_histogram_slots_ragged = (
            lambda *args, **kw: real_hist(*args, **kw).at[LAST_BLOCK:].set(0))
    elif variant != "sound":
        raise SystemExit(f"unknown variant {variant!r}")


def main(variant: str, seeds: list, cell: str = CELL,
         root: str = harness.REPO) -> None:
    plant(variant)
    for seed in seeds:
        line = harness.run(["--root", root, "--workload", cell, "--seed",
                            str(seed), "--seconds", "1", "--trace", "0"])
        print("CONTROL", json.dumps({
            "cell": cell, "variant": variant, "seed": seed,
            "correct": line["correct"],
            "failed_limits": sorted(k for k, v in line["compared"].items()
                                    if not v["value"] <= v["limit"]),
            "compared": {k: v["value"] for k, v in line["compared"].items()},
            "device": line["device"]["kind"],
            "memory_peak_bytes": line["device"]["memory_peak_bytes"],
            "wall_s": time.perf_counter() - T0}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
