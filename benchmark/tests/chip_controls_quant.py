#!/usr/bin/env python3
"""The controls and the faults of the quantized training cell, read on the
chip at the cell's own size.

    python benchmark/tests/chip_controls_quant.py <variant> <seed> [<seed> ...]

Not run by the benchmark's own runs. Each variant goes through the harness
whole (`harness.run`, a one-second window: the 10,500,000 rows, the three
warm-up trees and the plain quantized reference are the cell's), with the
timed path changed underneath, and prints the numbers compared:

  sound    the program as the configuration states it
  float    the program's default float path handed over as quantized: every
           tree is grown from the float gradients (bfloat16 histogram
           operands, float32 sums) while the learner says `quantized` and
           hands over a sound integer pack
  nearest  the discretizer rounds to nearest where the configuration states
           stochastic rounding
  half     half of the batch left out: the second half of the rows carries
           zero gradient and hessian into the discretizer (`chip_controls`')
  altered  an answer altered where it is produced: one leaf's output
           changed by 1 % as each tree is made (`chip_controls`')

and the faults of the layer the cell is there for, the integer histogram,
planted around the histogram kernel inside the whole-tree program (the pack
handed over is sound; the tree is not the integers' tree):

  dropped    every second position's row left out of every histogram (the
             trees come out with empty leaves; ~12 min on the chip)
  saturated  the integer sums saturated at int16's 32,767
  rounded    the gradient and hessian sums rounded to bfloat16's eight bits;
             the counts are left exact (rounded too, they break the
             learner's own ranges: PR 32 read eight limits failed, three of
             them NaN, as under `dropped`)

Each has to fail at least one limit of `traffic/train_window_quant.json`.
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
sys.path.insert(0, HERE)

import chip_controls  # noqa: E402
import harness  # noqa: E402

CELL = "higgs_binary_quant.train"
MANTISSA_BITS = 7  # bfloat16's


def _dropped(real, bins, gh, *rest, **kw):
    return real(bins, gh.at[:, ::2].set(0.0), *rest, **kw)


def _saturated(real, *args, **kw):
    import jax.numpy as jnp

    return jnp.clip(real(*args, **kw), -32767, 32767)


def _rounded(real, *args, **kw):
    """`reduce_precision`, not a pair of `astype`: the TPU compiler takes a
    conversion to bfloat16 and back away (PR 32: the pair read as sound)."""
    import jax
    import jax.numpy as jnp

    hist = real(*args, **kw)  # [G, B, n_slots * CH]: a slot's last counts rows
    low = jax.lax.reduce_precision(
        hist.astype(jnp.float32), exponent_bits=8,
        mantissa_bits=MANTISSA_BITS).astype(hist.dtype)
    channels = args[1].shape[0]
    counts = jnp.arange(hist.shape[-1]) % channels == channels - 1
    return jnp.where(counts, hist, low)


HIST_FAULTS = {"dropped": _dropped, "saturated": _saturated,
               "rounded": _rounded}


def plant(variant: str) -> None:
    from lightgbm_tpu.treelearner import device, serial

    learner = device.DeviceTreeLearner
    if variant == "float":
        real_train = learner.train_async

        def float_tree(self, gh_ext, bag_indices=None):
            self._prepare_gh(gh_ext)  # a sound pack and scales, not used
            self.quantized = False
            try:
                return real_train(self, gh_ext, bag_indices)
            finally:
                self.quantized = True

        learner.train_async = float_tree
    elif variant == "nearest":
        real_pack = serial.quantize_pack

        def nearest(gh_ext, key, num_bins, stochastic):
            return real_pack(gh_ext, key, num_bins, False)

        serial.quantize_pack = nearest
    elif variant in HIST_FAULTS:
        # `_grow_impl` takes the kernel from its module as it is traced
        from lightgbm_tpu.ops import hist_pallas

        real_hist = hist_pallas.pallas_histogram_slots_ragged
        fault = HIST_FAULTS[variant]
        hist_pallas.pallas_histogram_slots_ragged = (
            lambda *args, **kw: fault(real_hist, *args, **kw))
    elif variant in ("half", "altered", "sound"):
        chip_controls.plant(variant)  # the float cell's, unchanged
    else:
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
