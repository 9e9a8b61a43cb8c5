"""Row sampling strategies: bagging and GOSS.

Counterpart of src/boosting/sample_strategy.{h,cpp} (factory), bagging.hpp
(BaggingSampleStrategy) and goss.hpp (GOSSStrategy). Bagging runs on host
once per iteration; GOSS has two equivalent homes for its |g·h| top-rate
selection:

* host (the original path, and the default off-accelerator): pull the
  gradients, argsort on host, hand the learner a host index bag;
* device (LGBM_TPU_GOSS_DEVICE, default auto = on when the device is a TPU):
  a jitted score + stable-argsort + scatter keeps the gradients and the
  bag membership mask on device — the only host work per iteration is the
  MT19937 position draw, which consumes the generator exactly like the
  host path's `choice(rest, ...)` (both reduce to `permutation(n)[:k]`),
  so the two paths pick bit-identical bags.

Both paths score in f32 with the multiclass per-class terms added in class
order (a fixed association), so the sort keys — and therefore the stable
argsort permutation — match bit for bit.
"""
from __future__ import annotations

import math
import os
from functools import partial
from typing import Optional, Tuple

import numpy as np

from ..config import Config
from ..utils.log import Log
from ..utils.timer import global_timer


class SampleStrategy:
    """Base: no sampling (full data every iteration)."""

    is_use_subset = False
    # whether any iteration of the run grows its tree from a row subset
    samples_rows = False

    def __init__(self, config: Config, num_data: int, metadata,
                 num_tree_per_iteration: int) -> None:
        self.config = config
        self.num_data = num_data
        self.metadata = metadata
        self.num_tree_per_iteration = num_tree_per_iteration

    def bagging(self, iteration: int, grad, hess
                ) -> Tuple[Optional[np.ndarray], object, object]:
        """Returns (bag_indices or None for full data, grad, hess) — the
        gradients are passed through so GOSS can rescale them."""
        return None, grad, hess


class BaggingSampleStrategy(SampleStrategy):
    """bagging_fraction / bagging_freq (+ pos/neg fractions for binary)
    — bagging.hpp:30-296. The bag is resampled every `bagging_freq`
    iterations and reused in between."""

    def __init__(self, config: Config, num_data: int, metadata,
                 num_tree_per_iteration: int) -> None:
        super().__init__(config, num_data, metadata, num_tree_per_iteration)
        self.balanced = (config.pos_bagging_fraction < 1.0
                         or config.neg_bagging_fraction < 1.0)
        self.need = config.bagging_freq > 0 and (
            config.bagging_fraction < 1.0 or self.balanced)
        if self.balanced and config.objective not in ("binary",):
            Log.warning("Only can use pos/neg bagging with binary objective")
            self.balanced = False
            self.need = config.bagging_freq > 0 and config.bagging_fraction < 1.0
        self.samples_rows = self.need
        self._bag: Optional[np.ndarray] = None

    def bagging(self, iteration: int, grad, hess):
        if not self.need:
            return None, grad, hess
        freq = self.config.bagging_freq
        if self._bag is None or iteration % freq == 0:
            rng = np.random.RandomState(self.config.bagging_seed + iteration)
            if self.balanced:
                label = np.asarray(self.metadata.label)
                pos = label > 0
                keep = np.where(
                    pos, rng.rand(self.num_data) < self.config.pos_bagging_fraction,
                    rng.rand(self.num_data) < self.config.neg_bagging_fraction)
                self._bag = np.nonzero(keep)[0].astype(np.int32)
            else:
                cnt = int(round(self.config.bagging_fraction * self.num_data))
                cnt = max(min(cnt, self.num_data), 1)
                self._bag = np.sort(rng.choice(
                    self.num_data, cnt, replace=False)).astype(np.int32)
        return self._bag, grad, hess


class DeviceBag:
    """A bag that lives on device: membership as a bool mask, the count
    known host-side from shapes alone. Consumers that genuinely need host
    indices (the serial learner's RowPartition, the distributed learners)
    materialize them lazily through `.indices` — one pull per bag, outside
    the per-iteration sampling path."""

    def __init__(self, mask, n_bag: int, num_data: int) -> None:
        self.mask = mask  # device bool [num_data]
        self.n_bag = int(n_bag)
        self.num_data = int(num_data)
        self._host: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return self.n_bag

    @property
    def indices(self) -> np.ndarray:
        if self._host is None:
            self._host = np.nonzero(np.asarray(self.mask))[0].astype(np.int32)
        return self._host


def host_bag_indices(bag):
    """Normalize a bag to host int32 indices (identity for host bags)."""
    if isinstance(bag, DeviceBag):
        return bag.indices
    return bag


def use_device_goss() -> bool:
    """LGBM_TPU_GOSS_DEVICE: 1/on forces the device selection, 0/off the
    host path; auto (default) enables it on accelerator backends where the
    per-iteration gradient pull is the cost being removed."""
    mode = os.environ.get("LGBM_TPU_GOSS_DEVICE", "auto").lower()
    if mode in ("0", "false", "off", "host"):
        return False
    if mode in ("1", "true", "on", "device"):
        return True
    from ..utils.backend import on_tpu

    return on_tpu()


def _goss_select(grad, hess, sampled_pos, multiplier, top_k: int):
    """Device half of GOSS: f32 |g·h| score, stable argsort (identical
    permutation to the host np stable sort — stability uniquely determines
    the output for equal keys), top-`top_k` kept, `sampled_pos` indexes the
    REST segment of the order (the host RNG drew positions, not rows), and
    the sampled small-gradient rows are rescaled in place. Returns the
    in-bag mask and the rescaled gradients; nothing touches the host."""
    import jax.numpy as jnp

    if grad.ndim == 1:
        score = jnp.abs(grad * hess)
    else:
        # fixed class-order association — mirrors the host loop bit for bit
        score = jnp.abs(grad[0] * hess[0])
        for c in range(1, grad.shape[0]):
            score = score + jnp.abs(grad[c] * hess[c])
    order = jnp.argsort(-score, stable=True)
    mask = jnp.zeros(score.shape[0], dtype=jnp.bool_)
    mask = mask.at[order[:top_k]].set(True)
    if sampled_pos.shape[0] > 0:
        sampled = order[top_k:][sampled_pos]
        mask = mask.at[sampled].set(True)
        mult = jnp.asarray(multiplier, dtype=jnp.float32)
        if grad.ndim == 1:
            grad = grad.at[sampled].mul(mult)
            hess = hess.at[sampled].mul(mult)
        else:
            grad = grad.at[:, sampled].mul(mult)
            hess = hess.at[:, sampled].mul(mult)
    return mask, grad, hess


class GOSSStrategy(SampleStrategy):
    """Gradient-based One-Side Sampling — goss.hpp:30-172.

    Keeps the top `top_rate` fraction of rows by sum_c |g_c·h_c|, samples
    `other_rate` of the rest, and scales the sampled small-gradient rows'
    grad/hess by (1-top_rate)/other_rate. Inactive during the warm-up
    (iteration < 1/learning_rate, goss.hpp) like the reference.
    """

    samples_rows = True

    def __init__(self, config: Config, num_data: int, metadata,
                 num_tree_per_iteration: int) -> None:
        super().__init__(config, num_data, metadata, num_tree_per_iteration)
        if config.top_rate + config.other_rate > 1.0:
            Log.fatal("The sum of top_rate and other_rate cannot be greater than 1.0")
        if config.top_rate <= 0.0 or config.other_rate <= 0.0:
            # goss.hpp CHECK: both subsample fractions must be positive
            Log.fatal("top_rate and other_rate must be positive in GOSS")
        if config.bagging_freq > 0 and config.bagging_fraction < 1.0:
            Log.warning("Cannot use bagging in GOSS")
        self._select_jit = None

    def _sizes(self) -> Tuple[int, int, int]:
        n = self.num_data
        top_k = max(int(math.ceil(n * self.config.top_rate)), 1)
        other_k = int(math.ceil(n * self.config.other_rate))
        n_rest = n - top_k
        n_sampled = min(other_k, n_rest) if (other_k > 0 and n_rest > 0) else 0
        return top_k, n_rest, n_sampled

    def _bagging_device(self, iteration: int, grad, hess):
        """Device-resident selection: the host draws sample POSITIONS from
        the same MT19937 stream (`choice(n_rest, k)` and the host path's
        `choice(rest, k)` both reduce to `permutation(n_rest)[:k]`), the
        jitted kernel turns them into rows of the device-side order."""
        import jax
        import jax.numpy as jnp

        top_k, n_rest, n_sampled = self._sizes()
        rng = np.random.RandomState(self.config.bagging_seed + iteration)
        if n_sampled > 0:
            pos = rng.choice(n_rest, n_sampled, replace=False)
        else:
            pos = np.empty(0, dtype=np.int64)
        multiplier = (1.0 - self.config.top_rate) / max(
            self.config.other_rate, 1e-12)
        if self._select_jit is None:
            self._select_jit = jax.jit(
                partial(_goss_select, top_k=top_k))
        with global_timer.scope("goss_device_select"):
            mask, grad, hess = self._select_jit(
                grad, hess, jnp.asarray(pos.astype(np.int32)),
                jnp.float32(multiplier))
        return DeviceBag(mask, top_k + n_sampled, self.num_data), grad, hess

    def bagging(self, iteration: int, grad, hess):
        lr = max(self.config.learning_rate, 1e-12)
        if iteration < int(1.0 / lr):
            return None, grad, hess
        if use_device_goss():
            return self._bagging_device(iteration, grad, hess)
        import jax.numpy as jnp

        g = np.asarray(grad, dtype=np.float32)
        h = np.asarray(hess, dtype=np.float32)
        if g.ndim == 1:
            score = np.abs(g * h)
        else:
            # per-class terms added in class order: the same f32 value
            # chain as the device kernel, so the sort keys match bitwise
            score = np.abs(g[0] * h[0])
            for c in range(1, g.shape[0]):
                score = score + np.abs(g[c] * h[c])
        top_k, n_rest, n_sampled = self._sizes()
        order = np.argsort(-score, kind="stable")
        top = order[:top_k]
        rest = order[top_k:]
        rng = np.random.RandomState(self.config.bagging_seed + iteration)
        if n_sampled > 0:
            sampled = rng.choice(rest, n_sampled, replace=False)
        else:
            sampled = np.empty(0, dtype=np.int64)
        multiplier = (1.0 - self.config.top_rate) / max(
            self.config.other_rate, 1e-12)
        if len(sampled) > 0:
            sampled_dev = jnp.asarray(np.sort(sampled).astype(np.int32))
            if g.ndim == 1:
                grad = grad.at[sampled_dev].mul(multiplier)
                hess = hess.at[sampled_dev].mul(multiplier)
            else:
                grad = grad.at[:, sampled_dev].mul(multiplier)
                hess = hess.at[:, sampled_dev].mul(multiplier)
        bag = np.sort(np.concatenate([top, sampled])).astype(np.int32)
        return bag, grad, hess


def create_sample_strategy(config: Config, num_data: int, metadata,
                           num_tree_per_iteration: int) -> SampleStrategy:
    """sample_strategy.cpp:27: data_sample_strategy ∈ {bagging, goss}; the
    legacy boosting=goss spelling is normalized by the config layer."""
    strategy = config.data_sample_strategy
    if strategy == "goss" or config.boosting == "goss":
        return GOSSStrategy(config, num_data, metadata, num_tree_per_iteration)
    if strategy == "bagging":
        return BaggingSampleStrategy(config, num_data, metadata,
                                     num_tree_per_iteration)
    Log.fatal("Unknown data sample strategy: %s", strategy)
