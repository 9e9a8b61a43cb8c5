"""Leaf-wise (best-first) tree learner on TPU.

Counterpart of SerialTreeLearner (src/treelearner/serial_tree_learner.cpp:182+)
with the execution structure of the CUDA single-GPU learner
(src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:169-360): the leaf-wise
loop runs on host, each step dispatching three fused device computations —

  1. leaf histogram           (ops/histogram.py — one-hot MXU contraction)
  2. best-split search        (ops/split.py — cumsum + masked argmax)
  3. partition update         (ops/partition.py — stable-sort compaction)

with the histogram-subtraction trick (larger child = parent − smaller,
feature_histogram.hpp:99) and one device→host sync per split (the packed
best-split record), exactly the CUDA learner's sync budget.

Histograms are cached per leaf (the HistogramPool analog — device arrays held
by the frontier map; LRU capping arrives with histogram_pool_size support).
"""
from __future__ import annotations

import os
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import Dataset
from ..models.sample_strategy import host_bag_indices
from ..models.tree import Tree
from ..ops.histogram import build_histogram_rows, subtract_histogram
from ..ops.partition import RowPartition
from ..ops.quantize import quantize_pack
from ..ops.split import (FeatureMeta, SplitInfo, bins_to_bitset,
                         derive_cat_left_bins, find_best_split,
                         make_feature_meta)
from .cegb import CEGB
from .col_sampler import ColSampler
from .. import perfmodel, telemetry
from ..utils.backend import on_tpu
from ..utils.log import Log
from ..utils.timer import SPAN_QUANTIZE, global_timer


@dataclass
class _LeafState:
    hist: Optional[jax.Array]  # [G, B, 3] leaf histogram
    totals: Tuple[float, float, float]  # (sum_g, sum_h, count)
    split: Optional[SplitInfo]
    depth: int
    features_in_path: frozenset = frozenset()  # real indices (interaction constraints)
    # basic-mode monotone output bounds inherited from ancestors
    # (monotone_constraints.hpp BasicLeafConstraints)
    bounds: Tuple[float, float] = (-np.inf, np.inf)


class SerialTreeLearner:
    def __init__(self, config: Config, dataset: Dataset) -> None:
        self.config = config
        self.dataset = dataset
        self.num_data = dataset.num_data
        # device-resident bin matrix (the CUDARowData analog)
        with global_timer.scope("learner_init"):
            self.bins_dev = self._device_bins(dataset)
        self.group_bin_padded = int(max(dataset.group_bin_counts().max(), 2))
        self.meta: FeatureMeta = make_feature_meta(dataset, self.group_bin_padded)
        self.params_dev = jnp.asarray([
            config.lambda_l1, config.lambda_l2,
            float(config.min_data_in_leaf), config.min_sum_hessian_in_leaf,
            config.min_gain_to_split, config.max_delta_step,
            float(config.max_cat_to_onehot), float(config.max_cat_threshold),
            config.cat_l2, config.cat_smooth,
            float(config.min_data_per_group),
        ], dtype=jnp.float32)
        self.partition: Optional[RowPartition] = None
        self.col_sampler = ColSampler(config, self.meta.real_feature)
        self._tree_feature_mask: Optional[jax.Array] = None
        # HistogramPool byte cap (feature_histogram.hpp:1367-1597): when
        # histogram_pool_size (MB) is set, at most `_pool_cap` leaf
        # histograms stay materialized; LRU-evicted ones recompute on demand
        self._pool_cap = 0
        if config.histogram_pool_size > 0:
            hist_bytes = (len(dataset.groups) * self.group_bin_padded * 3 * 4)
            self._pool_cap = max(
                2, int(config.histogram_pool_size * 1024 * 1024 / hist_bytes))
        self._hist_lru: "OrderedDict[int, bool]" = OrderedDict()
        self._has_mc = bool(dataset.monotone_constraints
                            and any(dataset.monotone_constraints))
        if self._has_mc and config.monotone_constraints_method not in (
                "basic",):
            Log.fatal("monotone_constraints_method=%s is not supported "
                      "(only 'basic')", config.monotone_constraints_method)
        self.cegb: Optional[CEGB] = (CEGB(config, dataset)
                                     if CEGB.enabled(config) else None)
        # quantized-gradient training (GradientDiscretizer analog)
        self.quantized = bool(config.use_quantized_grad)
        self._scale_vec: Optional[jax.Array] = None
        self._gh_int: Optional[jax.Array] = None
        if self.quantized:
            self._quant_key = jax.random.PRNGKey(
                int(getattr(config, "data_random_seed", 1)))
        # forcedsplits_filename (SerialTreeLearner::ForceSplits,
        # serial_tree_learner.cpp:627+): nested {"feature","threshold",
        # "left","right"} JSON applied at the top of every tree
        self._forced_json = None
        if config.forcedsplits_filename:
            import json as _json

            try:
                with open(config.forcedsplits_filename) as fh:
                    self._forced_json = _json.load(fh)
            except OSError:
                Log.warning("Could not open forced splits file %s",
                            config.forcedsplits_filename)

    # ------------------------------------------------------------- checkpoint

    def snapshot_state(self) -> dict:
        """Learner state a bit-identical resume needs, split into ndarrays
        (stored raw in the checkpoint sidecar's npz) and scalars (stored in
        the JSON manifest): the column-sampler's MT19937 stream, the
        per-tree quantized-gradient PRNG key, and a structural fingerprint
        (num_data / padded bin count) that restore refuses to cross."""
        kind, keys, pos, has_gauss, cached = self.col_sampler.rng.get_state()
        st = {
            "rng_kind": kind,
            "colsampler_keys": np.asarray(keys, dtype=np.uint32),
            "colsampler_pos": int(pos),
            "colsampler_has_gauss": int(has_gauss),
            "colsampler_cached_gaussian": float(cached),
            "num_data": int(self.num_data),
            "group_bin_padded": int(self.group_bin_padded),
        }
        if self.quantized:
            st["quant_key"] = np.asarray(self._quant_key, dtype=np.uint32)
        return st

    def restore_snapshot_state(self, st: dict) -> None:
        if int(st.get("num_data", self.num_data)) != int(self.num_data) \
                or int(st.get("group_bin_padded", self.group_bin_padded)) \
                != int(self.group_bin_padded):
            Log.fatal("Checkpoint learner state was captured on a different "
                      "dataset shape (num_data=%s, group_bin_padded=%s vs "
                      "%d, %d) — refusing to resume",
                      st.get("num_data"), st.get("group_bin_padded"),
                      self.num_data, self.group_bin_padded)
        self.col_sampler.rng.set_state((
            str(st["rng_kind"]),
            np.asarray(st["colsampler_keys"], dtype=np.uint32),
            int(st["colsampler_pos"]),
            int(st["colsampler_has_gauss"]),
            float(st["colsampler_cached_gaussian"])))
        if self.quantized and "quant_key" in st:
            # plain asarray, NOT device_put: a fresh PRNGKey lives on the
            # default device, and bit-identity requires matching placement
            self._quant_key = jnp.asarray(
                np.asarray(st["quant_key"], dtype=np.uint32),
                dtype=jnp.uint32)

    # ------------------------------------------------------------------ train

    def train(self, gh_ext: jax.Array,
              bag_indices: Optional[np.ndarray] = None) -> Tree:
        """Grow one tree from extended gradients gh_ext [N+1, 3]
        (zero sentinel row at N)."""
        cfg = self.config
        num_leaves = cfg.num_leaves
        tree = Tree(num_leaves, track_branch_features=cfg.linear_tree,
                    is_linear=cfg.linear_tree)
        self._begin_tree(gh_ext, bag_indices)

        frontier: Dict[int, _LeafState] = {}
        with global_timer.scope("hist_root"):
            root_hist = self._leaf_hist(0)
        root_totals = self._root_totals(root_hist)
        frontier[0] = _LeafState(root_hist, root_totals, None, depth=0)
        if not self._force_splits(tree, frontier):
            self._find_split(frontier, 0)

        for _ in range(num_leaves - 1):
            best_leaf, best = None, None
            for leaf, state in frontier.items():
                if state.split is not None and state.split.valid:
                    if best is None or state.split.gain > best.gain:
                        best_leaf, best = leaf, state.split
            if best_leaf is None:
                Log.debug("No further splits with positive gain, best gain: -inf")
                break
            self._apply_split(tree, frontier, best_leaf, best)
            if tree.num_leaves >= num_leaves:
                break

        # leaf outputs: already set by _apply_split; root-only tree handled
        if tree.num_leaves == 1:
            tree.as_constant_tree(0.0)
        elif self.quantized and cfg.quant_train_renew_leaf:
            self._renew_quantized_leaves(tree, frontier)
        self._last_frontier = frontier
        return tree

    def _renew_quantized_leaves(self, tree: Tree,
                                frontier: Dict[int, _LeafState]) -> None:
        """Recompute leaf outputs from the TRUE float gradients, removing
        quantization error (GradientDiscretizer::RenewIntGradTreeOutput,
        gradient_discretizer.cpp:166-233). Unlike the reference (which renews
        unclamped), renewed outputs stay inside the leaf's monotone bounds so
        quantized training keeps the monotonicity guarantee."""
        cfg = self.config
        for leaf in range(tree.num_leaves):
            idx = jnp.asarray(np.asarray(self.partition.indices(leaf)),
                              dtype=jnp.int32)
            gh = jnp.take(self._gh_float, idx, axis=0).sum(axis=0)
            sums = np.asarray(gh)
            out = _leaf_output_host(float(sums[0]), float(sums[1]),
                                    cfg.lambda_l1, cfg.lambda_l2,
                                    cfg.max_delta_step)
            if self._has_mc and leaf in frontier:
                lo, hi = frontier[leaf].bounds
                out = float(np.clip(out, lo, hi))
            tree.set_leaf_output(leaf, out)

    # ------------------------------------------------ device-execution hooks
    # The parallel learners (parallel/learners.py) subclass and override
    # these hooks; the leaf-wise control flow above is shared.

    def _device_bins(self, dataset: Dataset) -> jax.Array:
        """Upload the bin matrix at its native width. uint8 planes (every
        group <= 256 bins, the common case) stay 8-bit end to end — the
        device learner carries and histograms them unwidened. The int32
        escape hatch: LGBM_TPU_BINS_I32=1 forces a wide plane; datasets
        with any group > 256 bins are uint16 host-side already and widen
        automatically downstream."""
        if (dataset.bins.dtype.itemsize == 1
                and os.environ.get("LGBM_TPU_BINS_I32", "") == "1"):
            return jnp.asarray(dataset.bins, dtype=jnp.int32)
        return jnp.asarray(dataset.bins, dtype=dataset.bins.dtype)

    def _prepare_gh(self, gh_ext: jax.Array) -> jax.Array:
        """Quantize the gradient pack when use_quantized_grad is on: int8
        (g, h, 1) rows + a zero sentinel; scales kept for the scan. One
        jitted step a tree (ops/quantize.py `quantize_pack`); the tree's
        pack and scales stay on the learner (`quant_pack`)."""
        if not self.quantized:
            return gh_ext
        self._gh_float = gh_ext  # kept for leaf-output renewal
        with global_timer.scope(SPAN_QUANTIZE):
            self._quant_key, ghq_ext, self._scale_vec = quantize_pack(
                gh_ext, self._quant_key, self.config.num_grad_quant_bins,
                self.config.stochastic_rounding)
        self._gh_int = ghq_ext
        global_timer.add_count("quantized_trees", 1)
        global_timer.add_count("quantized_rows", gh_ext.shape[0] - 1)
        return ghq_ext

    def quant_pack(self) -> Optional[Tuple[jax.Array, jax.Array]]:
        """The current tree's integer pack [N+1, 3] int8 (g_int, h_int, 1;
        zero sentinel row) and its float32 scales [grad_scale, hess_scale,
        1], as the histograms took them: references to the device arrays,
        no copy and no pull. None before a quantized learner's first tree,
        and for a float learner."""
        if not self.quantized or self._gh_int is None:
            return None
        return self._gh_int, self._scale_vec

    def _hist_for_scan(self, hist: jax.Array) -> jax.Array:
        """Integer histograms re-enter float space via the quantization
        scales right before the split scan."""
        if not self.quantized:
            return hist
        scale = self._scale_vec
        # the distributed learners hand over mesh-committed histograms;
        # the per-tree scales come off the default device — replicate them
        # onto the same mesh once so the multiply has one device set
        if (isinstance(hist.sharding, jax.sharding.NamedSharding)
                and scale.sharding.device_set != hist.sharding.device_set):
            scale = jax.device_put(scale, jax.sharding.NamedSharding(
                hist.sharding.mesh, jax.sharding.PartitionSpec()))
            self._scale_vec = scale
        return hist.astype(jnp.float32) * scale

    def _begin_tree(self, gh_ext: jax.Array,
                    bag_indices: Optional[np.ndarray]) -> None:
        self._gh = self._prepare_gh(gh_ext)
        self._hist_lru.clear()
        partition = RowPartition(self.num_data)
        if bag_indices is not None:
            # a DeviceBag (device GOSS) materializes host indices here —
            # the host-driven learner's RowPartition is index-based anyway
            partition.set_used_indices(host_bag_indices(bag_indices))
        self.partition = partition
        if self.col_sampler.active:
            self._tree_feature_mask = jnp.asarray(
                self.col_sampler.reset_by_tree(), dtype=jnp.bool_)
        else:
            self._tree_feature_mask = None

    def _leaf_hist(self, leaf: int) -> jax.Array:
        return build_histogram_rows(
            self.bins_dev, self._gh, self.partition.indices(leaf),
            self.group_bin_padded,
            compute_dtype=jnp.int8 if self.quantized else jnp.float32)

    def _root_totals(self, root_hist: jax.Array) -> Tuple[float, float, float]:
        # any group's bins partition all rows, so group 0's bin-sum = totals
        return tuple(float(x) for x in np.asarray(
            self._hist_for_scan(root_hist)[0].sum(axis=0)))

    def _node_feature_mask(self, state: "_LeafState") -> Optional[jax.Array]:
        cs = self.col_sampler
        if not cs.active:
            return None
        if cs.fraction_bynode < 1.0 or cs.constraints:
            return jnp.asarray(cs.get_by_node(set(state.features_in_path)),
                               dtype=jnp.bool_)
        return self._tree_feature_mask

    def _search_split(self, state: "_LeafState", leaf: int) -> SplitInfo:
        args = (self._hist_for_scan(state.hist),
                jnp.asarray(state.totals, dtype=jnp.float32),
                self.meta, self.params_dev, self._node_feature_mask(state),
                self._constraint_of(state), self._penalty_of(state, leaf))
        if telemetry.enabled():
            # one-time capture of the gain-scan dispatch signature for
            # perfmodel's AOT cost_analysis (dict-check no-op afterwards)
            perfmodel.note_dispatch("scan", find_best_split, *args)
        rec = find_best_split(*args)
        return SplitInfo.from_packed(np.asarray(rec))

    def _constraint_of(self, state: "_LeafState") -> Optional[jax.Array]:
        if not self._has_mc:
            return None
        return jnp.asarray(state.bounds, dtype=jnp.float32)

    def _penalty_of(self, state: "_LeafState",
                    leaf: int) -> Optional[jax.Array]:
        if self.cegb is None:
            return None
        rows = self._leaf_rows(leaf) if self.cegb.needs_rows else None
        return jnp.asarray(
            self.cegb.penalty_vector(state.totals[2], rows),
            dtype=jnp.float32)

    def _leaf_rows(self, leaf: int) -> np.ndarray:
        """Actual (unpadded) row indices of a leaf, for CEGB lazy tracking."""
        rows = np.asarray(self.partition.indices(leaf))
        return rows[rows < self.num_data]

    def _partition_split(self, leaf: int, new_leaf: int, gi: int,
                         decision: jax.Array,
                         cat_mask: Optional[jax.Array] = None
                         ) -> Tuple[int, int]:
        return self.partition.split(leaf, new_leaf, self.bins_dev[gi],
                                    decision, cat_mask)

    def _cat_bin_stats(self, state: "_LeafState", gi: int,
                       dense_f: int) -> np.ndarray:
        """Aggregated histogram row of a winning categorical split's feature
        (categorical features are never EFB-bundled, so the feature's
        histogram row IS its group's). Scaled on device so the host bin-set
        re-derivation replays bit-identical f32 values to the scan."""
        return np.asarray(self._hist_for_scan(state.hist)[gi])

    def _feature_hist_row(self, state: "_LeafState",
                          dense_f: int) -> np.ndarray:
        """One feature's aggregated [Bmax, 3] histogram (forced splits).
        Overridden by the distributed learners, whose state.hist layouts
        differ from the serial group-major [G, Bpad, 3]."""
        from ..ops.split import gather_feature_hist

        return np.asarray(gather_feature_hist(
            self._hist_for_scan(state.hist), self.meta,
            jnp.asarray(state.totals, dtype=jnp.float32))[dense_f])

    # --------------------------------------------------------------- internal

    def _max_depth_ok(self, depth: int) -> bool:
        return self.config.max_depth <= 0 or depth < self.config.max_depth

    def _force_splits(self, tree: Tree, frontier: Dict[int, _LeafState]) -> int:
        """Apply the forced-splits JSON at the top of the tree
        (SerialTreeLearner::ForceSplits, serial_tree_learner.cpp:627+).
        Returns the number of applied splits."""
        if self._forced_json is None:
            return 0
        count = 0
        queue = [(self._forced_json, 0)]
        while queue and tree.num_leaves < self.config.num_leaves:
            jnode, leaf = queue.pop(0)
            split = self._forced_split_info(frontier[leaf], jnode)
            if split is None:
                continue
            new_leaf = tree.num_leaves
            self._apply_split(tree, frontier, leaf, split)
            count += 1
            if isinstance(jnode.get("left"), dict):
                queue.append((jnode["left"], leaf))
            if isinstance(jnode.get("right"), dict):
                queue.append((jnode["right"], new_leaf))
        return count

    # graftlint: disable=untimed-hot-func -- cold path: runs only when forcedsplits_filename is set
    def _forced_split_info(self, state: "_LeafState",
                           jnode) -> Optional[SplitInfo]:
        """Split stats for a forced (feature, threshold) pair, computed from
        the leaf histogram at the forced bin instead of the best-split scan."""
        try:
            real_f = int(jnode["feature"])
            thr = float(jnode["threshold"])
        except (KeyError, TypeError, ValueError):
            return None
        if real_f not in self.meta.real_feature:
            return None
        dense_f = self.meta.real_feature.index(real_f)
        mapper = self.dataset.mappers[real_f]
        if mapper.bin_type == 1:  # categorical forced splits unsupported
            Log.warning("Forced split on categorical feature %d ignored", real_f)
            return None
        fh = self._feature_hist_row(state, dense_f)
        tbin = int(mapper.value_to_bin(thr))
        nb = mapper.num_bin
        has_nan = mapper.missing_type == 2
        # keep at least one real bin right of the threshold; with NaN missing
        # the last bin is the NaN bin, which clamping also keeps on the right
        # (default_left=False)
        if tbin >= nb - (2 if has_nan else 1):
            tbin = nb - (3 if has_nan else 2)
        if tbin < 0:
            return None
        left = fh[: tbin + 1].sum(axis=0)
        tg, th_, tc = state.totals
        lg, lh, lc = float(left[0]), float(left[1]), float(left[2])
        rg, rh, rc = tg - lg, th_ - lh, tc - lc
        cfg = self.config
        if (lc < cfg.min_data_in_leaf or rc < cfg.min_data_in_leaf
                or lh < cfg.min_sum_hessian_in_leaf
                or rh < cfg.min_sum_hessian_in_leaf):
            return None
        lout = _leaf_output_host(lg, lh, cfg.lambda_l1, cfg.lambda_l2,
                                 cfg.max_delta_step)
        rout = _leaf_output_host(rg, rh, cfg.lambda_l1, cfg.lambda_l2,
                                 cfg.max_delta_step)

        def g(sg, sh, out):
            sgl = np.sign(sg) * max(abs(sg) - cfg.lambda_l1, 0.0)
            return -(2.0 * sgl * out + (sh + cfg.lambda_l2) * out * out)

        parent_out = _leaf_output_host(tg, th_, cfg.lambda_l1, cfg.lambda_l2,
                                       cfg.max_delta_step)
        gain = g(lg, lh, lout) + g(rg, rh, rout) - g(tg, th_, parent_out)
        return SplitInfo(gain=float(gain), feature=dense_f, threshold_bin=tbin,
                         default_left=False, left_sum_g=lg, left_sum_h=lh,
                         left_count=int(round(lc)), right_sum_g=rg,
                         right_sum_h=rh, right_count=int(round(rc)),
                         left_output=lout, right_output=rout)

    def _pool_touch(self, frontier: Dict[int, _LeafState], leaf: int) -> None:
        """Materialize an evicted leaf histogram and refresh its LRU slot,
        evicting the coldest leaves past the pool cap."""
        state = frontier[leaf]
        if state.hist is None:
            with global_timer.scope("hist_recompute"):
                state.hist = self._leaf_hist(leaf)
        if not self._pool_cap:
            return
        lru = self._hist_lru
        lru.pop(leaf, None)
        lru[leaf] = True
        while len(lru) > self._pool_cap:
            old, _ = lru.popitem(last=False)
            old_state = frontier.get(old)
            if old_state is not None and old_state.hist is not None:
                old_state.hist = None

    def _find_split(self, frontier: Dict[int, _LeafState], leaf: int) -> None:
        state = frontier[leaf]
        cnt = state.totals[2]
        if (not self._max_depth_ok(state.depth)
                or cnt < 2 * self.config.min_data_in_leaf
                or state.totals[1] < 2 * self.config.min_sum_hessian_in_leaf):
            state.split = SplitInfo()
            return
        self._pool_touch(frontier, leaf)
        with global_timer.scope("find_best_split"):
            state.split = self._search_split(state, leaf)

    def _apply_split(self, tree: Tree, frontier: Dict[int, _LeafState],
                     leaf: int, split: SplitInfo) -> None:
        ds = self.dataset
        meta = self.meta
        dense_f = split.feature
        real_f = meta.real_feature[dense_f]
        mapper = ds.mappers[real_f]
        gi, mi = ds.feature_to_group[real_f]
        fg = ds.groups[gi]
        lo, hi, dbin = fg.feature_bin_range(mi)

        state = frontier[leaf]
        new_leaf = tree.num_leaves
        self._pool_touch(frontier, leaf)  # parent hist needed for subtraction

        # 1. record the split in the tree (real-value threshold / bitset)
        parent_output = _leaf_output_host(
            state.totals[0], state.totals[1],
            self.config.lambda_l1, self.config.lambda_l2,
            self.config.max_delta_step)
        cat_mask = None
        if split.is_categorical:
            bin_stats = self._cat_bin_stats(state, gi, dense_f)
            left_bins = derive_cat_left_bins(
                bin_stats, mapper.num_bin, split, self.config.cat_smooth)
            split.cat_bitset_bins = left_bins
            cat_values = [mapper.bin_2_categorical[b] for b in left_bins
                          if 0 <= b < len(mapper.bin_2_categorical)]
            tree.split_categorical(
                leaf=leaf, feature_inner=dense_f, real_feature=real_f,
                bin_bitset=bins_to_bitset(left_bins),
                value_bitset=bins_to_bitset(cat_values),
                missing_type=mapper.missing_type, gain=split.gain,
                left_value=split.left_output, right_value=split.right_output,
                left_count=split.left_count, right_count=split.right_count,
                left_weight=split.left_sum_h, right_weight=split.right_sum_h,
                parent_value=parent_output)
            mask = np.zeros(self.group_bin_padded, dtype=bool)
            mask[np.asarray(left_bins, dtype=np.int64)] = True
            cat_mask = jnp.asarray(mask, dtype=jnp.bool_)
        else:
            threshold_double = mapper.bin_to_value(split.threshold_bin)
            tree.split(leaf=leaf, feature_inner=dense_f, real_feature=real_f,
                       threshold_bin=split.threshold_bin,
                       threshold_double=threshold_double,
                       default_left=split.default_left,
                       missing_type=mapper.missing_type,
                       gain=split.gain,
                       left_value=split.left_output,
                       right_value=split.right_output,
                       left_count=split.left_count,
                       right_count=split.right_count,
                       left_weight=split.left_sum_h,
                       right_weight=split.right_sum_h,
                       parent_value=parent_output)

        # 2. partition rows (one host sync for the left count)
        decision = jnp.asarray([
            float(split.threshold_bin), 1.0 if split.default_left else 0.0,
            float(mapper.missing_type), float(mapper.default_bin),
            float(mapper.num_bin), float(lo), float(hi),
            1.0 if fg.is_multi else 0.0,
        ], dtype=jnp.float32)
        with global_timer.scope("partition"):
            left_cnt, right_cnt = self._partition_split(
                leaf, new_leaf, gi, decision, cat_mask)
        if left_cnt != split.left_count or right_cnt != split.right_count:
            Log.debug("Partition count mismatch at leaf %d: %d/%d vs %d/%d",
                      leaf, left_cnt, right_cnt, split.left_count, split.right_count)

        # 3. child histograms: construct the smaller, subtract for the larger
        parent_hist = state.hist
        left_totals = (split.left_sum_g, split.left_sum_h, float(left_cnt))
        right_totals = (split.right_sum_g, split.right_sum_h, float(right_cnt))
        with global_timer.scope("hist_children"):
            if left_cnt <= right_cnt:
                small, big = leaf, new_leaf
            else:
                small, big = new_leaf, leaf
            small_hist = self._leaf_hist(small)
            big_hist = subtract_histogram(parent_hist, small_hist)
        depth = state.depth + 1
        child_path = state.features_in_path | {int(real_f)}
        # monotone bound propagation (BasicLeafConstraints::Update,
        # monotone_constraints.hpp:487-503): a numerical split on a monotone
        # feature pins the children's shared boundary at the output midpoint
        lbounds = rbounds = state.bounds
        if self._has_mc and not split.is_categorical:
            mono = (self.dataset.monotone_constraints[real_f]
                    if real_f < len(self.dataset.monotone_constraints) else 0)
            if mono != 0:
                lo, hi_b = state.bounds
                mid = (split.left_output + split.right_output) / 2.0
                if mono > 0:
                    lbounds = (lo, min(hi_b, mid))
                    rbounds = (max(lo, mid), hi_b)
                else:
                    lbounds = (max(lo, mid), hi_b)
                    rbounds = (lo, min(hi_b, mid))
        frontier[leaf] = _LeafState(
            small_hist if small == leaf else big_hist, left_totals, None, depth,
            child_path, lbounds)
        frontier[new_leaf] = _LeafState(
            small_hist if small == new_leaf else big_hist, right_totals, None,
            depth, child_path, rbounds)
        state.hist = None  # release parent histogram
        self._hist_lru.pop(leaf, None)
        self._pool_touch(frontier, leaf)
        self._pool_touch(frontier, new_leaf)
        refresh_frontier = False
        if self.cegb is not None:
            rows = None
            if self.cegb.needs_rows:
                rows = np.concatenate([self._leaf_rows(leaf),
                                       self._leaf_rows(new_leaf)])
            refresh_frontier = self.cegb.on_split_applied(dense_f, rows)
        self._find_split(frontier, leaf)
        self._find_split(frontier, new_leaf)
        if refresh_frontier:
            # a coupled feature penalty was just lifted: refresh the other
            # pending scans so their gains drop the stale coupled penalty
            # (UpdateLeafBestSplits, cost_effective_gradient_boosting.hpp:100)
            for lf in frontier:
                if lf not in (leaf, new_leaf):
                    self._find_split(frontier, lf)


def _leaf_output_host(sum_g: float, sum_h: float, l1: float, l2: float,
                      max_delta: float) -> float:
    num = -np.sign(sum_g) * max(abs(sum_g) - l1, 0.0)
    out = num / max(sum_h + l2, 1e-15)
    if max_delta > 0:
        out = float(np.clip(out, -max_delta, max_delta))
    return float(out)


def device_growth_applies(device_type: str, config: Config,
                          dataset: Dataset) -> bool:
    """Whether the on-device whole-tree wave learner grows this config's
    trees, said out loud when it does not.

    The wave learner trades O(leaf) index gathers for O(N) static-shape
    masked histograms — near-free on the MXU, slow on the CPU backend — so
    it is for a TPU only. device_type=cpu forces the host-driven learner;
    an explicit device_type=tpu on a machine without one is fatal, never a
    quiet host run; unset ("auto": see Config._post_process) chooses, and
    logs why whenever the answer is the host loop. A backend that fails to
    initialise raises out of on_tpu(). Shared by the serial factory below
    and the data-parallel factory (parallel/learners.py), which stacks its
    sharded grower on the same device-growth conditions.
    """
    if device_type == "cpu":
        return False
    if not on_tpu():
        if device_type == "tpu":
            Log.fatal("device_type=tpu was asked for but the default JAX "
                      "device is %r: no TPU is attached to this process "
                      "(device_type=cpu runs the host learner)",
                      jax.devices()[0].platform)
        Log.info("device_type=%s: no TPU attached, growing trees with the "
                 "host-driven learner", device_type)
        return False
    reasons = []
    if any(dataset.mappers[f].bin_type == 1 for f in dataset.used_features):
        reasons.append("categorical features")
    # per-node feature masks / per-leaf bounds and penalties need the
    # host-driven loop for now
    if config.feature_fraction_bynode < 1.0:
        reasons.append("feature_fraction_bynode")
    if config.interaction_constraints:
        reasons.append("interaction_constraints")
    if dataset.monotone_constraints and any(dataset.monotone_constraints):
        reasons.append("monotone_constraints")
    if CEGB.enabled(config):
        reasons.append("cegb_*")
    if config.linear_tree:
        reasons.append("linear_tree")
    if config.forcedsplits_filename:
        reasons.append("forcedsplits_filename")
    if reasons:
        say = Log.warning if device_type == "tpu" else Log.info
        say("device_type=%s: the device learner does not support %s yet, "
            "growing trees with the host-driven learner", device_type,
            ", ".join(reasons))
    return not reasons


def create_tree_learner(learner_type: str, device_type: str, config: Config,
                        dataset: Dataset):
    """Factory (tree_learner.cpp:17-57). Distributed learners (feature/data/
    voting) are built on the parallel backend in parallel/."""
    if learner_type in ("serial",):
        from .device import DeviceTreeLearner
        # out-of-core: an HBM budget (LGBM_TPU_HBM_BUDGET) means the plane
        # must NOT be uploaded whole — the streamed learner takes
        # precedence over device growth (streaming/learner.py)
        from ..streaming.learner import (StreamedTreeLearner,
                                         streaming_requested)

        if streaming_requested():
            return StreamedTreeLearner(config, dataset)
        if device_growth_applies(device_type, config, dataset):
            return DeviceTreeLearner(config, dataset)
        return SerialTreeLearner(config, dataset)
    if learner_type in ("feature", "data", "voting"):
        from ..parallel.learners import create_parallel_learner

        return create_parallel_learner(learner_type, config, dataset)
    Log.fatal("Unknown tree learner type: %s", learner_type)
