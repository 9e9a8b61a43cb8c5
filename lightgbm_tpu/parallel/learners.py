"""Distributed tree learners: data-, feature-, and voting-parallel.

All three reuse the leaf-wise control flow of SerialTreeLearner and override
its device-execution hooks; collectives run inside `jax.shard_map` over the
``data`` mesh axis, replacing the reference's Network::ReduceScatter /
Allreduce stack (src/network/network.cpp:71-331).

Data-parallel (src/treelearner/data_parallel_tree_learner.cpp):
  * rows sharded across devices; a device-resident per-shard leaf-id vector
    replaces index permutation (the CUDADataPartition design, kept local —
    partitioning needs NO communication);
  * per-leaf histograms are built locally then `psum_scatter` distributes
    aggregated FEATURE blocks (the ReduceScatter with feature-block
    assignment of :252-299);
  * each device scans its feature block, then an `all_gather` + argmax picks
    the global best split (SyncUpGlobalBestSplit, parallel_tree_learner.h:209).

Feature-parallel (feature_parallel_tree_learner.cpp): data replicated, only
the split scan is sharded over the feature axis, best split all_gathered.

Voting-parallel (voting_parallel_tree_learner.cpp, PV-Tree): each device
votes its local top-k features from a local scan; the global top-2k by vote
count are the only histogram columns reduced (`psum` of a [2k, Bmax, 3]
gather), decoupling comm volume from the feature count.
"""
from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import Config
from ..io.dataset import Dataset
from ..models.sample_strategy import host_bag_indices
from ..models.tree import Tree
from ..ops.histogram import build_histogram
from ..ops.partition import split_decision_bins, split_decision_bins_cat
from ..ops.quantize import int16_reduction_safe
from ..ops.split import (SplitInfo, gather_feature_hist, pad_cat_words,
                         pad_feature_meta, per_feature_best,
                         per_feature_best_categorical, reduce_best_record,
                         scan_meta_of)
from ..perfmodel import (feature_ici_bytes_per_wave, ici_bytes_per_wave,
                         ici_overlap_pct, voting_ici_bytes_per_wave)
from ..treelearner.device import (DeviceTreeLearner, _PendingTree,
                                  make_sharded_grow_fn)
from ..treelearner.serial import (SerialTreeLearner, _LeafState,
                                  device_growth_applies)
from ..utils import sanitize
from ..utils.log import Log
from ..utils.timer import (SCOPE_FINISH, SCOPE_TREE_SETUP,
                           SPAN_GATHER_LEAF_IDS, SPAN_SHARD_INPUTS,
                           global_timer)
from .dist import (host_value, init_distributed, put_global, put_global_tree,
                   put_replicated)
from .mesh import data_mesh, padded_row_count


def _ceil_to(n: int, d: int) -> int:
    return -(-n // d) * d


class RowLayout(NamedTuple):
    """Where a row-sharded learner keeps a tree's per-row arrays: `n_pad`
    rows in the data set's order, the `n_pad - num_data` pad rows at the
    end, split on the mesh's `data` axis as `rows` says (the sharding of an
    `[n_pad]` vector). A driver whose scores and gradients live in this
    layout hands the learner its rows in place (`train_rows_async`)."""

    num_data: int
    n_pad: int
    rows: NamedSharding


def _better_record(recs: jax.Array, other: jax.Array) -> jax.Array:
    """Row-wise pick the higher-gain record. Each feature is either numerical
    or categorical, so exactly one of the two scans can be finite per row;
    the numerical records are widened by the categorical left-set words."""
    recs = pad_cat_words(recs, other.shape[1] - recs.shape[1])
    return jnp.where((other[:, 0] > recs[:, 0])[:, None], other, recs)


def _make_inbag_count_fn(mesh):
    """jit(shard_map) GLOBAL in-bag row count: psum of each shard's local
    `leaf_id == 0` count. Every dtype decision on the reduction wire (the
    int16 histogram packing) must key off this global count — under skewed
    bagging two shards' LOCAL counts can fall on opposite sides of the
    int16 bound, and shards disagreeing on the wire dtype deadlock or
    garble the psum."""

    def body(leaf_sh):
        return jax.lax.psum((leaf_sh == 0).sum().astype(jnp.int32), "data")

    return jax.jit(shard_map(body, mesh=mesh, in_specs=(P("data"),),
                             out_specs=P(), check_vma=False))


def _make_feature_scan_fn(mesh, f_local, has_cat: bool = False):
    """jit(shard_map) best-split scan over feature blocks: each device scans
    its block (numerical + categorical lanes), offsets local feature indices,
    all_gathers the packed records and reduces to the global best
    (SyncUpGlobalBestSplit, parallel_tree_learner.h:209)."""

    def scan_block(fh_blk, totals, params, scan_meta_sh, mask_sh, constraint):
        recs = per_feature_best(fh_blk, totals, scan_meta_sh, params, mask_sh,
                                constraint)
        if has_cat:
            recs = _better_record(recs, per_feature_best_categorical(
                fh_blk, totals, scan_meta_sh, params, mask_sh, constraint))
        off = (jax.lax.axis_index("data") * f_local).astype(jnp.float32)
        feat = recs[:, 1]
        recs = recs.at[:, 1].set(jnp.where(feat >= 0, feat + off, -1.0))
        all_recs = jax.lax.all_gather(recs, "data", axis=0, tiled=True)
        return reduce_best_record(all_recs)

    return jax.jit(shard_map(
        scan_block, mesh=mesh,
        in_specs=(P("data"), P(), P(), P("data"), P("data"), P()),
        out_specs=P(), check_vma=False))


class LeafIdPartition:
    """Partition view backed by a sharded per-row leaf-id vector.

    Exposes the same indices()/count() surface as ops.partition.RowPartition
    (used by score updates and L1-style leaf refits); index materialization
    pulls the leaf-id vector to host once per tree.
    """

    def __init__(self, learner: "DataParallelTreeLearner") -> None:
        self._learner = learner
        self.counts = {}
        self._host_ids: Optional[np.ndarray] = None

    def count(self, leaf: int) -> int:
        return self.counts[leaf]

    def leaf_ids_dev(self) -> jax.Array:
        """Vectorized score-update fast path (see GBDT._update_train_score)."""
        return self._learner.leaf_id[: self._learner.num_data]

    def indices(self, leaf: int) -> np.ndarray:
        if self._host_ids is None:
            # leaf_ids_dev() is already sliced to the real rows — one pull
            # of exactly num_data ids (the old path pulled the padded
            # vector and sliced on host)
            self._host_ids = np.asarray(self.leaf_ids_dev())
        return np.nonzero(self._host_ids == leaf)[0].astype(np.int32)

    def invalidate(self) -> None:
        self._host_ids = None


class DataParallelTreeLearner(SerialTreeLearner):
    def __init__(self, config: Config, dataset: Dataset) -> None:
        self.mesh = data_mesh(config.num_machines)
        self.D = int(self.mesh.devices.size)
        self.n_pad = _ceil_to(dataset.num_data, self.D)
        super().__init__(config, dataset)
        F = len(self.meta.real_feature)
        self.f_pad = _ceil_to(max(F, self.D), self.D)
        self.f_local = self.f_pad // self.D
        self.meta_pad = pad_feature_meta(self.meta, self.f_pad)
        self.scan_meta_sharded = put_global_tree(
            scan_meta_of(self.meta_pad), self.mesh, P("data"))
        self._row_valid = np.zeros(self.n_pad, dtype=bool)
        self._row_valid[: self.num_data] = True
        self.leaf_id: Optional[jax.Array] = None
        self._inbag_count_fn = _make_inbag_count_fn(self.mesh)
        self._build_step_fns()

    # -------------------------------------------------------- device layout

    def _device_bins(self, dataset: Dataset) -> jax.Array:
        """Rows padded to a multiple of the mesh size and sharded on `data`
        (each device holds its contiguous row block — the pre-partitioned
        load of DatasetLoader::LoadFromFile(rank, num_machines))."""
        bins_pad = np.pad(dataset.bins,
                          ((0, 0), (0, self.n_pad - dataset.num_data)))
        return put_global(bins_pad, self.mesh, P(None, "data"))

    # graftlint: disable=untimed-hot-func -- builder only defines jitted closures; real cost is lazy trace+compile inside the timed train() scopes
    def _build_step_fns(self) -> None:
        mesh = self.mesh
        bpad = self.group_bin_padded
        f_local = self.f_local
        qz = self.quantized
        cd = jnp.int8 if qz else jnp.float32

        def make_fh_block(narrow: bool):
            def fh_block(bins_sh, gh_sh, leaf_id_sh, leaf, meta_full):
                """Local masked histogram -> locally-gathered feature hists ->
                psum_scatter so each device owns an aggregated feature block.
                `narrow` reduces quantized int32 histograms in int16 (half the
                ICI bytes — the int16 reduction of
                data_parallel_tree_learner.cpp:285-297), chosen per leaf when
                leaf_count * num_grad_quant_bins provably fits."""
                mask = leaf_id_sh == leaf
                ghm = jnp.where(mask[:, None], gh_sh,
                                jnp.zeros((), gh_sh.dtype))
                hist = build_histogram(bins_sh, ghm, bpad, compute_dtype=cd)
                local_tot = hist[0].sum(axis=0)
                # EFB FixHistogram runs on local totals: the reconstruction is
                # linear in (hist, totals) so it commutes with the reduction
                fh = gather_feature_hist(hist, meta_full, local_tot)
                if narrow:
                    fh = fh.astype(jnp.int16)
                red = jax.lax.psum_scatter(fh, "data", scatter_dimension=0,
                                           tiled=True)
                return red.astype(jnp.int32) if narrow else red

            return jax.jit(shard_map(
                fh_block, mesh=mesh,
                in_specs=(P(None, "data"), P("data"), P("data"), P(), P()),
                out_specs=P("data")))

        self._fh_block_fn = make_fh_block(False)
        self._fh_block_fn_i16 = make_fh_block(True) if qz else None

        self._scan_fn = _make_feature_scan_fn(
            mesh, f_local, self.meta.has_categorical)

        def totals_fn(gh_sh, leaf_id_sh):
            mask = leaf_id_sh == 0
            vals = jnp.where(mask[:, None], gh_sh, jnp.zeros((), gh_sh.dtype))
            if qz:
                vals = vals.astype(jnp.int32)
            return jax.lax.psum(vals.sum(axis=0), "data")

        self._totals_fn = jax.jit(shard_map(
            totals_fn, mesh=mesh,
            in_specs=(P("data"), P("data")), out_specs=P()))

        def partition_fn(bins_sh, leaf_id_sh, decision, gi, leaf, new_leaf,
                         cat_mask, use_cat):
            gb = jnp.take(bins_sh, gi, axis=0)
            go_left = jnp.where(use_cat,
                                split_decision_bins_cat(gb, decision, cat_mask),
                                split_decision_bins(gb, decision))
            on_leaf = leaf_id_sh == leaf
            new_ids = jnp.where(on_leaf & go_left, leaf,
                                jnp.where(on_leaf, new_leaf, leaf_id_sh))
            left = jax.lax.psum((on_leaf & go_left).sum(), "data")
            return new_ids, left

        self._partition_fn = jax.jit(shard_map(
            partition_fn, mesh=mesh,
            in_specs=(P(None, "data"), P("data"), P(), P(), P(), P(), P(),
                      P()),
            out_specs=(P("data"), P())))

    # ------------------------------------------------------------------ hooks

    def _begin_tree(self, gh_ext: jax.Array,
                    bag_indices: Optional[np.ndarray]) -> None:
        n, npad = self.num_data, self.n_pad
        # sharded learners address rows host-side; a DeviceBag (device
        # GOSS) materializes its indices once here
        bag_indices = host_bag_indices(bag_indices)
        gh_ext = self._prepare_gh(gh_ext)
        gh = jnp.concatenate(
            [gh_ext[:n], jnp.zeros((npad - n, gh_ext.shape[1]), gh_ext.dtype)])
        self._gh_sh = put_global(gh, self.mesh, P("data"))
        in_bag = self._row_valid
        if bag_indices is not None:
            in_bag = np.zeros(npad, dtype=bool)
            in_bag[np.asarray(bag_indices, dtype=np.int64)] = True
            in_bag &= self._row_valid
        ids = np.where(in_bag, 0, -1).astype(np.int32)
        self.leaf_id = put_global(ids, self.mesh, P("data"))
        self.partition = LeafIdPartition(self)
        # root count from the DEVICE psum, not the host-side in_bag.sum():
        # _int16_reduction_safe keys the reduction dtype off counts[0], and
        # a local/per-process bag view here would let shards pick different
        # wire dtypes under skewed bagging (see _make_inbag_count_fn)
        self.partition.counts[0] = int(host_value(
            self._inbag_count_fn(self.leaf_id)))
        # tree-level column sampling (per-node masks would need a transfer
        # per leaf; the distributed learners sample per tree only)
        F = len(self.meta.real_feature)
        mask = np.ones(self.f_pad, dtype=bool)
        if self.col_sampler.active:
            mask[:F] = self.col_sampler.reset_by_tree()
        self._mask_padded = put_global(mask, self.mesh, P("data"))

    def _leaf_hist(self, leaf: int) -> jax.Array:
        fn = self._fh_block_fn
        if self.quantized and self._int16_reduction_safe(leaf):
            fn = self._fh_block_fn_i16
        return fn(self.bins_dev, self._gh_sh, self.leaf_id,
                  jnp.int32(leaf), self.meta_pad)

    def _int16_reduction_safe(self, leaf: int) -> bool:
        """All channel sums (and every ring partial sum) of a leaf's integer
        histogram are bounded by leaf_count * num_grad_quant_bins."""
        count = self.partition.counts.get(leaf, self.num_data)
        return int16_reduction_safe(count, self.config.num_grad_quant_bins)

    def _root_totals(self, root_hist) -> Tuple[float, float, float]:
        tot = host_value(self._totals_fn(self._gh_sh, self.leaf_id))
        if self.quantized:
            s = np.asarray(self._scale_vec)
            return (float(tot[0]) * float(s[0]),
                    float(tot[1]) * float(s[1]), float(tot[2]))
        return (float(tot[0]), float(tot[1]), float(tot[2]))

    def _search_split(self, state: _LeafState, leaf: int) -> SplitInfo:
        rec = self._scan_fn(self._hist_for_scan(state.hist),
                            jnp.asarray(state.totals, dtype=jnp.float32),
                            self.params_dev, self.scan_meta_sharded,
                            self._mask_padded, self._constraint_dev(state))
        return SplitInfo.from_packed(host_value(rec))

    def _constraint_dev(self, state: _LeafState) -> jax.Array:
        return jnp.asarray(state.bounds, dtype=jnp.float32)

    def _partition_split(self, leaf: int, new_leaf: int, gi: int,
                         decision: jax.Array,
                         cat_mask=None) -> Tuple[int, int]:
        use_cat = cat_mask is not None
        if cat_mask is None:  # static-shape placeholder for the jitted fn
            cat_mask = jnp.zeros(self.group_bin_padded, dtype=bool)
        new_ids, left_dev = self._partition_fn(
            self.bins_dev, self.leaf_id, decision, jnp.int32(gi),
            jnp.int32(leaf), jnp.int32(new_leaf), cat_mask,
            jnp.bool_(use_cat))
        self.leaf_id = new_ids
        left = int(host_value(left_dev))
        parent = self.partition.counts[leaf]
        self.partition.counts[leaf] = left
        self.partition.counts[new_leaf] = parent - left
        self.partition.invalidate()
        return left, parent - left

    def _feature_hist_row(self, state: _LeafState,
                          dense_f: int) -> np.ndarray:
        # state.hist is the psum_scatter'd FEATURE-major [f_pad, Bmax, 3]
        # block array: the row IS the aggregated feature histogram
        return host_value(self._hist_for_scan(state.hist)[dense_f])


class FeatureParallelTreeLearner(SerialTreeLearner):
    """Full data on every device; only the split scan is feature-sharded."""

    def __init__(self, config: Config, dataset: Dataset) -> None:
        self.mesh = data_mesh(config.num_machines)
        self.D = int(self.mesh.devices.size)
        super().__init__(config, dataset)
        F = len(self.meta.real_feature)
        self.f_pad = _ceil_to(max(F, self.D), self.D)
        self.f_local = self.f_pad // self.D
        self.meta_pad = pad_feature_meta(self.meta, self.f_pad)
        self.scan_meta_sharded = put_global_tree(
            scan_meta_of(self.meta_pad), self.mesh, P("data"))
        self._scan_fn = _make_feature_scan_fn(self.mesh, self.f_local,
                                              self.meta.has_categorical)
        self._gather_fn = jax.jit(gather_feature_hist)

    def _begin_tree(self, gh_ext, bag_indices) -> None:
        super()._begin_tree(gh_ext, bag_indices)
        F = len(self.meta.real_feature)
        mask = np.ones(self.f_pad, dtype=bool)
        if self._tree_feature_mask is not None:
            mask[:F] = np.asarray(self._tree_feature_mask)
        self._mask_padded = put_global(mask, self.mesh, P("data"))

    def _search_split(self, state: _LeafState, leaf: int) -> SplitInfo:
        totals = jnp.asarray(state.totals, dtype=jnp.float32)
        fh = self._gather_fn(self._hist_for_scan(state.hist), self.meta_pad,
                             totals)
        rec = self._scan_fn(fh, totals, self.params_dev,
                            self.scan_meta_sharded, self._mask_padded,
                            jnp.asarray(state.bounds, dtype=jnp.float32))
        return SplitInfo.from_packed(host_value(rec))


class VotingParallelTreeLearner(DataParallelTreeLearner):
    """PV-Tree: two-phase voting (local top-k -> global top-2k -> reduce only
    the elected columns)."""

    def __init__(self, config: Config, dataset: Dataset) -> None:
        super().__init__(config, dataset)
        F = len(self.meta.real_feature)
        self.k_local = max(1, min(config.top_k, F))
        self.k_global = max(1, min(2 * config.top_k, F))
        # voting replaces the DP psum_scatter hist + feature-block scan with
        # its own local-hist/vote pipeline (only totals/partition are reused)
        self._fh_block_fn = None
        self._scan_fn = None
        self.scan_meta_full = scan_meta_of(self.meta_pad)
        self._build_voting_fns()

    # graftlint: disable=untimed-hot-func -- builder only defines jitted closures; real cost is lazy trace+compile inside the timed train() scopes
    def _build_voting_fns(self) -> None:
        mesh = self.mesh
        bpad = self.group_bin_padded
        k_local, k_global = self.k_local, self.k_global

        def local_hist(bins_sh, gh_sh, leaf_id_sh, leaf):
            mask = leaf_id_sh == leaf
            ghm = jnp.where(mask[:, None], gh_sh, 0.0)
            hist = build_histogram(bins_sh, ghm, bpad)
            return hist[None]  # stacked [1, G, Bpad, 3] per device

        self._local_hist_fn = jax.jit(shard_map(
            local_hist, mesh=mesh,
            in_specs=(P(None, "data"), P("data"), P("data"), P()),
            out_specs=P("data")))

        has_cat = self.meta.has_categorical

        def vote_scan(local_hist_blk, totals, params, meta_full,
                      scan_meta_full, mask_full, constraint):
            lh = local_hist_blk[0]  # this device's [G, Bpad, 3]
            local_tot = lh[0].sum(axis=0)
            fh_local = gather_feature_hist(lh, meta_full, local_tot)
            local_recs = per_feature_best(fh_local, local_tot,
                                          scan_meta_full, params, mask_full,
                                          constraint)
            if has_cat:
                local_recs = _better_record(
                    local_recs, per_feature_best_categorical(
                        fh_local, local_tot, scan_meta_full, params,
                        mask_full, constraint))
            # phase 1: local proposal of top-k features by local gain
            _, topk_idx = jax.lax.top_k(local_recs[:, 0], k_local)
            votes = jax.lax.all_gather(topk_idx, "data", tiled=True)
            counts = jnp.zeros((fh_local.shape[0],), jnp.int32).at[votes].add(1)
            # phase 2: global top-2k by vote count (GlobalVoting,
            # parallel_tree_learner.h:153); replicated + deterministic
            _, selected = jax.lax.top_k(counts, k_global)
            sel_fh = jax.lax.psum(fh_local[selected], "data")  # [K, Bmax, 3]
            sel_meta = jax.tree_util.tree_map(
                lambda a: a[selected], scan_meta_full)
            recs = per_feature_best(sel_fh, totals, sel_meta, params,
                                    None, constraint)
            if has_cat:
                recs = _better_record(recs, per_feature_best_categorical(
                    sel_fh, totals, sel_meta, params, None, constraint))
            valid = recs[:, 1] >= 0
            recs = recs.at[:, 1].set(
                jnp.where(valid, selected.astype(jnp.float32), -1.0))
            return reduce_best_record(recs)

        self._vote_scan_fn = jax.jit(shard_map(
            vote_scan, mesh=mesh,
            in_specs=(P("data"), P(), P(), P(), P(), P(), P()), out_specs=P(),
            check_vma=False))

    def _leaf_hist(self, leaf: int) -> jax.Array:
        return self._local_hist_fn(self.bins_dev, self._gh_sh, self.leaf_id,
                                   jnp.int32(leaf))

    def _feature_hist_row(self, state: _LeafState,
                          dense_f: int) -> np.ndarray:
        from ..ops.split import gather_feature_hist

        agg = self._hist_for_scan(state.hist.sum(axis=0))  # [G, Bpad, 3]
        fh = gather_feature_hist(agg, self.meta_pad,
                                 jnp.asarray(state.totals, jnp.float32))
        return host_value(fh[dense_f])

    def _search_split(self, state: _LeafState, leaf: int) -> SplitInfo:
        mask_full = jnp.ones(self.f_pad, dtype=bool)
        if self.col_sampler.active:
            mask_full = mask_full.at[: len(self.meta.real_feature)].set(
                jnp.asarray(np.asarray(self.col_sampler._tree_mask)))
        rec = self._vote_scan_fn(state.hist,
                                 jnp.asarray(state.totals, dtype=jnp.float32),
                                 self.params_dev, self.meta_pad,
                                 self.scan_meta_full, mask_full,
                                 jnp.asarray(state.bounds, dtype=jnp.float32))
        return SplitInfo.from_packed(host_value(rec))


class DeviceDataParallelTreeLearner(DeviceTreeLearner):
    """tree_learner=data + device growth: the whole-tree wave learner
    sharded data-parallel over the ICI mesh — ONE dispatch per tree across
    every device (see treelearner/device.py make_sharded_grow_fn). The
    host-driven DataParallelTreeLearner below stays the fallback for
    configs the device grower cannot serve (categorical, per-node masks,
    monotone, CEGB, linear trees — device_growth_applies)."""

    # the feature-parallel subclass replicates the rows (and skips the
    # per-shard row padding — the grower pads internally, single-device
    # style); everything else about the dispatch shell is shared
    _replicate_rows = False

    def __init__(self, config: Config, dataset: Dataset) -> None:
        from ..ops.compact_pallas import COMPACT_TILE
        from ..ops.hist_pallas import DEFAULT_TILE_ROWS

        self.mesh = data_mesh(config.num_machines)
        self.D = int(self.mesh.devices.size)
        # every shard must be a multiple of the wave tile unit so the
        # shard_map body needs no per-device re-padding
        self._row_unit = max(DEFAULT_TILE_ROWS, COMPACT_TILE)
        if self._replicate_rows:
            self.n_pad = dataset.num_data
            self._row_spec = P()
        else:
            self.n_pad = padded_row_count(dataset.num_data, self.D,
                                          self._row_unit)
            self._row_spec = P("data")
        self._row_sharding = NamedSharding(self.mesh, self._row_spec)
        super().__init__(config, dataset)
        F = len(self.meta.real_feature)
        self.f_pad = _ceil_to(max(F, self.D), self.D)
        self.f_local = self.f_pad // self.D
        self.meta_pad = pad_feature_meta(self.meta, self.f_pad)
        self.scan_meta_sharded = put_global_tree(
            scan_meta_of(self.meta_pad), self.mesh, P("data"))
        # full-feature raw gather tables ride replicated: every device
        # gathers ALL features locally before the psum_scatter hands it
        # its reduced feature block
        self._gidx_rep = put_replicated(self.meta_pad.gather_index,
                                        self.mesh)
        self._vslot_rep = put_replicated(self.meta_pad.valid_slot, self.mesh)
        self._tables_rep = put_replicated(self.tables, self.mesh)
        self._params_rep = put_replicated(self.params_dev, self.mesh)
        self._grow_fns = {}
        self._inbag_count_fn = (None if self._replicate_rows
                                else _make_inbag_count_fn(self.mesh))
        self._scan_args()

    # --------------------------------------------------- per-mode hooks
    # (overridden by the voting / feature-parallel subclasses below)

    def _scan_args(self) -> None:
        """Placement of the scan tables + the feature-mask spec for this
        mode: data-parallel scans feature-SHARDED blocks after the
        psum_scatter, so scan_meta/mask shard and the raw gather tables
        replicate."""
        self._scan_meta_arg = self.scan_meta_sharded
        self._gidx_arg = self._gidx_rep
        self._vslot_arg = self._vslot_rep
        self._fmask_spec = P("data")

    def _grow_fn_extra(self) -> dict:
        return {}

    def _extra_grow_args(self) -> tuple:
        return ()

    def _note_grow_extras(self, extra: tuple) -> None:
        pass

    def _narrow(self, leaf_sh: jax.Array) -> bool:
        """int16 wire packing decision from the GLOBAL psum'd in-bag count
        (satellite bugfix: a local/per-process bag view can fall on
        opposite sides of the int16 bound under skewed bagging, and shards
        disagreeing on the reduction dtype deadlock or garble the wire).
        The scalar pull only syncs on the quantized path."""
        if not self.quantized:
            return False
        n_g = int(host_value(self._inbag_count_fn(leaf_sh)))
        return int16_reduction_safe(n_g, self.config.num_grad_quant_bins)

    def snapshot_state(self) -> dict:
        st = super().snapshot_state()
        st["n_devices"] = int(self.D)
        return st

    def restore_snapshot_state(self, st: dict) -> None:
        n = int(st.get("n_devices", self.D))
        if n != self.D:
            Log.warning("Checkpoint was captured on a %d-device mesh; "
                        "resuming on %d devices. Committed trees are "
                        "replicated so training stays bit-identical, but "
                        "per-wave comm volume will differ", n, self.D)
        super().restore_snapshot_state(st)

    def _device_bins(self, dataset: Dataset) -> jax.Array:
        """Rows padded to the sharded tile unit and split on `data` (each
        device holds its contiguous row block); same native-width rules as
        the single-device learner. The feature-parallel subclass places
        them replicated instead (n_pad == num_data, so the pad is empty)."""
        bins_pad = np.pad(dataset.bins,
                          ((0, 0), (0, self.n_pad - dataset.num_data)))
        if (bins_pad.dtype.itemsize == 1
                and os.environ.get("LGBM_TPU_BINS_I32", "") == "1"):
            bins_pad = bins_pad.astype(np.int32)
        spec = P() if self._replicate_rows else P(None, "data")
        return put_global(bins_pad, self.mesh, spec)

    def _grow_fn(self, bagged: bool, narrow: bool):
        key = (bagged, narrow)
        if key not in self._grow_fns:
            self._grow_fns[key] = make_sharded_grow_fn(
                self.mesh, num_leaves=self.config.num_leaves,
                num_bins=self.group_bin_padded,
                max_depth=self.config.max_depth, quantized=self.quantized,
                batch=self.wave, bagged=bagged, narrow=narrow,
                **self._grow_fn_extra())
        return self._grow_fns[key]

    def _record_ici_bytes(self, narrow: bool) -> None:
        """Gauge: ICI bytes per wave — the psum_scatter'd [K, F_pad, Bmax,
        CH] raw feature histograms plus the all_gathered [2K, F_pad, REC]
        records. O(K*F*Bmax*CH): independent of the row count
        (docs/PERF_NOTES.md comm-volume model); tests assert the
        N-independence."""
        self._set_ici_bytes_per_wave(ici_bytes_per_wave(
            self.wave_k, self.f_pad, self.meta.max_bins,
            pool_bytes=2 if narrow else 4))

    def _set_ici_bytes_per_wave(self, bytes_w: int) -> None:
        """The gauge, and the learner's own copy of it for the tree's
        `tree_wave` note (a gauge is the process's, not the learner's)."""
        self._ici_bytes_per_wave = int(bytes_w)
        global_timer.set_count("device_ici_bytes_per_wave", bytes_w)

    def row_layout(self) -> Optional[RowLayout]:
        """The layout this learner takes a tree's rows in without moving
        them, or None where it has none to offer: replicated rows (feature-
        parallel), a mesh of several processes (no one process addresses a
        whole per-row array), and quantized training, whose per-tree pack
        and stochastic rounding are drawn over the `[N + 1, 3]` pack."""
        if (self._replicate_rows or self.quantized
                or not self.bins_dev.is_fully_addressable):
            return None
        return RowLayout(self.num_data, self.n_pad, self._row_sharding)

    def _shard_rows(self, gh_ext: jax.Array,
                    bag_indices: Optional[np.ndarray]) -> tuple:
        """A tree's gradients (computed on one chip) and initial leaf ids
        padded to the sharded row count and split on `data`."""
        n, npad = self.num_data, self.n_pad
        gh = gh_ext[:-1]
        if bag_indices is not None:
            in_bag = np.zeros(n, dtype=bool)
            # graftlint: disable=R1 -- bag_indices is a host ndarray from the bagging sampler (see the parameter annotation); asarray only normalizes dtype, nothing crosses the device boundary
            in_bag[np.asarray(bag_indices, dtype=np.int64)] = True
            gh = jnp.where(jnp.asarray(in_bag, dtype=jnp.bool_)[:, None], gh,
                           jnp.zeros((), gh.dtype))
            ids = np.where(in_bag, 0, -1).astype(np.int32)
            n_bag = len(bag_indices)
        else:
            ids = np.zeros(n, dtype=np.int32)
            n_bag = n
        ids_pad = np.full(npad, -1, dtype=np.int32)
        ids_pad[:n] = ids
        gh_pad = jnp.concatenate(
            [gh, jnp.zeros((npad - n, gh.shape[1]), gh.dtype)])
        gh_sh = put_global(gh_pad, self.mesh, self._row_spec)
        leaf_sh = put_global(ids_pad, self.mesh, self._row_spec)
        return gh_sh, leaf_sh, n_bag

    def _tree_constants(self) -> tuple:
        """The feature mask and the quantization scales of one tree, on the
        mesh: bytes, whatever the row count."""
        F = len(self.meta.real_feature)
        mask = np.ones(self.f_pad, dtype=bool)
        if self.col_sampler.active:
            mask[:F] = self.col_sampler.reset_by_tree()
        fmask_sh = put_global(mask, self.mesh, self._fmask_spec)
        scale = (self._scale_vec if self.quantized
                 else jnp.ones(3, jnp.float32))
        return fmask_sh, put_global(scale, self.mesh, P())

    def train_async(self, gh_ext: jax.Array,
                    bag_indices: Optional[np.ndarray] = None) -> _PendingTree:
        bag_indices = host_bag_indices(bag_indices)
        if self.quantized:
            gh_ext = self._prepare_gh(gh_ext)  # int8 rows + scales
        with global_timer.scope(SPAN_SHARD_INPUTS):
            gh_sh, leaf_sh, n_bag = self._shard_rows(gh_ext, bag_indices)
            constants = self._tree_constants()
        return self._grow(gh_sh, leaf_sh, constants, n_bag,
                          bagged=bag_indices is not None,
                          rows_resident=False)

    def train_rows(self, gh_rows: jax.Array) -> Tree:
        return self.finalize(self.train_rows_async(gh_rows))

    def train_rows_async(self, gh_rows: jax.Array) -> _PendingTree:
        """`train_async` for a driver that keeps its rows in `row_layout()`:
        `gh_rows` is the tree's `[n_pad, 3]` pack where the tree reads it
        (pad rows all zero; donated to the tree), the initial leaf ids are
        made on the mesh, and the tree's leaf ids stay where the tree wrote
        them: nothing per-row is padded, moved or pulled. Every tree is
        grown from all rows: there is no bag to hand over."""
        with global_timer.scope(SPAN_SHARD_INPUTS):
            leaf_sh = _root_leaf_ids(self.num_data, self.n_pad,
                                     self._row_sharding)
            constants = self._tree_constants()
        return self._grow(gh_rows, leaf_sh, constants, self.num_data,
                          bagged=False, rows_resident=True)

    def _grow(self, gh_sh: jax.Array, leaf_sh: jax.Array, constants: tuple,
              n_bag: int, bagged: bool, rows_resident: bool) -> _PendingTree:
        fmask_sh, scale_rep = constants
        narrow = self._narrow(leaf_sh)
        self._record_ici_bytes(narrow)
        grow = sanitize.guard(
            self._grow_fn(bagged, narrow), (0, 1, 2),
            "the sharded grow dispatch (parallel/learners.py _grow)")
        with global_timer.scope("tree_device"):
            out = grow(
                jnp.copy(self.bins_dev), gh_sh, leaf_sh, self._gidx_arg,
                self._vslot_arg, self._scan_meta_arg, self._tables_rep,
                self._params_rep, fmask_sh, scale_rep,
                *self._extra_grow_args())
        rec_store, leaf_id, _, hist_rows, n_waves, work_counts = out[:6]
        self._note_grow_extras(out[6:])
        to_host = [rec_store, hist_rows, n_waves, work_counts]
        with global_timer.scope(SPAN_GATHER_LEAF_IDS):
            if not rows_resident:
                leaf_id = self._gather_leaf_ids(leaf_id)
                to_host.append(leaf_id)
            for arr in to_host:
                start = getattr(arr, "copy_to_host_async", None)
                if start is not None:
                    start()
        return _PendingTree(Tree(self.config.num_leaves), rec_store, leaf_id,
                            hist_rows, n_waves, work_counts, n_bag,
                            wave_k=self.wave_k, rows_resident=rows_resident)

    def _gather_leaf_ids(self, leaf_id: jax.Array) -> jax.Array:
        """The tree's per-row leaf ids without the row padding, on the
        mesh's first chip, where the scores and gradients of a run outside
        the row layout live (the score update reads them there). Both
        steps are enqueued behind the tree's program and block nothing. A
        multi-process mesh keeps the sharded array: no one process can
        address it whole, and models/gbdt.py `_colocate` allgathers it."""
        leaf_id = _without_row_padding(leaf_id, self.num_data)
        if leaf_id.is_fully_addressable:
            leaf_id = jax.device_put(leaf_id, self.mesh.devices.flat[0])
        return leaf_id

    def _renew_quantized_leaves_device(self, tree: Tree,
                                       leaf_id: jax.Array) -> None:
        # densify onto one device first: the parent's single scatter-add
        # then sums in the SAME order as the single-device learner
        # (sharded scatter-adds may reorder the f32 accumulation)
        super()._renew_quantized_leaves_device(
            tree, jnp.asarray(np.asarray(leaf_id)))


@partial(jax.jit, static_argnames=("num_data",))
def _without_row_padding(leaf_id: jax.Array, num_data: int) -> jax.Array:
    """The sharded tree's [n_pad] leaf ids cut to the real rows: the slice
    an eager `leaf_id[:n]` dispatches, as one program under the tree's
    finishing scope (the output's placement is the compiler's, as it was)."""
    with jax.named_scope(SCOPE_FINISH):
        return leaf_id[:num_data]


@partial(jax.jit, static_argnames=("num_data", "n_pad", "rows"))
def _root_leaf_ids(num_data: int, n_pad: int, rows: NamedSharding
                   ) -> jax.Array:
    """A full-data tree's initial leaf ids in the row layout, made where
    they are read: 0 on the real rows, -1 on the pad."""
    with jax.named_scope(SCOPE_TREE_SETUP):
        ids = jnp.where(jnp.arange(n_pad, dtype=jnp.int32) < num_data, 0, -1)
        return jax.lax.with_sharding_constraint(ids.astype(jnp.int32), rows)


class VotingDataParallelTreeLearner(DeviceDataParallelTreeLearner):
    """tree_learner=voting + device growth: the whole-tree wave learner
    with PV-Tree two-phase voting (voting_parallel_tree_learner.cpp) on
    the reduction. Rows shard like the data-parallel learner, but every
    device keeps the full LOCAL group-histogram pool and scans ALL
    features locally; a [2K, D*top_k] nomination all_gather elects <=
    2*top_k global candidates per child, and ONLY the elected [Bmax, CH]
    slices are psum'd before a replicated rescan commits the split — per-
    wave ICI volume is O(K * top_k * Bmax), independent of F
    (perfmodel.voting_ici_bytes_per_wave). With top_k >= F every feature
    is elected and the trees are bit-identical to the data-parallel
    learner. LGBM_TPU_VOTING_EXACT_CHECK=1 also runs the full reduction
    and counts committed-split disagreements (voting_miss_total)."""

    def __init__(self, config: Config, dataset: Dataset) -> None:
        super().__init__(config, dataset)
        self._exact_check = os.environ.get(
            "LGBM_TPU_VOTING_EXACT_CHECK", "").lower() in ("1", "true",
                                                           "on")
        self._k_local = max(1, min(int(config.top_k), self.f_pad))
        self._k_global = max(1, min(2 * int(config.top_k), self.f_pad))
        self._pending_miss = []

    def _scan_args(self) -> None:
        # the local scan covers the FULL padded feature axis on every
        # device: scan meta, gather tables and mask all ride replicated
        self._scan_meta_arg = put_replicated(scan_meta_of(self.meta_pad),
                                             self.mesh)
        self._gidx_arg = self._gidx_rep
        self._vslot_arg = self._vslot_rep
        self._fmask_spec = P()

    def _grow_fn_extra(self) -> dict:
        return {"mode": "voting", "top_k": int(self.config.top_k),
                "exact_check": self._exact_check}

    def _extra_grow_args(self) -> tuple:
        from ..utils import faults
        skew = faults.vote_skew_params()
        r, w = skew if skew is not None else (-1, -1)
        return (put_replicated(jnp.int32(r), self.mesh),
                put_replicated(jnp.int32(w), self.mesh))

    def _note_grow_extras(self, extra: tuple) -> None:
        self._pending_miss.append(extra[0])

    def _record_ici_bytes(self, narrow: bool) -> None:
        """Gauge: the nomination all_gather + the ELECTED slice psum only
        — no term scales with F (tests assert F-independence at two
        widths). The smaller-child half of each wave is dispatched before
        the larger-child subtraction it overlaps, so half the wave's ICI
        bytes hide behind local compute by construction."""
        K = self.wave_k
        pool_bytes = 2 if narrow else 4
        bytes_w = voting_ici_bytes_per_wave(
            K, self._k_local, self._k_global, self.meta.max_bins, self.D,
            pool_bytes=pool_bytes)
        self._set_ici_bytes_per_wave(bytes_w)
        global_timer.set_count("voting_ici_bytes_per_wave", bytes_w)
        global_timer.set_count(
            "device_ici_overlap_pct",
            int(ici_overlap_pct(bytes_w // 2, bytes_w)))

    def finalize(self, pending: _PendingTree) -> Tree:
        tree = super().finalize(pending)
        if self._pending_miss:
            from ..utils import faults
            miss = int(host_value(self._pending_miss.pop(0)))
            global_timer.add_count("voting_miss_total", miss)
            faults.check_vote_skew_surfaced(miss, self._exact_check)
        return tree


class DeviceFeatureParallelTreeLearner(DeviceDataParallelTreeLearner):
    """tree_learner=feature + device growth: rows REPLICATED, each device
    owns a disjoint block of the padded feature axis and scans only it;
    the single collective is the [2K, D, REC] best-record all_gather
    (feature_parallel_tree_learner.cpp semantics — comm independent of
    both N and F, the right regime for wide-sparse data). The lowest
    device owns the lowest feature range and reduce_best_record breaks
    ties toward the first record, so the gathered argmax equals the
    serial learner's full-scan argmax."""

    _replicate_rows = True

    def _scan_args(self) -> None:
        # rows replicate; the gather tables + scan meta + mask shard on
        # the feature axis instead
        self._scan_meta_arg = self.scan_meta_sharded
        self._gidx_arg = put_global(self.meta_pad.gather_index, self.mesh,
                                    P("data"))
        self._vslot_arg = put_global(self.meta_pad.valid_slot, self.mesh,
                                     P("data"))
        self._fmask_spec = P("data")

    def _grow_fn_extra(self) -> dict:
        return {"mode": "feature"}

    def _narrow(self, leaf_sh: jax.Array) -> bool:
        # nothing histogram-shaped crosses the wire — no packing decision
        return False

    def _record_ici_bytes(self, narrow: bool) -> None:
        """Gauge: the best-record all_gather is the ONLY collective —
        O(2K*D*REC), independent of N and F (tests assert the
        N-independence)."""
        bytes_w = feature_ici_bytes_per_wave(self.wave_k,
                                             self.D)
        self._set_ici_bytes_per_wave(bytes_w)
        global_timer.set_count("feature_ici_bytes_per_wave", bytes_w)


def _streamed_learner_or_none(learner_type: str, config: Config,
                              dataset: Dataset):
    from ..streaming.learner import streaming_requested

    if not streaming_requested():
        return None
    # LGBM_TPU_HBM_BUDGET + a parallel learner: the plane must stay
    # host-resident, so route to the gang-sharded streamed learner
    # (streaming/sharded.py) instead of the resident device mesh
    if learner_type != "data":
        Log.fatal("LGBM_TPU_HBM_BUDGET streaming supports "
                  "tree_learner=serial or data only (got %s): feature/"
                  "voting learners need the full plane device-resident",
                  learner_type)
    from ..streaming.sharded import ShardedStreamedTreeLearner

    return ShardedStreamedTreeLearner(config, dataset)


def create_parallel_learner(learner_type: str, config: Config,
                            dataset: Dataset):
    from ..treelearner.cegb import CEGB

    # join the multi-host world first when a machine list / env is present,
    # so the mesh below spans every process's devices
    if init_distributed(config) and config.pre_partition:
        Log.warning(
            "pre_partition=true is not yet honored: every process must load "
            "the full dataset (device memory IS stripe-partitioned; host "
            "memory is replicated)")
    if CEGB.enabled(config):
        Log.fatal("cegb_* parameters are not supported with distributed "
                  "tree learners (use tree_learner=serial)")
    streamed = _streamed_learner_or_none(learner_type, config, dataset)
    if streamed is not None:
        return streamed
    # device growth shards the whole-tree wave learner over the mesh (one
    # dispatch per tree); host-driven leaf-wise growth stays the fallback
    # for configs the device grower cannot serve
    on_device = device_growth_applies(getattr(config, "device_type", "cpu"),
                                      config, dataset, sharded=True)
    if (config.use_quantized_grad and learner_type == "voting"
            and not on_device):
        # the DEVICE voting learner reduces raw integer slices exactly
        # like the data-parallel path; only the host-driven PV-Tree
        # fallback keeps the restriction
        Log.fatal("use_quantized_grad is not supported with the host "
                  "tree_learner=voting fallback (use data or feature)")
    if learner_type == "data":
        if on_device:
            return DeviceDataParallelTreeLearner(config, dataset)
        return DataParallelTreeLearner(config, dataset)
    if learner_type == "feature":
        if on_device:
            return DeviceFeatureParallelTreeLearner(config, dataset)
        return FeatureParallelTreeLearner(config, dataset)
    if learner_type == "voting":
        if on_device:
            return VotingDataParallelTreeLearner(config, dataset)
        return VotingParallelTreeLearner(config, dataset)
    Log.fatal("Unknown parallel tree learner type: %s", learner_type)
