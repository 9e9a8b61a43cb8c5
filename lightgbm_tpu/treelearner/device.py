"""Whole-tree-on-device learner: one XLA dispatch per tree.

The host-driven SerialTreeLearner pays per-split dispatch latency (3 calls +
2 blocking scalar pulls), which dominates wall-clock on a remote-attached
TPU. This learner instead grows the ENTIRE tree inside a single jitted
function: a `lax.while_loop` over speculative WAVES carrying the data in a
LEAF-CONTIGUOUS permutation:

    bins_p     [Gp,Np]      bin columns, rows permuted leaf-contiguously
    row_p      [8,Np]       f32 payload rows: gh channels, perm, leaf id
                            (+ zero rows up to the 8-sublane tile)
    start/cnt  [L+1]        per-leaf (start, count) row ranges
    pool       [L+1,G,B,CH] per-leaf histograms (subtraction trick)
    leaf_best  [L+1,R]      per-leaf packed best-split records
    depth      [L+1]        per-leaf depth
    rec_store  [L,R+4]      the split log the host replays into a Tree

Per wave: top-K frontier leaves by gain -> stable 2-way partition of every
selected leaf's range (ops/compact_pallas.py) -> ragged rows-in-leaf
histogram of ONLY the smaller children (ops/hist_pallas.py ragged tiles,
K*CH channels) -> larger children by histogram subtraction from the pool ->
2K split scans -> an on-device replay that commits splits in exact
best-first order until the argmax needs a leaf whose children were not
precomputed. All shapes are static; the only host traffic per TREE is the
split log + final leaf ids (recovered in original row order by one
sort_key_val over the carried permutation).

Design notes:
  * Histogram work per tree is O(rows in selected leaves) ~ <= ~4N, not
    O(N * waves): the wave partitions FIRST (safe even for leaves the
    replay later declines — an internally reordered range is still one
    contiguous range), then histograms only the smaller-child subranges.
  * Row routing (which leaf owns a row, split decision fields, commit
    application) is position-range compares and masked [F,K]@[K,N]
    matmuls — TPU gathers serialize, compares and matmuls vectorize.
  * Every per-row array has the ROWS ON THE MINOR AXIS ([k, Np], never
    [Np, k]): the two Pallas kernels take their per-row operands that way,
    and a custom call's operand layout reaches back into the glue that
    makes it — an [Np, k<128] array pads k to 128 lanes, 2 GB a pass at 4M
    rows and 8 useful values a vector register (ops/compact_pallas.py).
  * The wave replay keeps the reference's leaf-wise semantics bit-exact
    (tree.h best-first; growth stops when the best gain <= 0; masked no-op
    steps write to dump rows so the loop body stays branch-free).
  * The histogram pool this design needs (subtraction trick) is updated
    OUTSIDE the replay fori_loop in one vectorized masked write — per-step
    dynamic pool writes inside the loop defeat XLA's in-place analysis.

Counterpart of SerialTreeLearner::Train + CUDASingleGPUTreeLearner::Train
+ CUDADataPartition::SplitInner (serial_tree_learner.cpp:182,
cuda_single_gpu_tree_learner.cpp:169-360, cuda_data_partition.cu).
"""
from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from ..models.sample_strategy import DeviceBag
from ..models.tree import Tree
from ..ops.compact_pallas import COMPACT_WORK_FIELDS
from ..ops.histogram import build_histogram
from ..ops.split import (SPLIT_FIELDS, ScanMeta, SplitInfo, find_best_split,
                         fix_feature_hist, gather_feature_hist_raw,
                         per_feature_best, reduce_best_record)
from .. import perfmodel, telemetry
from ..utils import sanitize
from ..utils.backend import on_tpu, pallas_interpret
from ..utils.log import Log
from ..utils.timer import (SCOPE_ALLREDUCE, SCOPE_COMMIT, SCOPE_COMPACT,
                           SCOPE_FINISH, SCOPE_HIST, SCOPE_RENEW_LEAVES,
                           SCOPE_REPLAY, SCOPE_ROUTE, SCOPE_SCAN,
                           SCOPE_SELECT, SCOPE_TREE_SETUP, global_timer)
from .serial import SerialTreeLearner, _leaf_output_host

REC = len(SPLIT_FIELDS)
# rec_store row: [leaf, parent_output, depth, valid] + SPLIT_FIELDS
STORE = REC + 4

# Speculative-wave width: a wave partitions and histograms K candidate
# splits, 2*K*3 histogram channels a pass; 2*21*3 = 126 fills one 128-lane
# M tile of the MXU. One width a run: `batch` is a static argument of the
# whole-tree program, so every other width is another program (36-40 s to
# compile on a v5e). A controller that stepped K down with the commit rate
# lost on the chip (PERF.md, PR 29: trees at K = 16 cost 1.18-1.47 s and at
# K = 8 1.34-1.42 s against 1.12-1.37 s at 21, 4.19 M rows) and is gone.
WAVE_K = 21

# What a tree handed its two Mosaic kernels, summed over the tree's calls
# (root pass, initial compaction and every wave): the one int32 vector
# `_grow_impl` carries through the wave loop, and the fields of the tree's
# `tree_wave` note. The (row tile, slot) pairs the histogram kernel walked,
# the distinct tiles among them and the static pair-axis length T + 2K of
# its grid; the compaction kernel's COMPACT_WORK_FIELDS. All zeros where
# the XLA bodies run instead of the kernels.
HIST_WORK_FIELDS = ("hist_tile_visits", "hist_tiles_active",
                    "hist_grid_steps")
WORK_FIELDS = HIST_WORK_FIELDS + COMPACT_WORK_FIELDS


class FeatureTables(NamedTuple):
    """Per-dense-feature decision fields for device-side partitioning."""

    group: jax.Array  # [F] int32 group row in the bin matrix
    lo: jax.Array  # [F] int32 EFB group-bin range
    hi: jax.Array  # [F] int32
    default_bin: jax.Array  # [F] int32
    nbins: jax.Array  # [F] int32
    missing_type: jax.Array  # [F] int32
    is_efb: jax.Array  # [F] bool


def _feature_tables(dataset, used_features) -> FeatureTables:
    F = len(used_features)
    group = np.zeros(F, dtype=np.int32)
    lo = np.zeros(F, dtype=np.int32)
    hi = np.zeros(F, dtype=np.int32)
    db = np.zeros(F, dtype=np.int32)
    nb = np.zeros(F, dtype=np.int32)
    mt = np.zeros(F, dtype=np.int32)
    ie = np.zeros(F, dtype=bool)
    for k, f in enumerate(used_features):
        m = dataset.mappers[f]
        gi, mi = dataset.feature_to_group[f]
        fg = dataset.groups[gi]
        l, h, _ = fg.feature_bin_range(mi)
        group[k], lo[k], hi[k] = gi, l, h
        db[k], nb[k], mt[k] = m.default_bin, m.num_bin, m.missing_type
        ie[k] = fg.is_multi
    return FeatureTables(*(jnp.asarray(a, dtype=a.dtype)
                           for a in (group, lo, hi, db, nb, mt, ie)))


from ..common import MISSING_NAN, MISSING_ZERO  # noqa: E402


def _decide_go_left(gb, thresh, default_left, missing_type, default_bin,
                    nbins, efb_lo, efb_hi, is_efb):
    """NumericalDecisionInner on raw group bins with traced scalar fields
    (the per-node twin of ops.partition.split_decision_bins)."""
    gb = gb.astype(jnp.int32)
    in_range = (gb >= efb_lo) & (gb < efb_hi)
    shifted = gb - efb_lo
    natural = shifted + (shifted >= default_bin).astype(jnp.int32)
    fbin = jnp.where(is_efb, jnp.where(in_range, natural, default_bin), gb)
    is_missing = jnp.where(
        missing_type == MISSING_NAN, fbin == nbins - 1,
        jnp.where(missing_type == MISSING_ZERO, fbin == default_bin, False))
    return jnp.where(is_missing, default_left, fbin <= thresh)


class ShardMeta(NamedTuple):
    """Split-scan metadata for the ICI-sharded growers. Layout depends on
    the comm mode (see make_sharded_grow_fn):

    * mode="data" — gather tables span the FULL padded feature axis
      replicated (every device gathers all features from its local group
      histogram before the psum_scatter hands it a feature block); `scan`
      holds only this device's feature block.
    * mode="voting" — everything spans the FULL padded feature axis
      replicated: local scans nominate over all features and only elected
      slices are reduced.
    * mode="feature" — everything holds only this device's feature block
      (tables arrive feature-sharded; rows are replicated)."""

    gather_index: jax.Array  # [F_pad | f_local, Bmax] int32
    valid_slot: jax.Array  # [F_pad | f_local, Bmax] bool
    scan: ScanMeta  # matching [F_pad | f_local] feature block


# graftlint: disable=untimed-hot-func -- traced only inside the jitted grow_tree_on_device / make_sharded_grow_fn wrappers; every call site runs under the timed tree_device scope
def _grow_impl(bins: jax.Array, gh: jax.Array, leaf_id0: jax.Array,
               meta, tables: FeatureTables, params: jax.Array,
               feature_mask: jax.Array, scale_vec: Optional[jax.Array], *,
               num_leaves: int, num_bins: int, max_depth: int,
               quantized: bool, batch: int, bagged: bool,
               sharded: bool, narrow: bool, mode: str = "data",
               top_k: int = 0, exact_check: bool = False,
               skew: Optional[Tuple[jax.Array, jax.Array]] = None):
    """Shared wave-loop body of the single-device and ICI-sharded growers.

    sharded=False: `meta` is a FeatureMeta and everything is local — the
    body of the public `grow_tree_on_device`.

    sharded=True runs inside a `jax.shard_map` over the "data" mesh axis
    (see make_sharded_grow_fn); `meta` is a ShardMeta and `mode` picks the
    comm scheme:

    mode="data" — bins/gh/leaf_id0 are this device's leaf-contiguous row
    shard, and per wave the ONLY cross-device traffic — all of it
    O(K*F*Bmax*CH), independent of the row count — is
      * a psum of the K per-shard left counts, so the smaller/larger-child
        choice and the subtraction pool key off GLOBAL row counts
        (SyncUpGlobalBestSplit semantics, parallel_tree_learner.h:209);
      * ONE psum_scatter merging the [K, F_pad, Bmax, CH] RAW smaller-child
        feature histograms into per-device feature blocks (int16 when
        `narrow` — the reference's int16 histogram reduction);
      * an all_gather of the [2K, F_pad, REC] per-feature best records
        before the replicated argmax.
    Partition, ragged histograms, and the leaf-id relabel stay 100% local
    (the CUDADataPartition-style local design); the best-first replay
    consumes only replicated values, so every device commits the identical
    tree. The histogram pool turns feature-major ([L+1, f_local, Bmax, CH]
    raw reduced blocks) and is paired with replicated raw leaf totals +
    global leaf counts so subtraction works on already-reduced data.

    mode="voting" — rows sharded like "data", but the histogram pool keeps
    the LOCAL group layout and the full reduction is replaced by PV-Tree
    two-phase voting (voting_parallel_tree_learner.cpp, arxiv 1611.01276):
    each device scans its local feature histograms, nominates its top-k
    features per candidate leaf, one tiny all_gather of the nomination ids
    elects the global top-2k by vote count (deterministic and replicated),
    and ONLY the elected features' raw histogram slices cross the wire via
    a gathered psum before a replicated rescan commits a true global
    argmax over the candidate set. Per-wave ICI volume is
    O(K*(D*k + 2k*Bmax*CH)) — independent of F. The K smaller children
    are nominated/elected/reduced BEFORE the pool subtraction produces the
    K larger children (double-buffered dispatch): the first slice psum is
    in flight while the subtraction runs, which is what the
    `device_ici_overlap_pct` gauge prices. `exact_check` additionally runs
    the full reduction each scan and counts elected-vs-exact best-feature
    disagreements (the `voting_miss_total` counter, returned as a sixth
    output); `skew` is the vote_skew fault hook — (rank, wave) traced
    scalars, -1 to disarm.

    mode="feature" — rows REPLICATED (feature_parallel_tree_learner.cpp):
    every device builds the full local histogram and partitions
    identically; only the split scan is feature-sharded (meta holds this
    device's block) and the single collective per scan is the [2K, D, REC]
    best-record all_gather — O(2K*REC), independent of rows AND features.
    """
    L = num_leaves
    G, N = bins.shape
    CH = gh.shape[1]
    K = max(1, min(batch, L))
    voting = sharded and mode == "voting"
    feature_par = sharded and mode == "feature"
    data_par = sharded and mode == "data"
    # "data" and "voting" shard the rows; "feature" replicates them and
    # shards only the scan
    row_sharded = sharded and not feature_par
    min_data, min_hess = params[2], params[3]
    neg_inf = jnp.float32(-jnp.inf)
    from ..ops.compact_pallas import (COMPACT_TILE, compact_rows,
                                      range_partition_dst)
    from ..ops.hist_pallas import (DEFAULT_TILE_ROWS, hist_force_f32,
                                   pallas_histogram_slots_ragged,
                                   tile_slot_pairs)

    # pad rows ONCE to a common multiple of the histogram and compaction
    # tiles; padded rows carry leaf_id -1 and zero gh and (like bagged-out
    # rows) sit after every leaf range, contributing nothing anywhere
    with jax.named_scope(SCOPE_TREE_SETUP):
        unit = max(DEFAULT_TILE_ROWS, COMPACT_TILE)
        assert unit % COMPACT_TILE == 0 and unit % DEFAULT_TILE_ROWS == 0
        Np = -(-N // unit) * unit
        if Np != N:
            bins = jnp.pad(bins, ((0, 0), (0, Np - N)), constant_values=0)
            gh = jnp.pad(gh, ((0, Np - N), (0, 0)))
            leaf_id0 = jnp.pad(leaf_id0, (0, Np - N), constant_values=-1)
        # 8-bit planes (uint8 bins, every group <= 256 bins) are carried
        # UNWIDENED through the wave loop — 4x less HBM traffic on the dominant
        # [Gp, Np] array, single-limb compaction transport. Mosaic tiles 8-bit
        # as (32, 128), so the group dim pads to 32 instead of 8. Wider planes
        # (uint16 groups, or the LGBM_TPU_BINS_I32 escape hatch upstream)
        # widen to int32 here as before.
        plane8 = bins.dtype.itemsize == 1
        Gp = -(-G // 32) * 32 if plane8 else -(-G // 8) * 8
        bins_p = bins if plane8 else bins.astype(jnp.int32)
        if Gp != G:
            bins_p = jnp.pad(bins_p, ((0, Gp - G), (0, 0)), constant_values=0)
        T_hist = Np // DEFAULT_TILE_ROWS
        # Pallas kernels on a TPU; the XLA bodies (CPU tests) share the
        # forward-map/range logic and differ only in kernel dispatch.
        # LGBM_TPU_PALLAS_INTERPRET=1 runs the TPU kernel path in interpret
        # mode — CPU-runnable end-to-end coverage of the ragged machinery.
        interp = pallas_interpret()
        use_kernels = on_tpu() or interp
        pool_dtype = jnp.int32 if quantized else jnp.float32
        pos = jnp.arange(Np, dtype=jnp.int32)

        # leaf-contiguous payload, one ROW a channel: gh channels, original
        # position, leaf id, all exact in f32 (positions < 2**24, ids < 2**8;
        # quantized int8 gh values are exact too) and moved bit-exactly by the
        # compaction kernel, which stacks the payload's limbs on whole
        # 8-sublane tiles: zero rows fill the last one
        gh_rows = gh.astype(jnp.float32).T  # [CH, Np]: the tree's one relayout
        POS_ROW = CH
        LEAF_ROW = CH + 1
        row_p = jnp.concatenate([
            gh_rows, pos.astype(jnp.float32)[None],
            leaf_id0.astype(jnp.float32)[None],
            jnp.zeros((-(CH + 2) % 8, Np), jnp.float32)])  # [8, Np]

    def scan_hist(hist):
        if quantized:
            return hist.astype(jnp.float32) * scale_vec
        return hist

    def hist_totals(hist):
        if quantized:
            return hist[0].sum(axis=0).astype(jnp.float32) * scale_vec
        return hist[0].sum(axis=0)

    def guard(rec, cnt, sum_h, depth):
        """BeforeFindBestSplit gates (serial_tree_learner.cpp:343)."""
        ok = (cnt >= 2 * min_data) & (sum_h >= 2 * min_hess)
        if max_depth > 0:
            ok &= depth < max_depth
        return rec.at[0].set(jnp.where(ok, rec[0], neg_inf))

    def ranged_hist(bins_c, row_c, slot, n_slots, starts, ends, valid):
        """[G, B, n_slots*CH] histogram of the rows inside the given
        leaf-contiguous ranges, one a slot (slot must be the dump value
        outside), and [3] int32, HIST_WORK_FIELDS of the call: the (tile,
        slot) pairs the kernel walked, the distinct tiles among them and
        the grid's static pair-axis length (zeros off the kernel path).
        bins_c/row_c passed explicitly: inside the wave loop they are the
        CARRY arrays, not the pre-loop closure values."""
        with jax.named_scope(SCOPE_HIST):
            ghc = row_c[:CH]  # the payload's gh rows, f32 [CH, rows]
            if use_kernels:
                tiles, slots, n_pairs, n_active = tile_slot_pairs(
                    starts, ends, valid, T_hist, DEFAULT_TILE_ROWS)
                h = pallas_histogram_slots_ragged(
                    bins_c, ghc, slot, tiles, slots, n_pairs, num_bins,
                    n_slots, quantized=quantized, f32=hist_force_f32(),
                    n_groups=G, interpret=interp)
                return h, jnp.concatenate([
                    n_pairs, n_active,
                    jnp.full(1, tiles.shape[0], jnp.int32)])
            # XLA fallback: flat slot-expanded build over the full row set
            col_slot = jnp.arange(n_slots * CH, dtype=jnp.int32) // CH
            ghK = jnp.where(slot[:, None] == col_slot[None, :],
                            jnp.tile(ghc.T, (1, n_slots)), 0.0)
            h = build_histogram(bins_c[:G], ghK, num_bins)
            # quantized: exact ints below 2**24
            return h.astype(pool_dtype), jnp.zeros(len(HIST_WORK_FIELDS),
                                                   jnp.int32)

    if data_par:
        gidx, vslot, sm = meta.gather_index, meta.valid_slot, meta.scan
        F_pad, Bmax = gidx.shape
        f_local = sm.default_bin.shape[0]
        shard_off = (jax.lax.axis_index("data") * f_local).astype(
            jnp.float32)

        def raw_blocks(hists_k):
            """[k, G, B, CH] raw local group hists -> [k, f_local, Bmax, CH]
            RAW per-device feature blocks via ONE psum_scatter over the
            padded feature axis — the wave's dominant ICI transfer
            (K*F_pad*Bmax*CH values, int16 when `narrow`). The gather is a
            pure selection, so it commutes bit-exactly with the reduction;
            EFB reconstruction and scaling happen AFTER, on reduced blocks
            with global totals, matching the single-device op order."""
            fh = jax.vmap(
                lambda h: gather_feature_hist_raw(h, gidx, vslot))(hists_k)
            if narrow:
                fh = fh.astype(jnp.int16)
            with jax.named_scope(SCOPE_ALLREDUCE):
                blk = jax.lax.psum_scatter(fh, "data", scatter_dimension=1,
                                           tiled=True)
            return blk.astype(pool_dtype)

        def scan_blocks(blk_raw, tot_raw, depths):
            """[k, f_local, Bmax, CH] raw reduced blocks + [k, CH] raw
            GLOBAL totals -> [k, REC] guarded globally-best records:
            scale -> EFB fix -> local per-feature scan -> all_gather +
            argmax (SyncUpGlobalBestSplit) — the sharded twin of
            find_best_split over the same values."""
            if quantized:
                blk = blk_raw.astype(jnp.float32) * scale_vec
                tot = tot_raw.astype(jnp.float32) * scale_vec[None, :]
            else:
                blk, tot = blk_raw, tot_raw
            blk = jax.vmap(
                lambda b, t: fix_feature_hist(b, t, sm.efb_omitted,
                                              sm.default_bin))(blk, tot)
            recs = jax.vmap(
                lambda b, t: per_feature_best(b, t, sm, params,
                                              feature_mask))(blk, tot)
            feat = recs[:, :, 1]
            recs = recs.at[:, :, 1].set(
                jnp.where(feat >= 0, feat + shard_off, -1.0))
            with jax.named_scope(SCOPE_ALLREDUCE):
                recs = jax.lax.all_gather(recs, "data", axis=1, tiled=True)
            best = jax.vmap(reduce_best_record)(recs)
            return jax.vmap(guard)(best, tot[:, 2], tot[:, 1], depths)

    if voting:
        gidx, vslot, sm_full = meta.gather_index, meta.valid_slot, meta.scan
        F_pad, Bmax = gidx.shape
        k_local = max(1, min(top_k, F_pad))
        k_global = max(1, min(2 * top_k, F_pad))

        def _scaled(a):
            if quantized:
                return a.astype(jnp.float32) * scale_vec
            return a

        def _fix_scan(fh, tot):
            """Scaled feature hists + matching totals -> [*, F_pad, REC]
            per-feature records (EFB fix commutes with the reduction, so
            fixing local hists with local totals and reduced hists with
            global totals yields consistent values)."""
            fh = jax.vmap(lambda b, t: fix_feature_hist(
                b, t, sm_full.efb_omitted, sm_full.default_bin))(fh, tot)
            return jax.vmap(lambda b, t: per_feature_best(
                b, t, sm_full, params, feature_mask))(fh, tot)

        def vote_scan(hists_k, tot_raw, depths, wave_no):
            """[k, G, B, CH] raw LOCAL group hists + [k, CH] raw GLOBAL
            totals -> ([k, REC] guarded globally-best records over the
            ELECTED candidate set, disagreement count).

            PV-Tree two-phase voting: local full-F scan -> top-k
            nomination -> all_gather + vote count -> replicated top-2k
            election (jax.lax.top_k ties break to the LOWER index and the
            elected set is sorted, so top_k >= F elects arange(F) and the
            rescan is bit-identical to a full scan) -> psum of ONLY the
            elected raw slices -> replicated rescan."""
            kk = hists_k.shape[0]
            fh_raw = jax.vmap(lambda h: gather_feature_hist_raw(
                h, gidx, vslot))(hists_k)  # [k, F_pad, Bmax, CH] raw local
            loc_tot_raw = hists_k[:, 0].sum(axis=1)  # [k, CH] raw local
            local_recs = _fix_scan(_scaled(fh_raw), _scaled(loc_tot_raw))
            # phase 1 (LocalVoting): nominate the local top-k by local gain
            _, nom = jax.lax.top_k(local_recs[:, :, 0], k_local)  # [k, kl]
            if skew is not None:
                # vote_skew@R:K fault: this rank's nominations are garbage
                # at the armed wave (highest feature ids — the padded/inert
                # tail), modelling a worker whose local scan is corrupted
                hit = ((jax.lax.axis_index("data") == skew[0])
                       & (wave_no == skew[1]))
                garbage = (F_pad - 1 - jnp.arange(k_local, dtype=nom.dtype)
                           ) % F_pad
                nom = jnp.where(hit, jnp.broadcast_to(garbage[None, :],
                                                      nom.shape), nom)
            with jax.named_scope(SCOPE_ALLREDUCE):
                votes = jax.lax.all_gather(nom, "data", axis=1,
                                           tiled=True)  # [k, D*kl]
            counts = jax.vmap(lambda v: jnp.zeros(
                (F_pad,), jnp.int32).at[v].add(1))(votes)
            # phase 2 (GlobalVoting): elect the top-2k by vote count —
            # replicated inputs, deterministic ties, ascending elected ids
            _, selected = jax.lax.top_k(counts, k_global)  # [k, kg]
            selected = jnp.sort(selected, axis=1)
            sel_raw = jnp.take_along_axis(
                fh_raw, selected[:, :, None, None], axis=1)
            if narrow:
                sel_raw = sel_raw.astype(jnp.int16)
            with jax.named_scope(SCOPE_ALLREDUCE):
                sel_red = jax.lax.psum(sel_raw, "data").astype(pool_dtype)
            tot = _scaled(tot_raw)

            def rescan(blk, idx, t):
                m = jax.tree_util.tree_map(lambda a: a[idx], sm_full)
                blk = fix_feature_hist(blk, t, m.efb_omitted, m.default_bin)
                recs = per_feature_best(blk, t, m, params,
                                        feature_mask[idx])
                feat = recs[:, 1]
                gid = idx[jnp.maximum(feat.astype(jnp.int32), 0)].astype(
                    jnp.float32)
                recs = recs.at[:, 1].set(jnp.where(feat >= 0, gid, -1.0))
                return reduce_best_record(recs)

            best = jax.vmap(rescan)(_scaled(sel_red), selected, tot)
            best = jax.vmap(guard)(best, tot[:, 2], tot[:, 1], depths)
            if not exact_check:
                return best, jnp.int32(0)
            # LGBM_TPU_VOTING_EXACT_CHECK=1: also run the full reduction
            # the vote avoided and count best-feature disagreements (the
            # documented approximation: the exact best can be un-nominated)
            full_raw = fh_raw.astype(jnp.int16) if narrow else fh_raw
            with jax.named_scope(SCOPE_ALLREDUCE):
                full = jax.lax.psum(full_raw, "data").astype(pool_dtype)
            frecs = _fix_scan(_scaled(full),
                              jnp.broadcast_to(tot, (kk, CH)))
            fbest = jax.vmap(reduce_best_record)(frecs)
            fbest = jax.vmap(guard)(fbest, tot[:, 2], tot[:, 1], depths)
            miss = jnp.sum(((fbest[:, 0] > 0)
                            & (fbest[:, 1] != best[:, 1])).astype(jnp.int32))
            return best, miss

    if feature_par:
        gidx, vslot, sm = meta.gather_index, meta.valid_slot, meta.scan
        f_local = sm.default_bin.shape[0]
        shard_off = (jax.lax.axis_index("data") * f_local).astype(
            jnp.float32)

        def feature_scan(hists_k, tots, depths):
            """[k, G, B, CH] replicated raw group hists + [k, CH] scaled
            totals -> [k, REC] guarded best records: every device gathers
            and scans its OWN feature block of the full local histogram;
            the only cross-device traffic is the [k, D, REC] best-record
            all_gather (FeatureParallelTreeLearner semantics)."""
            fh = jax.vmap(lambda h: gather_feature_hist_raw(
                scan_hist(h), gidx, vslot))(hists_k)
            fh = jax.vmap(lambda b, t: fix_feature_hist(
                b, t, sm.efb_omitted, sm.default_bin))(fh, tots)
            recs = jax.vmap(lambda b, t: per_feature_best(
                b, t, sm, params, feature_mask))(fh, tots)
            feat = recs[:, :, 1]
            recs = recs.at[:, :, 1].set(
                jnp.where(feat >= 0, feat + shard_off, -1.0))
            best = jax.vmap(reduce_best_record)(recs)  # [k, REC] local
            with jax.named_scope(SCOPE_ALLREDUCE):
                allr = jax.lax.all_gather(best[:, None], "data", axis=1,
                                          tiled=True)  # [k, D, REC]
            best = jax.vmap(reduce_best_record)(allr)
            return jax.vmap(guard)(best, tots[:, 2], tots[:, 1], depths)

    # --- initial compaction: in-bag rows to the front, root = [0, n_in)
    with jax.named_scope(SCOPE_TREE_SETUP):
        # the kernel work of this set-up: its one compaction when bagged
        compact_work = jnp.zeros(len(COMPACT_WORK_FIELDS), jnp.int32)
        if bagged:
            in_bag = leaf_id0 == 0
            n_in = in_bag.sum().astype(jnp.int32)
            # the one-range case of the wave's partition: all rows, in-bag
            # rows left
            whole = (jnp.zeros(1, jnp.int32), jnp.full(1, Np, jnp.int32),
                     jnp.ones(1, bool))
            dst0, _, lefts0 = range_partition_dst(
                in_bag, jnp.ones((1, Np), bool), jnp.ones(Np, bool), *whole,
                COMPACT_TILE)
            bins_p, row_p, compact_work = compact_rows(
                bins_p, row_p, dst0, lefts0, *whole, tile=COMPACT_TILE,
                use_pallas=use_kernels, interpret=interp)
        elif row_sharded:
            # the learner's global row padding trails the real rows, so every
            # shard's real rows are already contiguous from 0 — count, don't
            # compact
            n_in = (leaf_id0 == 0).sum().astype(jnp.int32)
        else:
            n_in = jnp.int32(N)

        start = jnp.zeros(L + 1, jnp.int32)
        count = jnp.zeros(L + 1, jnp.int32).at[0].set(n_in)

    # --- root histogram through the ragged slots kernel (satellite: the
    # thin-CH masked dot cost ~183 ms/tree; this path is O(n_in) and warm)
    root_hist, hist_work = ranged_hist(
        bins_p, row_p, jnp.where(pos < n_in, 0, 1), 1,
        jnp.zeros(1, jnp.int32), n_in[None], jnp.ones(1, bool))
    # instrumentation: rows histogrammed this tree, and what the two
    # kernels were handed for them (WORK_FIELDS)
    hist_rows = n_in
    work_counts = jnp.concatenate([hist_work, compact_work])

    with jax.named_scope(SCOPE_TREE_SETUP):
        depth = jnp.zeros(L + 1, jnp.int32)
        leaf_best = jnp.full((L + 1, REC), neg_inf, jnp.float32)
    with jax.named_scope(SCOPE_SCAN):
        if data_par:
            with jax.named_scope(SCOPE_ALLREDUCE):
                root_tot_raw = jax.lax.psum(root_hist[0].sum(axis=0), "data")
                n_in_g = jax.lax.psum(n_in, "data")
            root_blk = raw_blocks(root_hist[None])[0]
            with jax.named_scope(SCOPE_TREE_SETUP):
                pool = jnp.zeros((L + 1, f_local, Bmax, CH),
                                 pool_dtype).at[0].set(root_blk)
                tpool = jnp.zeros((L + 1, CH), pool_dtype).at[0].set(
                    root_tot_raw)
                count_g = jnp.zeros(L + 1, jnp.int32).at[0].set(n_in_g)
            root_rec = scan_blocks(pool[0][None], root_tot_raw[None],
                                   jnp.zeros(1, jnp.int32))[0]
        elif voting:
            # the pool keeps the LOCAL raw group layout — no feature-blocked
            # histogram crosses the wire until the vote elects its slice
            with jax.named_scope(SCOPE_ALLREDUCE):
                root_tot_raw = jax.lax.psum(root_hist[0].sum(axis=0), "data")
                n_in_g = jax.lax.psum(n_in, "data")
            with jax.named_scope(SCOPE_TREE_SETUP):
                pool = jnp.zeros((L + 1, G, num_bins, CH),
                                 pool_dtype).at[0].set(root_hist)
                tpool = jnp.zeros((L + 1, CH), pool_dtype).at[0].set(
                    root_tot_raw)
                count_g = jnp.zeros(L + 1, jnp.int32).at[0].set(n_in_g)
            root_rec, root_miss = vote_scan(
                root_hist[None].astype(pool_dtype), root_tot_raw[None],
                jnp.zeros(1, jnp.int32), jnp.int32(0))
            root_rec = root_rec[0]
        else:
            root_tot = hist_totals(root_hist)
            with jax.named_scope(SCOPE_TREE_SETUP):
                pool = jnp.zeros((L + 1, G, num_bins, CH),
                                 pool_dtype).at[0].set(root_hist)
            if feature_par:
                root_rec = feature_scan(root_hist[None].astype(pool_dtype),
                                        root_tot[None],
                                        jnp.zeros(1, jnp.int32))[0]
            else:
                root_rec = guard(find_best_split(scan_hist(root_hist), root_tot,
                                                 meta, params, feature_mask),
                                 root_tot[2], root_tot[1], jnp.int32(0))
    with jax.named_scope(SCOPE_TREE_SETUP):
        leaf_best = leaf_best.at[0].set(root_rec)
        # one extra dump row at the end for masked-out replay writes
        rec_store = jnp.zeros((max(L - 1, 1) + 1, STORE), jnp.float32)

    l1, l2, max_delta = params[0], params[1], params[5]

    def wave(carry):
        if voting:
            (bins_p, row_p, start, count, depth, leaf_best, rec_store, pool,
             n_cur, t, hist_rows, work_counts, tpool, count_g, miss,
             n_waves) = carry
        elif data_par:
            (bins_p, row_p, start, count, depth, leaf_best, rec_store, pool,
             n_cur, t, hist_rows, work_counts, tpool, count_g,
             n_waves) = carry
        else:
            (bins_p, row_p, start, count, depth, leaf_best, rec_store, pool,
             n_cur, t, hist_rows, work_counts, n_waves) = carry
        n_waves = n_waves + 1  # wave-efficiency telemetry (finalize())
        with jax.named_scope(SCOPE_SELECT):
            gains = leaf_best[:L, 0]
            sel_gain, sel = jax.lax.top_k(gains, K)  # [K] distinct leaves
            sel = sel.astype(jnp.int32)
            sel_ok = sel_gain > 0

            # --- per-selected-leaf split fields
            recs_sel = leaf_best[sel]  # [K, REC]
            f_k = jnp.maximum(recs_sel[:, 1].astype(jnp.int32), 0)
            thresh_k = recs_sel[:, 2].astype(jnp.int32)
            defl_k = recs_sel[:, 3] > 0.5
            s_k = jnp.take(start, sel)
            c_k = jnp.take(count, sel)
            e_k = s_k + c_k

        with jax.named_scope(SCOPE_ROUTE):
            # --- per-row ownership by POSITION RANGE (leaf-contiguous layout).
            # The [K, N] compare stays VECTORIZED on the VPU; a [L+1]-table
            # gather formulation measured ~20% slower end to end (TPU gathers
            # serialize, elementwise compares do not).
            match = ((pos[None, :] >= s_k[:, None])
                     & (pos[None, :] < e_k[:, None]) & sel_ok[:, None])  # [K, N]
            kvalid = match.any(axis=0)

            # per-row split fields as ONE masked [F,K]@[K,N] matmul over the
            # match matrix — vectorized VPU/MXU work; jnp.take gathers here
            # measured far slower (TPU gathers serialize), and separate
            # per-field matvecs would re-read the [K, N] matrix from HBM many
            # times. Field values are small ints, exact in f32. HIGHEST
            # precision: default TPU matmul rounds operands to bf16 (8 mantissa
            # bits), which would corrupt integer fields > 256 — group ids, new
            # leaf ids, bin offsets, row positions.
            matchf = match.astype(jnp.float32)

            def rows_of(per_k_fields):  # [F, K] -> [F, N]
                return jax.lax.dot(per_k_fields.astype(jnp.float32), matchf,
                                   precision=jax.lax.Precision.HIGHEST)

            fields = jnp.stack([
                tables.group[f_k], thresh_k, defl_k.astype(jnp.int32),
                tables.missing_type[f_k], tables.default_bin[f_k],
                tables.nbins[f_k], tables.lo[f_k], tables.hi[f_k],
                tables.is_efb[f_k].astype(jnp.int32),
            ])  # [9, K]
            rowsF = rows_of(fields)  # [9, N]
            ri = rowsF.astype(jnp.int32)
            grp_row = ri[0]
            # bins[grp_row[n], n] without a gather: compare-select over the G
            # group rows (G*N elementwise beats an N-sized row-varying gather)
            grp_iota = jnp.arange(Gp, dtype=jnp.int32)[:, None]
            gb_row = jnp.sum(
                jnp.where(grp_iota == grp_row[None, :], bins_p, 0),
                axis=0, dtype=jnp.int32)
            go_left = _decide_go_left(
                gb_row, ri[1], rowsF[2] > 0.5, ri[3], ri[4],
                ri[5], ri[6], ri[7], rowsF[8] > 0.5)

        with jax.named_scope(SCOPE_COMPACT):
            # --- stable partition of EVERY selected range (speculative: an
            # uncommitted leaf's range is merely reordered, still contiguous)
            dst, nl_k, lefts = range_partition_dst(
                go_left, match, kvalid, s_k, c_k, sel_ok, COMPACT_TILE)
            bins_p, row_p, compact_work = compact_rows(
                bins_p, row_p, dst, lefts, s_k, c_k, sel_ok,
                tile=COMPACT_TILE, use_pallas=use_kernels, interpret=interp)

        with jax.named_scope(SCOPE_HIST):
            # --- ragged histogram of ONLY the smaller children; tie -> left,
            # matching the serial learner's _apply_split choice
            nr_k = c_k - nl_k
            if row_sharded:
                # smaller/larger child by GLOBAL row counts (psum of the
                # per-shard left counts — SyncUpGlobalBestSplit semantics):
                # every device histograms its LOCAL rows of the globally
                # smaller child, whatever their local count
                with jax.named_scope(SCOPE_ALLREDUCE):
                    nl_g = jax.lax.psum(nl_k, "data")
                c_g = jnp.take(count_g, sel)
                nr_g = c_g - nl_g
                left_small = nl_g <= nr_g
                sc_k = jnp.where(left_small, nl_k, nr_k)
            else:
                left_small = nl_k <= nr_k
                sc_k = jnp.minimum(nl_k, nr_k)
            ss_k = jnp.where(left_small, s_k, s_k + nl_k)
            se_k = ss_k + sc_k
            inS = ((pos[None, :] >= ss_k[:, None])
                   & (pos[None, :] < se_k[:, None]) & sel_ok[:, None])  # [K, N]
            slotS = jnp.where(inS.any(axis=0),
                              jnp.argmax(inS, axis=0).astype(jnp.int32), K)
            hist_rows = hist_rows + jnp.sum(jnp.where(sel_ok, sc_k, 0))
            histS, hist_work = ranged_hist(bins_p, row_p, slotS, K, ss_k,
                                           se_k, sel_ok & (sc_k > 0))
            work_counts = work_counts + jnp.concatenate(
                [hist_work, compact_work])
            histS_k = jnp.moveaxis(
                histS.reshape(G, num_bins, K, CH), 2, 0)  # [K, G, B, CH]
        with jax.named_scope(SCOPE_SCAN):
            child_depth = depth[sel] + 1  # [K]
            depth2 = jnp.repeat(child_depth, 2)  # [2K]
            if data_par:
                # global raw totals of the smaller children, then ONE
                # psum_scatter merges the raw gathered feature hists into this
                # device's reduced block; subtraction happens on reduced data
                with jax.named_scope(SCOPE_ALLREDUCE):
                    totS_raw = jax.lax.psum(histS_k[:, 0].sum(axis=1), "data")
                blkS = raw_blocks(histS_k)  # [K, f_local, Bmax, CH]
                pool_sel = jnp.take(pool, sel, axis=0)
                tp_sel = jnp.take(tpool, sel, axis=0)  # [K, CH]
                histL = jnp.where(left_small[:, None, None, None], blkS,
                                  pool_sel - blkS)
                histR = pool_sel - histL  # subtract_histogram, on blocks
                totL_raw = jnp.where(left_small[:, None], totS_raw,
                                     tp_sel - totS_raw)
                totR_raw = tp_sel - totL_raw
                hists = jnp.stack([histL, histR], axis=1).reshape(
                    2 * K, f_local, Bmax, CH)
                tot2_raw = jnp.stack([totL_raw, totR_raw], axis=1).reshape(
                    2 * K, CH)
                totals = tot2_raw
                if quantized:
                    totals = totals.astype(jnp.float32) * scale_vec[None, :]
                recs2 = scan_blocks(hists, tot2_raw, depth2)
            elif voting:
                # double-buffered dispatch: elect + reduce the SMALLER children
                # first, so their nomination gather and elected-slice psum are
                # in flight while the larger-child subtraction runs on local
                # data — the overlapped half of the wave's ICI traffic
                # (device_ici_overlap_pct)
                with jax.named_scope(SCOPE_ALLREDUCE):
                    totS_raw = jax.lax.psum(histS_k[:, 0].sum(axis=1), "data")
                histSblk = histS_k.astype(pool_dtype)
                recsS, missS = vote_scan(histSblk, totS_raw, child_depth,
                                         n_waves)
                pool_sel = jnp.take(pool, sel, axis=0)  # [K, G, B, CH] local
                tp_sel = jnp.take(tpool, sel, axis=0)  # [K, CH] global raw
                histB = pool_sel - histSblk  # the bigger sibling, local raw
                totB_raw = tp_sel - totS_raw
                recsB, missB = vote_scan(histB, totB_raw, child_depth, n_waves)
                miss = miss + missS + missB
                histL = jnp.where(left_small[:, None, None, None], histSblk,
                                  histB)
                histR = pool_sel - histL
                totL_raw = jnp.where(left_small[:, None], totS_raw, totB_raw)
                totR_raw = tp_sel - totL_raw
                recsL = jnp.where(left_small[:, None], recsS, recsB)
                recsR = jnp.where(left_small[:, None], recsB, recsS)
                recs2 = jnp.stack([recsL, recsR], axis=1).reshape(2 * K, REC)
                tot2_raw = jnp.stack([totL_raw, totR_raw], axis=1).reshape(
                    2 * K, CH)
                totals = tot2_raw
                if quantized:
                    totals = totals.astype(jnp.float32) * scale_vec[None, :]
            else:
                pool_sel = jnp.take(pool, sel, axis=0)  # [K, G, B, CH]
                histL = jnp.where(left_small[:, None, None, None], histS_k,
                                  pool_sel - histS_k)
                histR = pool_sel - histL  # subtract_histogram, vectorized
                hists = jnp.stack([histL, histR], axis=1).reshape(
                    2 * K, G, num_bins, CH)
                totals = hists[:, 0].sum(axis=1)  # bins-summed -> [2K, CH]
                if quantized:
                    totals = totals.astype(jnp.float32) * scale_vec[None, :]
                if feature_par:
                    recs2 = feature_scan(hists, totals, depth2)
                else:
                    recs2 = jax.vmap(
                        lambda h, tot: find_best_split(scan_hist(h), tot, meta,
                                                       params, feature_mask))(
                        hists, totals)
                    recs2 = jax.vmap(guard)(recs2, totals[:, 2], totals[:, 1],
                                            depth2)

        with jax.named_scope(SCOPE_REPLAY):
            # --- exact best-first replay over the precomputed set
            def replay_step(_, rp):
                (leaf_best, depth, rec_store, n_cur, t, committed, newids,
                 active) = rp
                cur = leaf_best[:L, 0]
                b = jnp.argmax(cur).astype(jnp.int32)
                brec = leaf_best[b]
                eq = (sel == b) & sel_ok
                pos = jnp.argmax(eq).astype(jnp.int32)
                # ~committed[pos]: a left child reuses its parent's leaf id; its
                # slot holds the PARENT's children — never commit it twice.
                # t < L-1: the leaf budget binds mid-wave too.
                can = (active & (brec[0] > 0) & eq.any() & ~committed[pos]
                       & (t < L - 1))

                new_leaf = n_cur
                lrec = recs2[2 * pos]
                rrec = recs2[2 * pos + 1]
                ltot = totals[2 * pos]
                rtot = totals[2 * pos + 1]
                ptot = ltot + rtot
                pnum = -jnp.sign(ptot[0]) * jnp.maximum(jnp.abs(ptot[0]) - l1,
                                                        0.0)
                pout = pnum / jnp.maximum(ptot[1] + l2, 1e-15)
                pout = jnp.where(max_delta > 0,
                                 jnp.clip(pout, -max_delta, max_delta), pout)
                nd = depth[b] + 1

                wb = jnp.where(can, b, L)
                wn = jnp.where(can, new_leaf, L)
                depth = depth.at[wb].set(nd).at[wn].set(nd)
                leaf_best = leaf_best.at[wb].set(lrec).at[wn].set(rrec)
                leaf_best = leaf_best.at[L].set(jnp.full(REC, neg_inf,
                                                         dtype=jnp.float32))
                row = jnp.concatenate([
                    jnp.stack([b.astype(jnp.float32), pout,
                               nd.astype(jnp.float32),
                               jnp.where(can, 1.0, 0.0)]), brec])
                wt = jnp.where(can, t, rec_store.shape[0] - 1)
                rec_store = rec_store.at[wt].set(row)
                committed = committed.at[jnp.where(can, pos, K)].set(True)
                newids = newids.at[jnp.where(can, pos, K)].set(new_leaf)
                inc = jnp.where(can, 1, 0).astype(jnp.int32)
                return (leaf_best, depth, rec_store, n_cur + inc, t + inc,
                        committed, newids, active & can)

            rp0 = (leaf_best, depth, rec_store, n_cur, t,
                   jnp.zeros(K + 1, bool), jnp.zeros(K + 1, jnp.int32),
                   jnp.bool_(True))
            (leaf_best, depth, rec_store, n_cur, t, committed, newids,
             _) = jax.lax.fori_loop(0, K, replay_step, rp0)

        with jax.named_scope(SCOPE_COMMIT):
            # --- commit side effects, all OUTSIDE the replay fori_loop (the
            # heavy [K, G, B, CH] pool writes and [N]-row updates run once per
            # wave, vectorized over the committed mask, not once per replay
            # step). Uncommitted leaves keep their old (start, count, pool)
            # entries — their ranges were only reordered internally.
            wbK = jnp.where(committed[:K], sel, L)       # parent keeps left
            wnK = jnp.where(committed[:K], newids[:K], L)  # new leaf = right
            pool = pool.at[wbK].set(histL).at[wnK].set(histR)
            mid_k = s_k + nl_k
            start = start.at[wnK].set(mid_k)
            count = count.at[wnK].set(nr_k).at[wbK].set(nl_k)
            if row_sharded:
                # replicated raw totals + GLOBAL counts ride with the pool so
                # later subtractions stay reduction-free
                tpool = tpool.at[wbK].set(totL_raw).at[wnK].set(totR_raw)
                count_g = count_g.at[wnK].set(nr_g).at[wbK].set(nl_g)

            # per-row leaf relabel via the same stacked masked matmul (position
            # >= split midpoint <=> right child, thanks to the partition)
            post = jnp.stack([committed[:K].astype(jnp.int32), newids[:K],
                              mid_k])  # [3, K]
            rowsP = rows_of(post)  # [3, N]
            com_row = kvalid & (rowsP[0] > 0.5)
            is_right = com_row & (pos >= rowsP[2].astype(jnp.int32))
            row_p = row_p.at[LEAF_ROW].set(
                jnp.where(is_right, rowsP[1], row_p[LEAF_ROW]))
        if voting:
            return (bins_p, row_p, start, count, depth, leaf_best,
                    rec_store, pool, n_cur, t, hist_rows, work_counts, tpool,
                    count_g, miss, n_waves)
        if data_par:
            return (bins_p, row_p, start, count, depth, leaf_best,
                    rec_store, pool, n_cur, t, hist_rows, work_counts, tpool,
                    count_g, n_waves)
        return (bins_p, row_p, start, count, depth, leaf_best, rec_store,
                pool, n_cur, t, hist_rows, work_counts, n_waves)

    def cond(carry):
        with jax.named_scope(SCOPE_SELECT):
            leaf_best, t = carry[5], carry[9]
            return (t < L - 1) & (jnp.max(leaf_best[:L, 0]) > 0)

    carry = (bins_p, row_p, start, count, depth, leaf_best, rec_store, pool,
             jnp.int32(1), jnp.int32(0), hist_rows, work_counts)
    if row_sharded:
        carry = carry + (tpool, count_g)
    if voting:
        carry = carry + (root_miss,)
    carry = carry + (jnp.int32(0),)  # n_waves, last so indices above hold
    if L > 1:
        carry = jax.lax.while_loop(cond, wave, carry)
    row_p, rec_store, n_cur, hist_rows, work_counts = (
        carry[1], carry[6], carry[8], carry[10], carry[11])
    n_waves = carry[-1]
    if row_sharded:
        with jax.named_scope(SCOPE_ALLREDUCE):
            hist_rows = jax.lax.psum(hist_rows, "data")
            work_counts = jax.lax.psum(work_counts, "data")
    with jax.named_scope(SCOPE_FINISH):
        # undo the permutation without a TPU scatter: sort leaf ids by the
        # original-position row (both exact small ints in f32)
        _, leaf_sorted = jax.lax.sort_key_val(
            row_p[POS_ROW].astype(jnp.int32),
            row_p[LEAF_ROW].astype(jnp.int32))
    out = (rec_store[:-1], leaf_sorted[:N], n_cur, hist_rows, n_waves,
           work_counts)
    return out + (carry[14],) if voting else out


# bins/gh/leaf_id0 are donated: each is a fresh per-tree buffer (the
# learner COPIES bins_dev before the call) consumed by the wave loop, so
# XLA reuses their allocations for the loop carries instead of double
# buffering the two largest arrays. CPU backends ignore donation (warning
# suppressed by Python's default dedup filter).
# graftlint: disable=R11 -- this entry traces _grow_impl with the STATIC arg sharded=False, so every `if sharded:` collective is pruned from this trace; the sharded trace exists only inside make_sharded_grow_fn's shard_map, and test_sharded_device.py locks both paths bit-identical
@partial(jax.jit,
         static_argnames=("num_leaves", "num_bins", "max_depth", "quantized",
                          "batch", "bagged"),
         donate_argnums=(0, 1, 2))
def grow_tree_on_device(bins: jax.Array, gh: jax.Array, leaf_id0: jax.Array,
                        meta, tables: FeatureTables, params: jax.Array,
                        feature_mask: jax.Array,
                        num_leaves: int, num_bins: int, max_depth: int,
                        quantized: bool = False,
                        scale_vec: Optional[jax.Array] = None,
                        batch: int = 16, bagged: bool = False):
    """Grow one leaf-wise tree fully on device, K splits per histogram pass.

    bins [G, N], gh [N, 3] (bagged-out rows must have zero gh),
    leaf_id0 [N] (0 for in-bag rows, -1 otherwise; pass bagged=True when
    any row is bagged out so the initial compaction runs).
    quantized: gh is int8 (g_int, h_int, 1); histogram values stay exact
    ints (int32 pool) and re-enter float space via scale_vec at scan time —
    the on-device twin of the serial learner's quantized path.

    Rows-in-leaf waves over a leaf-contiguous permutation: each WAVE takes
    the top-K frontier leaves by gain, PARTITIONS each selected range into
    left|right in place (stable; safe even if the replay later declines the
    split — the range stays contiguous), histograms ONLY the smaller-child
    subranges via ragged tiles (K*CH channels), derives the larger children
    from the histogram pool by subtraction, then an on-device replay
    commits splits in exact best-first order until the global argmax falls
    outside the precomputed set (a child created this wave) — then the next
    wave recomputes. Semantics are EXACTLY the reference's leaf-wise
    best-first growth (serial_tree_learner.cpp:182): only histogram and
    partition WORK is speculative, never split decisions. Histogrammed rows
    per tree: N (root) + sum over waves of the selected smaller-child rows
    — <= ~4N in practice vs O(N * waves) for full-N masked waves.
    Returns (rec_store [L-1, STORE], leaf_id [N] in ORIGINAL row order,
    num_leaves_final, hist_rows — rows histogrammed, the perf counter,
    n_waves — while_loop trips, for the committed-vs-speculated telemetry,
    work_counts [len(WORK_FIELDS)] int32 — what the two Mosaic kernels
    were handed over the tree's calls: pairs, tiles and grid steps).
    """
    return _grow_impl(bins, gh, leaf_id0, meta, tables, params, feature_mask,
                      scale_vec, num_leaves=num_leaves, num_bins=num_bins,
                      max_depth=max_depth, quantized=quantized, batch=batch,
                      bagged=bagged, sharded=False, narrow=False)


# graftlint: disable=untimed-hot-func -- builder only defines the shard_map/jit closure; real cost is lazy trace+compile inside the timed tree_device scope every caller runs under
def make_sharded_grow_fn(mesh, *, num_leaves: int, num_bins: int,
                         max_depth: int, quantized: bool, batch: int,
                         bagged: bool, narrow: bool = False,
                         mode: str = "data", top_k: int = 0,
                         exact_check: bool = False):
    """jit(shard_map) whole-tree grower over the "data" mesh axis: one
    dispatch per tree across every device.

    Three modes (the tree_learner config knob):

    mode="data" — rows sharded, scan feature-sharded by ONE psum_scatter.
    Call signature of the returned fn (all arrays GLOBAL, rows padded by
    the caller to a per-shard multiple of the wave tile unit so each
    device's shard needs no further padding):

        fn(bins [G, Np], gh [Np, CH], leaf_id0 [Np],
           gather_index [F_pad, Bmax], valid_slot [F_pad, Bmax],
           scan_meta (ScanMeta over [F_pad], feature-sharded),
           tables, params, feature_mask [F_pad], scale_vec [CH])

    bins/gh/leaf_id0/feature_mask arrive row-/feature-sharded on "data";
    gather tables, decision tables, params and scale_vec replicated.

    mode="voting" — rows sharded like "data", but gather tables, scan_meta
    and feature_mask arrive REPLICATED over the FULL padded feature axis
    (every device scans all features locally; only elected slices are
    reduced — PV-Tree, `top_k` nominations per shard). Two extra trailing
    scalar args (skew_rank, skew_wave — int32, -1 disarmed) drive the
    vote_skew fault hook, and the returned tuple gains a trailing
    replicated `miss` count (non-zero only when exact_check=True).

    mode="feature" — bins/gh/leaf_id0 arrive REPLICATED (and unpadded:
    the internal padding handles them exactly like the single-device
    path) while gather tables, scan_meta and feature_mask arrive
    feature-sharded; the only collective is the best-record all_gather.

    scale_vec must be a real array even when quantized=False (pass ones —
    it is ignored). Categorical splits are not supported here (the factory
    routes categorical configs to the host-driven learners). Returns the
    same (rec_store, leaf_id [Np] global original order, n_cur, hist_rows,
    n_waves, work_counts) as grow_tree_on_device; all but leaf_id are
    replicated (hist_rows and work_counts summed over the row shards).
    """
    from jax.sharding import PartitionSpec as P

    if mode == "voting":
        def body(bins, gh, leaf_id0, gather_index, valid_slot, scan_meta,
                 tables, params, feature_mask, scale_vec, skew_rank,
                 skew_wave):
            meta = ShardMeta(gather_index, valid_slot, scan_meta)
            return _grow_impl(bins, gh, leaf_id0, meta, tables, params,
                              feature_mask,
                              scale_vec if quantized else None,
                              num_leaves=num_leaves, num_bins=num_bins,
                              max_depth=max_depth, quantized=quantized,
                              batch=batch, bagged=bagged, sharded=True,
                              narrow=narrow, mode="voting", top_k=top_k,
                              exact_check=exact_check,
                              skew=(skew_rank, skew_wave))

        return jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "data"), P("data"), P("data"), P(), P(),
                      P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), P("data"), P(), P(), P(), P(), P()),
            check_vma=False), donate_argnums=(0, 1, 2))

    if mode == "feature":
        def body(bins, gh, leaf_id0, gather_index, valid_slot, scan_meta,
                 tables, params, feature_mask, scale_vec):
            meta = ShardMeta(gather_index, valid_slot, scan_meta)
            return _grow_impl(bins, gh, leaf_id0, meta, tables, params,
                              feature_mask,
                              scale_vec if quantized else None,
                              num_leaves=num_leaves, num_bins=num_bins,
                              max_depth=max_depth, quantized=quantized,
                              batch=batch, bagged=bagged, sharded=True,
                              narrow=False, mode="feature")

        # no donation: the replicated row arrays arrive unpadded, so their
        # buffers never match the padded loop carries anyway (donating
        # them only buys a "not usable" warning)
        return jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P("data"), P("data"),
                      P(), P(), P("data"), P()),
            out_specs=(P(), P(), P(), P(), P(), P()),
            check_vma=False))

    def body(bins, gh, leaf_id0, gather_index, valid_slot, scan_meta,
             tables, params, feature_mask, scale_vec):
        meta = ShardMeta(gather_index, valid_slot, scan_meta)
        return _grow_impl(bins, gh, leaf_id0, meta, tables, params,
                          feature_mask,
                          scale_vec if quantized else None,
                          num_leaves=num_leaves, num_bins=num_bins,
                          max_depth=max_depth, quantized=quantized,
                          batch=batch, bagged=bagged, sharded=True,
                          narrow=narrow)

    return jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "data"), P("data"), P("data"), P(), P(),
                  P("data"), P(), P(), P("data"), P()),
        out_specs=(P(), P("data"), P(), P(), P(), P()),
        check_vma=False), donate_argnums=(0, 1, 2))


class DevicePartition:
    """Partition view over the final leaf-id vector (indices()/count()
    surface shared with ops.partition.RowPartition, plus the vectorized
    leaf_ids_dev fast path for score updates). The device vector may be
    the sharded learner's `[n_pad]` row layout (pad rows -1 at the end),
    left where the tree wrote it for the score update to read there; the
    host view is cut to the `num_data` real rows."""

    def __init__(self, leaf_ids_dev: jax.Array, counts: Dict[int, int],
                 num_data: int) -> None:
        self._ids_dev = leaf_ids_dev
        self._num_data = num_data
        self._ids: Optional[np.ndarray] = None
        self._order: Optional[np.ndarray] = None
        self._sorted: Optional[np.ndarray] = None
        self.counts = counts

    def leaf_ids_dev(self) -> jax.Array:
        return self._ids_dev

    @property
    def ids_host(self) -> np.ndarray:
        if self._ids is None:
            self._ids = np.asarray(self._ids_dev)[: self._num_data]
        return self._ids

    def count(self, leaf: int) -> int:
        return self.counts.get(leaf, 0)

    def indices(self, leaf: int) -> np.ndarray:
        # one stable argsort amortized over every leaf query (the old
        # per-leaf np.nonzero scan was O(N) PER LEAF under the serial
        # fallbacks and quantized leaf renewal). Stable sort keeps equal
        # ids in ascending position order, so each slice is bit-identical
        # to the nonzero scan's output.
        if self._order is None:
            ids = self.ids_host
            self._order = np.argsort(ids, kind="stable").astype(np.int32)
            self._sorted = ids[self._order]
        lo = np.searchsorted(self._sorted, leaf, side="left")
        hi = np.searchsorted(self._sorted, leaf, side="right")
        return self._order[lo:hi]


class _PendingTree(NamedTuple):
    """In-flight tree: dispatched on device, split log not yet replayed.

    `tree` is the (still empty) host Tree that finalize() fills IN PLACE —
    the async pipeline in models/gbdt.py appends it to the model list
    before the replay happens, so predictions through the model see the
    grown tree as soon as finalize() returns."""

    tree: Tree
    rec_store: jax.Array
    leaf_id: jax.Array
    hist_rows: jax.Array
    n_waves: jax.Array
    work_counts: jax.Array  # [len(WORK_FIELDS)] int32
    n_bag: int
    wave_k: int  # wave width this tree was dispatched with
    # leaf_id is the sharded learner's [n_pad] row layout, never moved
    rows_resident: bool = False


class DeviceTreeLearner(SerialTreeLearner):
    """Serial learner running the whole tree in one dispatch.

    train() splits into train_async() (dispatch + start the device->host
    copy of the split log, non-blocking) and finalize() (block on the log,
    replay it into the Tree, install the partition). The GBDT async
    pipeline overlaps tree t's device growth with the host replay of tree
    t-1 by holding the _PendingTree across iterations; the plain train()
    path chains the two immediately and is bit-identical."""

    # one chip: no mesh, and nothing of a wave crosses ICI (the sharded
    # learners of parallel/learners.py set both); the `tree_wave` note
    # carries them
    D = 1
    _ici_bytes_per_wave = 0

    def __init__(self, config, dataset) -> None:
        super().__init__(config, dataset)
        self.tables = _feature_tables(dataset, dataset.used_features)
        self._row_arange = np.arange(self.num_data, dtype=np.int32)
        # `wave` is the width asked for, `wave_k` the width a wave can use
        # (a tree of L leaves never holds more than L candidates) and the
        # one-chip program's static `batch`
        self.wave = WAVE_K
        self.wave_k = max(1, min(self.wave, int(config.num_leaves)))
        # which contraction this learner's histograms take (the `tree_wave`
        # note says it): the ragged kernel's gradient operand, or the XLA
        # body off the TPU. Read once, as the whole-tree program's trace
        # bakes LGBM_TPU_HIST_F32 in at its first call
        from ..ops.hist_pallas import hist_force_f32, hist_operand
        self.hist_operand = (
            hist_operand(self.quantized, hist_force_f32())
            if on_tpu() or pallas_interpret() else "xla")

    def snapshot_state(self) -> dict:
        st = super().snapshot_state()
        st["bins_dtype"] = str(self.bins_dev.dtype)
        return st

    def restore_snapshot_state(self, st: dict) -> None:
        want = st.get("bins_dtype")
        if want is not None and want != str(self.bins_dev.dtype):
            Log.fatal("Checkpoint was captured with a %s bin plane but the "
                      "resume run built %s (LGBM_TPU_BINS_I32 mismatch?) — "
                      "histogram accumulation order would differ, breaking "
                      "bit-identical resume", want, self.bins_dev.dtype)
        super().restore_snapshot_state(st)

    def _record_carry_bytes(self) -> None:
        """Gauges for the analytic bandwidth model (docs/PERF_NOTES.md,
        executable form in perfmodel.py): HBM bytes of the per-wave loop
        carry, bytes the ragged histogram kernel streams per row, and the
        gain-scan read volume per wave — perfmodel.attribution() reads
        these back to attribute the fused `tree_device` wall."""
        from .. import perfmodel
        from ..ops.compact_pallas import COMPACT_TILE
        from ..ops.hist_pallas import DEFAULT_TILE_ROWS
        unit = max(DEFAULT_TILE_ROWS, COMPACT_TILE)
        G = self.bins_dev.shape[0]
        plane_b = self.bins_dev.dtype.itemsize
        plane_b = plane_b if plane_b == 1 else 4
        global_timer.set_count(
            "device_carry_bytes_per_wave",
            perfmodel.carry_bytes_per_wave(self.num_data, G, plane_b, unit))
        global_timer.set_count(
            "device_hist_bytes_per_row",
            perfmodel.hist_bytes_per_row(G, plane_b))
        # the replay scan sweeps the [K, G, Bpad, CH] pool block and writes
        # the [2K, G, REC] best-record store; the pool is 4-byte in both the
        # float and quantized (int32) regimes
        global_timer.set_count(
            "device_scan_bytes_per_wave",
            perfmodel.scan_bytes_per_wave(self.wave_k, G,
                                          self.group_bin_padded))

    def train(self, gh_ext: jax.Array,
              bag_indices: Optional[np.ndarray] = None) -> Tree:
        return self.finalize(self.train_async(gh_ext, bag_indices))

    def train_async(self, gh_ext: jax.Array,
                    bag_indices: Optional[np.ndarray] = None) -> _PendingTree:
        cfg = self.config
        num_leaves = cfg.num_leaves
        if self.quantized:
            gh_ext = self._prepare_gh(gh_ext)  # int8 rows + scales
        gh = gh_ext[:-1]
        if isinstance(bag_indices, DeviceBag):
            # device-resident bag (GOSS): the mask never touches the host —
            # same where() ops as the host-index branch below, so the masked
            # gh and leaf seeds are bit-identical for an identical bag
            mask = bag_indices.mask
            leaf_id0 = jnp.where(mask, 0, -1).astype(jnp.int32)
            gh = jnp.where(mask[:, None], gh, jnp.zeros((), gh.dtype))
            n_bag = bag_indices.n_bag
        elif bag_indices is not None:
            in_bag = np.zeros(self.num_data, dtype=bool)
            in_bag[np.asarray(bag_indices, dtype=np.int64)] = True
            leaf_id0 = jnp.asarray(np.where(in_bag, 0, -1), dtype=jnp.int32)
            gh = jnp.where(jnp.asarray(in_bag, dtype=jnp.bool_)[:, None], gh,
                           jnp.zeros((), gh.dtype))
            n_bag = len(bag_indices)
        else:
            leaf_id0 = jnp.zeros(self.num_data, dtype=jnp.int32)
            n_bag = self.num_data

        if self.col_sampler.active:
            fmask = jnp.asarray(self.col_sampler.reset_by_tree(),
                                dtype=jnp.bool_)
        else:
            fmask = jnp.ones(len(self.meta.real_feature), dtype=bool)
        self._record_carry_bytes()
        grow = sanitize.guard(
            grow_tree_on_device, (0, 1, 2),
            "grow_tree_on_device (treelearner/device.py train_async)")
        if telemetry.enabled():
            # one-time dispatch capture: perfmodel AOT-relowers this exact
            # signature for cost_analysis() (dict-check no-op afterwards)
            perfmodel.note_dispatch(
                "grow_fused", grow_tree_on_device,
                self.bins_dev, gh, leaf_id0, self.meta, self.tables,
                self.params_dev, fmask, num_leaves, self.group_bin_padded,
                cfg.max_depth, quantized=self.quantized,
                scale_vec=self._scale_vec, batch=self.wave_k,
                bagged=bag_indices is not None)
        with global_timer.scope("tree_device"):
            # bins_dev is COPIED per tree: grow_tree_on_device donates its
            # first three args (gh and leaf_id0 are already fresh buffers).
            # The copy stays eager: inside a jitted function jnp.copy lowers
            # to nothing and the compiler's own copy of the result carries
            # no scope either (PERF.md, PR 36)
            rec_store, leaf_id, _, hist_rows, n_waves, work_counts = grow(
                jnp.copy(self.bins_dev), gh, leaf_id0, self.meta,
                self.tables, self.params_dev, fmask, num_leaves,
                self.group_bin_padded,
                cfg.max_depth, quantized=self.quantized,
                scale_vec=self._scale_vec, batch=self.wave_k,
                bagged=bag_indices is not None)
        # start the device->host copies without blocking; finalize() (maybe
        # a full iteration later, under the async pipeline) pays no wait if
        # the transfer already landed
        for arr in (rec_store, leaf_id, hist_rows, n_waves, work_counts):
            start = getattr(arr, "copy_to_host_async", None)
            if start is not None:
                start()
        return _PendingTree(Tree(num_leaves), rec_store, leaf_id, hist_rows,
                            n_waves, work_counts, n_bag, wave_k=self.wave_k)

    def finalize(self, pending: _PendingTree) -> Tree:
        cfg = self.config
        tree = pending.tree
        with global_timer.scope("tree_replay"):
            rec_np = np.asarray(pending.rec_store)  # the one blocking pull
        leaf_id = pending.leaf_id
        self.last_hist_rows = int(pending.hist_rows)
        global_timer.add_count("device_hist_rows", self.last_hist_rows)
        self.last_work = dict(zip(
            WORK_FIELDS, (int(v) for v in np.asarray(pending.work_counts))))

        counts: Dict[int, int] = {0: int(pending.n_bag)}
        for t in range(rec_np.shape[0]):
            row = rec_np[t]
            if row[3] < 0.5:  # valid flag: growth stopped here
                break
            leaf = int(row[0])
            split = SplitInfo.from_packed(row[4:])
            dense_f = split.feature
            real_f = self.meta.real_feature[dense_f]
            mapper = self.dataset.mappers[real_f]
            tree.split(
                leaf=leaf, feature_inner=dense_f, real_feature=real_f,
                threshold_bin=split.threshold_bin,
                threshold_double=mapper.bin_to_value(split.threshold_bin),
                default_left=split.default_left,
                missing_type=mapper.missing_type, gain=split.gain,
                left_value=split.left_output, right_value=split.right_output,
                left_count=split.left_count, right_count=split.right_count,
                left_weight=split.left_sum_h, right_weight=split.right_sum_h,
                parent_value=float(row[1]))
            counts[leaf] = split.left_count
            counts[tree.num_leaves - 1] = split.right_count

        self._record_wave_efficiency(pending, tree)
        self.partition = DevicePartition(leaf_id, counts, self.num_data)
        if tree.num_leaves == 1:
            tree.as_constant_tree(0.0)
        elif self.quantized and cfg.quant_train_renew_leaf:
            self._renew_quantized_leaves_device(tree, leaf_id)
        return tree

    def _record_wave_efficiency(self, pending: _PendingTree,
                                tree: Tree) -> None:
        """Committed-vs-speculated wave accounting: each wave partitions +
        histograms K candidate splits but the replay commits only as many
        as stay globally best-first. Split decisions are K-invariant given
        the same histogram sums, so K changes only the amount of
        speculative work, never the model: exact with use_quantized_grad
        (tests/test_device_learner.py), and in float up to the summation
        order of a backend whose histogram contraction depends on the 3*K
        output width (XLA:CPU's does)."""
        from .. import telemetry, tracing
        n_waves = int(pending.n_waves)
        wave_k = pending.wave_k
        committed = tree.num_leaves - 1
        speculated = n_waves * wave_k
        commit_rate = committed / speculated if speculated else 1.0
        global_timer.add_count("device_waves", n_waves)
        global_timer.add_count("wave_splits_committed", committed)
        global_timer.add_count("wave_splits_speculated", speculated)
        # flight-recorder mirror: plain already-computed ints, O(1), no
        # sync — the one in-memory record per tree (a postmortem sees the
        # last trees' wave shape even with telemetry off)
        tracing.note("tree_wave", waves=n_waves, wave_k=wave_k,
                     committed=committed, speculated=speculated,
                     hist_rows=self.last_hist_rows,
                     **self.last_work,
                     hist_operand=self.hist_operand,
                     hist_int=int(self.hist_operand == "int"),
                     ici_bytes=n_waves * self._ici_bytes_per_wave,
                     mesh_devices=self.D,
                     rows_resident=int(pending.rows_resident))
        if telemetry.enabled():
            telemetry.emit(
                "tree_wave", waves=n_waves, wave_width=wave_k,
                committed=committed, speculated=speculated,
                efficiency=round(commit_rate, 4) if speculated else 1.0,
                hist_rows=self.last_hist_rows,
                ici_bytes_per_wave=int(global_timer.counters.get(
                    "device_ici_bytes_per_wave", 0)),
                carry_bytes_per_wave=int(global_timer.counters.get(
                    "device_carry_bytes_per_wave", 0)))
        global_timer.set_count("wave_k", self.wave_k)

    def _renew_quantized_leaves_device(self, tree: Tree,
                                       leaf_id: jax.Array) -> None:
        """True-gradient leaf renewal in ONE scatter-add dispatch over the
        on-device leaf-id vector (no per-leaf host scans; no frontier bounds
        here — the factory routes monotone configs to the host learner)."""
        cfg = self.config
        sums = np.asarray(_leaf_gradient_sums(self._gh_float, leaf_id,
                                              cfg.num_leaves))
        for leaf in range(tree.num_leaves):
            out = _leaf_output_host(float(sums[leaf, 0]),
                                    float(sums[leaf, 1]),
                                    cfg.lambda_l1, cfg.lambda_l2,
                                    cfg.max_delta_step)
            tree.set_leaf_output(leaf, out)


# graftlint: disable=R6 -- both inputs must survive: the float pack is the learner's `_gh_float` and the leaf ids are the tree's partition, read again by the score update; no input matches the [L + 1, 2] output
@partial(jax.jit, static_argnames=("num_leaves",))
def _leaf_gradient_sums(gh_float: jax.Array, leaf_id: jax.Array,
                        num_leaves: int) -> jax.Array:
    """[num_leaves + 1, 2] float32 sums of the true (gradient, hessian)
    over each leaf's rows; bagged-out rows (leaf id -1) go to the last,
    dump row. One program whatever the tree's final leaf count."""
    with jax.named_scope(SCOPE_RENEW_LEAVES):
        ids = jnp.where(leaf_id >= 0, leaf_id, num_leaves)
        return jnp.zeros((num_leaves + 1, 2), jnp.float32).at[ids].add(
            gh_float[:-1, :2])
