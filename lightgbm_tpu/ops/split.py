"""Vectorized best-split search over histograms.

TPU-native replacement for FeatureHistogram::FindBestThreshold /
FindBestThresholdSequentially (src/treelearner/feature_histogram.hpp:165,832)
and the CUDA per-(leaf,feature) scan kernels (CUDABestSplitFinder,
src/treelearner/cuda/cuda_best_split_finder.cu).

Instead of the reference's per-feature sequential bidirectional scans, the
whole search is one fused computation over a dense [F, Bmax, 3] feature-
histogram tensor:

    cumsum over bins -> left/right aggregates for every threshold
    -> regularized gains for both missing directions -> masked argmax.

Missing-value directionality (the reference's templated REVERSE / NA_AS_MISSING
scan variants) becomes two gain lanes: the missing bin's mass (NaN bin for
MissingType::NaN, default/zero bin for MissingType::Zero) is pulled out of the
ordered scan and added to the left side in the "default-left" lane only.

Bundled features (EFB) omit their default bin in group storage; it is
reconstructed here from the leaf totals exactly like Dataset::FixHistogram
(include/LightGBM/dataset.h:770). A data set with a bundle gathers its
features in width classes (`WidthClasses`): a bundle's one-hot members at
their own two bins, not padded to the widest feature's.

Gain/output formulas mirror feature_histogram.hpp GetSplitGains /
CalculateSplittedLeafOutput: L1 soft-thresholding, L2 shrinkage,
max_delta_step clamping.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from ..utils.timer import SCOPE_BUNDLE_SCAN, SCOPE_CAT_SCAN

K_EPSILON = 1e-15
K_MIN_GAIN = -np.inf


@dataclass
class FeatureMeta:
    """Device-side per-feature split metadata, precomputed once per dataset."""

    gather_index: jax.Array  # [F, Bmax] int32 into flattened group-hist rows (+ sentinel)
    valid_slot: jax.Array  # [F, Bmax] bool
    default_bin: jax.Array  # [F] int32 zero/default bin (feature-bin space)
    efb_omitted: jax.Array  # [F] bool: default bin omitted in storage (EFB bundle)
    missing_type: jax.Array  # [F] int32
    nbins: jax.Array  # [F] int32 bins per feature
    is_categorical: jax.Array  # [F] bool
    monotone: jax.Array  # [F] int32 (-1/0/+1)
    # host-side
    real_feature: List[int]  # dense idx -> original feature index
    max_bins: int
    hist_rows: int  # rows in the flattened group-hist (without sentinel)
    has_categorical: bool = False  # static: gates the categorical scan
    # static: the longest prefix the categorical scan evaluates,
    # min(max_cat_threshold, max_bins // 2); 0 = max_bins // 2
    cat_scan_len: int = 0
    # where the data set has an EFB bundle and no categorical feature
    # (decided once, from the layout): the scan's features in classes of
    # their own gather width; None: one [F, Bmax] gather
    width_classes: Optional["WidthClasses"] = None

    def tree_flatten(self):
        return ((self.gather_index, self.valid_slot, self.default_bin,
                 self.efb_omitted, self.missing_type, self.nbins,
                 self.is_categorical, self.monotone, self.width_classes),
                (self.real_feature, self.max_bins, self.hist_rows,
                 self.has_categorical, self.cat_scan_len))


jax.tree_util.register_pytree_node(
    FeatureMeta,
    FeatureMeta.tree_flatten,
    lambda aux, ch: FeatureMeta(*ch[:8], real_feature=aux[0],
                                max_bins=aux[1], hist_rows=aux[2],
                                has_categorical=aux[3], cat_scan_len=aux[4],
                                width_classes=ch[8]),
)


class WidthClass(NamedTuple):
    """Features of at most `meta.max_bins` bins, gathered at that width:
    `meta` holds their rows of the whole data set's FeatureMeta, cut to
    the width, and `ids` their dense feature indices."""

    meta: FeatureMeta
    ids: jax.Array  # [F_c] int32


class WidthClasses(NamedTuple):
    """The split scan's features in classes by their bin count, made once
    from the layout (`width_classes`); `order[k]` is dense feature k's row
    in the classes' records laid end to end."""

    classes: Tuple[WidthClass, ...]
    order: jax.Array  # [F] int32


def make_feature_meta(dataset, group_bin_padded: int,
                      max_cat_threshold: int = 0) -> FeatureMeta:
    """Build FeatureMeta from a constructed io.dataset.Dataset.

    group_bin_padded is the per-group bin-axis padding used by the histogram
    kernel (hist shape [G, group_bin_padded, 3]); the flat row index of group
    g bin b is g * group_bin_padded + b. max_cat_threshold (the config's)
    bounds the categorical scan's prefixes statically; 0 leaves the bound
    at half the bins.
    """
    feats = dataset.used_features
    F = len(feats)
    Bmax = max((dataset.mappers[f].num_bin for f in feats), default=2)
    gather = np.zeros((F, Bmax), dtype=np.int32)
    valid = np.zeros((F, Bmax), dtype=bool)
    default_bin = np.zeros(F, dtype=np.int32)
    efb_omitted = np.zeros(F, dtype=bool)
    missing = np.zeros(F, dtype=np.int32)
    nbins = np.zeros(F, dtype=np.int32)
    is_cat = np.zeros(F, dtype=bool)
    mono = np.zeros(F, dtype=np.int32)
    G = dataset.num_groups
    sentinel = G * group_bin_padded  # flat index of the all-zero sentinel row
    for k, f in enumerate(feats):
        m = dataset.mappers[f]
        gi, mi = dataset.feature_to_group[f]
        fg = dataset.groups[gi]
        nb = m.num_bin
        nbins[k] = nb
        missing[k] = m.missing_type
        is_cat[k] = m.bin_type == 1
        if dataset.monotone_constraints:
            mono[k] = dataset.monotone_constraints[f]
        lo, hi, dbin = fg.feature_bin_range(mi)
        gather[k, :] = sentinel
        default_bin[k] = m.default_bin
        if not fg.is_multi:
            for b in range(nb):
                gather[k, b] = gi * group_bin_padded + b
                valid[k, b] = True
        else:
            # bundle member: natural bin b != default lives at
            # lo + b - (b > default); default bin is reconstructed
            for b in range(nb):
                valid[k, b] = True
                if b == dbin:
                    continue
                slot = lo + b - (1 if b > dbin else 0)
                gather[k, b] = gi * group_bin_padded + slot
            efb_omitted[k] = True
    has_bundle = any(g.is_multi for g in dataset.groups)
    arrays = dict(gather_index=gather, valid_slot=valid,
                  default_bin=default_bin, efb_omitted=efb_omitted,
                  missing_type=missing, nbins=nbins, is_categorical=is_cat,
                  monotone=mono)
    return FeatureMeta(
        **{k: jnp.asarray(v, dtype=v.dtype) for k, v in arrays.items()},
        real_feature=list(feats),
        max_bins=Bmax,
        hist_rows=G * group_bin_padded,
        has_categorical=bool(is_cat.any()),
        cat_scan_len=(min(int(max_cat_threshold), Bmax // 2)
                      if max_cat_threshold > 0 else 0),
        width_classes=(width_classes(arrays, list(feats), G * group_bin_padded)
                       if has_bundle and not is_cat.any() else None),
    )


def width_classes(arrays: dict, feats: list,
                  hist_rows: int) -> Optional[WidthClasses]:
    """Two classes of features by bin count, split where the gather is
    smallest: the features of at most w bins gathered [F_n, w], the rest
    [F_w, Bmax] (a bundled one-hot table's members at 2 bins beside its
    numerical columns at 255). None where no split is smaller than one
    [F, Bmax] gather. `arrays` are make_feature_meta's [F, ...] tables."""
    nbins = arrays["nbins"]
    F, Bmax = arrays["gather_index"].shape
    cost = {int(w): int((nbins <= w).sum()) * int(w)
            + int((nbins > w).sum()) * Bmax for w in np.unique(nbins)}
    w = min(cost, key=lambda k: (cost[k], k))
    if cost[w] >= F * Bmax:
        return None
    classes = []
    for ids, width in ((np.flatnonzero(nbins <= w), w),
                       (np.flatnonzero(nbins > w), Bmax)):
        if ids.size == 0:
            continue
        sub = {k: jnp.asarray(v[ids, :width] if v.ndim == 2 else v[ids],
                              dtype=v.dtype) for k, v in arrays.items()}
        classes.append(WidthClass(FeatureMeta(
            **sub, real_feature=[feats[i] for i in ids], max_bins=int(width),
            hist_rows=hist_rows), jnp.asarray(ids, jnp.int32)))
    order = np.empty(F, np.int32)
    order[np.concatenate([np.asarray(c.ids) for c in classes])] = \
        np.arange(F, dtype=np.int32)
    return WidthClasses(tuple(classes), jnp.asarray(order, dtype=jnp.int32))


class ScanMeta(NamedTuple):
    """The FeatureMeta subset the split scan reads — a plain pytree so
    distributed learners can shard it along the feature axis. efb_omitted
    rides along so sharded learners can run fix_feature_hist on their local
    feature block AFTER the cross-shard histogram reduction."""

    valid_slot: jax.Array  # [F, Bmax] bool
    default_bin: jax.Array  # [F] int32
    missing_type: jax.Array  # [F] int32
    nbins: jax.Array  # [F] int32
    is_categorical: jax.Array  # [F] bool
    monotone: jax.Array  # [F] int32 (-1/0/+1)
    efb_omitted: jax.Array  # [F] bool


def scan_meta_of(meta: FeatureMeta) -> ScanMeta:
    return ScanMeta(meta.valid_slot, meta.default_bin, meta.missing_type,
                    meta.nbins, meta.is_categorical, meta.monotone,
                    meta.efb_omitted)


def pad_feature_meta(meta: FeatureMeta, f_pad: int) -> FeatureMeta:
    """Pad the feature axis to f_pad with inert rows (valid_slot all False,
    gather hitting the zero sentinel) so it divides a mesh axis evenly."""
    F = meta.gather_index.shape[0]
    if f_pad == F:
        return meta
    pad = f_pad - F
    return FeatureMeta(
        gather_index=jnp.concatenate([
            meta.gather_index,
            jnp.full((pad, meta.max_bins), meta.hist_rows, jnp.int32)]),
        valid_slot=jnp.concatenate([
            meta.valid_slot, jnp.zeros((pad, meta.max_bins), bool)]),
        default_bin=jnp.concatenate([meta.default_bin, jnp.zeros(pad, jnp.int32)]),
        efb_omitted=jnp.concatenate([meta.efb_omitted, jnp.zeros(pad, bool)]),
        missing_type=jnp.concatenate([meta.missing_type, jnp.zeros(pad, jnp.int32)]),
        nbins=jnp.concatenate([meta.nbins, jnp.ones(pad, jnp.int32)]),
        is_categorical=jnp.concatenate([meta.is_categorical, jnp.zeros(pad, bool)]),
        monotone=jnp.concatenate([meta.monotone, jnp.zeros(pad, jnp.int32)]),
        real_feature=list(meta.real_feature) + [-1] * pad,
        max_bins=meta.max_bins,
        hist_rows=meta.hist_rows,
        has_categorical=meta.has_categorical,
        cat_scan_len=meta.cat_scan_len,
    )


def threshold_l1(s, l1):
    # The barrier pins the soft-thresholded gradient to a rounded f32 before
    # it feeds the output division and the gain products. Without it, XLA's
    # algebraic rewrite of the fused sign/abs/divide/multiply chain differs
    # between the inlined single-device lowering and the SPMD-partitioned
    # >=2-device lowering, and split gains wiggle by one ULP across mesh
    # sizes — which breaks the shrink-to-fit resume bit-identity contract
    # (docs/ROBUSTNESS.md). Pinning this one value makes every mesh size
    # produce identical records.
    return jax.lax.optimization_barrier(
        jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0))


def leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step):
    """CalculateSplittedLeafOutput (feature_histogram.hpp)."""
    num = -threshold_l1(sum_grad, l1)
    out = num / jnp.maximum(sum_hess + l2, K_EPSILON)
    return jnp.where(max_delta_step > 0,
                     jnp.clip(out, -max_delta_step, max_delta_step), out)


def leaf_gain_given_output(sum_grad, sum_hess, l1, l2, output):
    g = threshold_l1(sum_grad, l1)
    return -(2.0 * g * output + (sum_hess + l2) * output * output)


def leaf_gain(sum_grad, sum_hess, l1, l2, max_delta_step):
    out = leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step)
    return leaf_gain_given_output(sum_grad, sum_hess, l1, l2, out)


# Packed best-split record layout (device -> host, one sync per leaf).
# For categorical splits: threshold_bin holds the one-hot bin (cat_dir=0) or
# the sorted-subset prefix LENGTH (cat_dir=+/-1 giving the scan direction);
# the left set itself follows SPLIT_FIELDS as bitset words (cat_words).
SPLIT_FIELDS = ["gain", "feature", "threshold_bin", "default_left",
                "left_sum_g", "left_sum_h", "left_count",
                "right_sum_g", "right_sum_h", "right_count",
                "left_output", "right_output", "is_cat", "cat_dir"]


@dataclass
class SplitInfo:
    """Host-side split record (counterpart of split_info.hpp SplitInfo)."""

    gain: float = -np.inf
    feature: int = -1  # dense (used-feature) index
    threshold_bin: int = 0
    default_left: bool = False
    left_sum_g: float = 0.0
    left_sum_h: float = 0.0
    left_count: int = 0
    right_sum_g: float = 0.0
    right_sum_h: float = 0.0
    right_count: int = 0
    left_output: float = 0.0
    right_output: float = 0.0
    is_categorical: bool = False
    cat_dir: int = 0  # 0 = one-hot; +/-1 = sorted-subset scan direction
    cat_bitset_bins: Optional[List[int]] = None  # the left set's bins

    @property
    def valid(self) -> bool:
        return self.feature >= 0 and np.isfinite(self.gain) and self.gain > 0

    @classmethod
    def from_packed(cls, vec: np.ndarray) -> "SplitInfo":
        out = cls(gain=float(vec[0]), feature=int(vec[1]),
                  threshold_bin=int(vec[2]), default_left=bool(vec[3] > 0.5),
                  left_sum_g=float(vec[4]), left_sum_h=float(vec[5]),
                  left_count=int(round(vec[6])), right_sum_g=float(vec[7]),
                  right_sum_h=float(vec[8]), right_count=int(round(vec[9])),
                  left_output=float(vec[10]), right_output=float(vec[11]))
        if len(vec) > 13:
            out.is_categorical = bool(vec[12] > 0.5)
            out.cat_dir = int(round(vec[13]))
        if out.is_categorical and len(vec) > len(SPLIT_FIELDS):
            out.cat_bitset_bins = bitset_word_bins(vec[len(SPLIT_FIELDS):])
        return out


def gather_feature_hist_raw(hist: jax.Array, gather_index: jax.Array,
                            valid_slot: jax.Array) -> jax.Array:
    """[G, Bg, CH] group hist -> [F, Bmax, CH] by pure index gather, NO EFB
    reconstruction. Selection commutes bit-exactly with sum reductions
    (integer or float, any summation order), so sharded learners gather
    their raw local histograms, reduce across shards, and apply
    fix_feature_hist on the reduced blocks with GLOBAL totals — matching
    the single-device op order exactly."""
    flat = hist.reshape(-1, hist.shape[-1])
    flat = jnp.concatenate(
        [flat, jnp.zeros((1, hist.shape[-1]), flat.dtype)], axis=0)
    fh = flat[gather_index]  # [F, Bmax, CH]
    return fh * valid_slot[:, :, None]


def fix_feature_hist(fh: jax.Array, totals: jax.Array,
                     efb_omitted: jax.Array,
                     default_bin: jax.Array) -> jax.Array:
    """EFB default-bin reconstruction: default = leaf totals - sum(other
    bins), added at the default bin of bundle members only (FixHistogram,
    include/LightGBM/dataset.h:770). Works on the full [F, Bmax, CH] tensor
    or a sharded feature block — totals must be the LEAF totals matching
    fh's aggregation scope.

    (dtype-preserving multiply, not jnp.where with a float 0: quantized
    histograms flow through here as exact int32)"""
    missing_mass = totals[None, :].astype(fh.dtype) - fh.sum(axis=1)  # [F, CH]
    add = missing_mass * efb_omitted[:, None]
    return fh.at[jnp.arange(fh.shape[0], dtype=jnp.int32),
                 default_bin].add(add)


@partial(jax.jit, static_argnames=())
def gather_feature_hist(hist: jax.Array, meta: FeatureMeta,
                        totals: jax.Array) -> jax.Array:
    """[G, Bg, 3] group hist -> [F, Bmax, 3] feature hist with EFB default
    reconstruction (FixHistogram)."""
    fh = gather_feature_hist_raw(hist, meta.gather_index, meta.valid_slot)
    return fix_feature_hist(fh, totals, meta.efb_omitted, meta.default_bin)


def per_feature_best(fh: jax.Array, totals: jax.Array, meta: FeatureMeta,
                     params: jax.Array,
                     feature_mask: Optional[jax.Array] = None,
                     constraint: Optional[jax.Array] = None,
                     penalty: Optional[jax.Array] = None) -> jax.Array:
    """Best split per feature: [F, len(SPLIT_FIELDS)] records.

    fh:     [F, Bmax, 3] feature histograms (after gather_feature_hist)
    totals: [3] leaf (sum_grad, sum_hess, count)
    params: [lambda_l1, lambda_l2, min_data_in_leaf, min_sum_hessian_in_leaf,
             min_gain_to_split, max_delta_step] as a device vector
    constraint: optional [2] (min, max) leaf output bounds — basic-mode
             monotone constraints (monotone_constraints.hpp BasicLeafConstraints):
             candidate outputs are clamped, and splits on a monotone feature
             whose clamped outputs violate the direction are discarded
             (GetSplitGains, feature_histogram.hpp:788-792).
    penalty: optional [F] gain penalty subtracted per feature (CEGB DeltaGain,
             cost_effective_gradient_boosting.hpp:80-98).

    The `feature` field is the LOCAL row index into fh (invalid rows get -1);
    distributed feature shards offset it by their block start. This is the
    core scan shared by the serial learner and the data/feature/voting
    parallel learners (the reference runs FindBestThresholdSequentially per
    rank feature block, data_parallel_tree_learner.cpp:305+).
    """
    l1, l2, min_data, min_hess, min_gain, max_delta = (
        params[0], params[1], params[2], params[3], params[4], params[5])
    F, Bmax, _ = fh.shape

    total_g, total_h, total_cnt = totals[0], totals[1], totals[2]

    # pull the missing bin out of the ordered scan: the NaN bin is the last
    # bin for MissingType::NaN, the zero/default bin for MissingType::Zero
    missing_pos = jnp.where(meta.missing_type == MISSING_NAN,
                            meta.nbins - 1, meta.default_bin)
    has_missing = meta.missing_type != MISSING_NONE
    rows = jnp.arange(F, dtype=jnp.int32)
    missing_vals = jnp.where(has_missing[:, None],
                             fh[rows, missing_pos], 0.0)  # [F, 3]
    scan_hist = jnp.where(
        (has_missing[:, None] & (jnp.arange(Bmax, dtype=jnp.int32)[None, :] == missing_pos[:, None]))[:, :, None],
        0.0, fh)

    cum = jnp.cumsum(scan_hist, axis=1)  # [F, Bmax, 3]

    # lane 0: missing goes right (natural);  lane 1: missing goes left
    left0 = cum
    left1 = cum + missing_vals[:, None, :]
    results = []
    for lane, left in enumerate((left0, left1)):
        lg, lh, lc = left[..., 0], left[..., 1], left[..., 2]
        rg, rh, rc = total_g - lg, total_h - lh, total_cnt - lc
        ok = (lc >= min_data) & (rc >= min_data) & \
             (lh >= min_hess) & (rh >= min_hess)
        # threshold t must leave at least one real bin on the right
        tpos = jnp.arange(Bmax, dtype=jnp.int32)[None, :]
        ok &= tpos < (meta.nbins[:, None] - 1)
        ok &= meta.valid_slot
        ok &= ~meta.is_categorical[:, None]
        if feature_mask is not None:
            ok &= feature_mask[:, None]
        if lane == 1:
            ok &= has_missing[:, None]
        if constraint is not None:
            lo_ = leaf_output(lg, lh, l1, l2, max_delta)
            ro_ = leaf_output(rg, rh, l1, l2, max_delta)
            lo_ = jnp.clip(lo_, constraint[0], constraint[1])
            ro_ = jnp.clip(ro_, constraint[0], constraint[1])
            mono = meta.monotone[:, None]
            ok &= ~(((mono > 0) & (lo_ > ro_)) | ((mono < 0) & (lo_ < ro_)))
            gain = (leaf_gain_given_output(lg, lh, l1, l2, lo_)
                    + leaf_gain_given_output(rg, rh, l1, l2, ro_))
        else:
            gain = (leaf_gain(lg, lh, l1, l2, max_delta)
                    + leaf_gain(rg, rh, l1, l2, max_delta))
        gain = jnp.where(ok, gain, -jnp.inf)
        results.append((gain, lg, lh, lc, rg, rh, rc))

    gain_shift = leaf_gain(total_g, total_h, l1, l2, max_delta) + min_gain
    g0, g1 = results[0][0], results[1][0]
    per_f = jnp.stack([g0, g1], axis=1).reshape(F, 2 * Bmax)  # lane-major
    best_flat = jnp.argmax(per_f, axis=1)  # [F]
    lane_b = best_flat // Bmax
    t_b = best_flat % Bmax
    best_gain = jnp.take_along_axis(per_f, best_flat[:, None], axis=1)[:, 0]

    def pick(a0, a1):
        stack = jnp.stack([a0, a1])  # [2, F, Bmax]
        return stack[lane_b, rows, t_b]

    lg = pick(results[0][1], results[1][1])
    lh = pick(results[0][2], results[1][2])
    lc = pick(results[0][3], results[1][3])
    rg = pick(results[0][4], results[1][4])
    rh = pick(results[0][5], results[1][5])
    rc = pick(results[0][6], results[1][6])

    is_valid = jnp.isfinite(best_gain) & (best_gain > gain_shift)
    out_gain = jnp.where(is_valid, best_gain - gain_shift, -jnp.inf)
    if penalty is not None:
        out_gain = jnp.where(is_valid, out_gain - penalty, -jnp.inf)
    lout = leaf_output(lg, lh, l1, l2, max_delta)
    rout = leaf_output(rg, rh, l1, l2, max_delta)
    if constraint is not None:
        lout = jnp.clip(lout, constraint[0], constraint[1])
        rout = jnp.clip(rout, constraint[0], constraint[1])
    zeros = jnp.zeros_like(out_gain)
    # default_left lane semantics: lane 1 sends the missing bin left
    return jnp.stack([
        out_gain,
        jnp.where(is_valid, rows.astype(jnp.float32), -1.0),
        t_b.astype(jnp.float32),
        lane_b.astype(jnp.float32),
        lg, lh, lc, rg, rh, rc, lout, rout, zeros, zeros,
    ], axis=1)


def class_records(hist: jax.Array, totals: jax.Array, wc: WidthClass,
                  params: jax.Array, feature_mask: Optional[jax.Array] = None,
                  constraint: Optional[jax.Array] = None,
                  penalty: Optional[jax.Array] = None) -> jax.Array:
    """per_feature_best's records of one width class: its features gathered
    and fixed at the class's width, the `feature` field the dense index."""
    fh = gather_feature_hist(hist, wc.meta, totals)  # [F_c, w, 3]
    recs = per_feature_best(
        fh, totals, wc.meta, params,
        None if feature_mask is None else feature_mask[wc.ids], constraint,
        None if penalty is None else penalty[wc.ids])
    return recs.at[:, 1].set(jnp.where(recs[:, 1] >= 0,
                                       wc.ids.astype(jnp.float32), -1.0))


# A categorical split's left set travels in its record as bin-space bitset
# words of CAT_WORD_BITS bits each, appended after SPLIT_FIELDS: a word is
# below 2**16, exact in float32 and through a HIGHEST-precision matmul, so
# the whole-tree program routes rows by the very set its scan chose and the
# host builds the node from it (no re-derivation from a histogram row).
CAT_WORD_BITS = 16


def cat_words(max_bins: int) -> int:
    """Bitset words a record carries for features of up to max_bins bins."""
    return -(-max_bins // CAT_WORD_BITS)


def split_record_width(meta) -> int:
    """Fields of the records find_best_split returns for this meta:
    SPLIT_FIELDS, and the left-set words where any feature is categorical."""
    extra = cat_words(meta.max_bins) if meta.has_categorical else 0
    return len(SPLIT_FIELDS) + extra


def pad_cat_words(recs: jax.Array, n_words: int) -> jax.Array:
    """Numerical [..., len(SPLIT_FIELDS)] records widened with empty
    left-set words, to sit beside categorical ones."""
    pad = [(0, 0)] * (recs.ndim - 1) + [(0, n_words)]
    return jnp.pad(recs, pad)


def bitset_word_bins(words) -> List[int]:
    """The bins a record's left-set words hold, ascending."""
    out = []
    for w, word in enumerate(np.asarray(words, dtype=np.float64)):
        v = int(round(float(word)))
        out.extend(w * CAT_WORD_BITS + b for b in range(CAT_WORD_BITS)
                   if (v >> b) & 1)
    return out


def _group_walk(reach: jax.Array, cum_cnt: jax.Array,
                min_group: jax.Array) -> jax.Array:
    """min_data_per_group throttling, exactly: the source keeps a count of
    the rows accumulated since the last prefix it EVALUATED and skips a
    prefix (without resetting it) until that count holds min_group rows.
    reach [F, S]: prefixes that pass every other check of the loop; cum_cnt
    [F, S]: left rows of each prefix. An elementwise recurrence over the S
    prefixes (XLA fuses it into one pass over [F])."""
    last = jnp.zeros(cum_cnt.shape[:-1], cum_cnt.dtype)
    out = []
    for i in range(cum_cnt.shape[-1]):
        hit = reach[..., i] & (cum_cnt[..., i] - last >= min_group)
        last = jnp.where(hit, cum_cnt[..., i], last)
        out.append(hit)
    return jnp.stack(out, axis=-1)


def per_feature_best_categorical(fh: jax.Array, totals: jax.Array,
                                 meta: FeatureMeta, params: jax.Array,
                                 feature_mask: Optional[jax.Array] = None,
                                 constraint: Optional[jax.Array] = None,
                                 penalty: Optional[jax.Array] = None
                                 ) -> jax.Array:
    """Best categorical split per feature: [F, len(SPLIT_FIELDS) + W]
    records, the last W fields the left set's bitset words (cat_words).

    Counterpart of FindBestThresholdCategoricalInner
    (src/treelearner/feature_histogram.cpp:147-241):

      * bin 0 (NaN, negative values and the categories past the mapper's
        99 % cut) is never a candidate: the source scans from bin 1;
      * one-hot when num_bin <= max_cat_to_onehot: every single bin is a
        left-set candidate (plain lambda_l2);
      * sorted-subset otherwise: bins with count >= cat_smooth, ordered by
        grad/(hess + cat_smooth) (stable), scanned as prefixes from both
        ends up to min(max_cat_threshold, (used+1)/2) categories, with
        lambda_l2+cat_l2, the split gain shifted by the parent's gain at
        plain lambda_l2, and min_data_per_group throttling as the source
        counts it (_group_walk).

    Bin counts come from the histogram's exact count channel (the reference
    reconstructs them as RoundInt(hess * num_data / sum_hessian)).
    """
    l1, l2, min_data, min_hess, min_gain, max_delta = (
        params[0], params[1], params[2], params[3], params[4], params[5])
    max_onehot, max_cat_thresh = params[6], params[7]
    cat_l2, cat_smooth, min_group = params[8], params[9], params[10]
    F, Bmax, _ = fh.shape
    n_words = cat_words(Bmax)
    rows = jnp.arange(F, dtype=jnp.int32)
    total_g, total_h, total_cnt = totals[0], totals[1], totals[2]
    gain_shift = leaf_gain(total_g, total_h, l1, l2, max_delta) + min_gain
    neg_inf = jnp.float32(-jnp.inf)
    eps = jnp.float32(K_EPSILON)
    bin_ids = jnp.arange(Bmax, dtype=jnp.int32)

    g, h, c = fh[..., 0], fh[..., 1], fh[..., 2]
    bin_valid = (meta.valid_slot & (bin_ids[None, :] < meta.nbins[:, None])
                 & (bin_ids[None, :] >= 1))

    # ---- one-hot lane (each bin alone goes left)
    other_h = total_h - h - eps
    other_c = total_cnt - c
    ok1 = bin_valid & (c >= min_data) & (h >= min_hess) & \
        (other_c >= min_data) & (other_h >= min_hess)
    gain1 = (leaf_gain(total_g - g, other_h, l1, l2, max_delta)
             + leaf_gain(g, h + eps, l1, l2, max_delta))
    gain1 = jnp.where(ok1, gain1, neg_inf)
    onehot_t = jnp.argmax(gain1, axis=1)
    onehot_gain = jnp.take_along_axis(gain1, onehot_t[:, None], axis=1)[:, 0]
    onehot_lg = g[rows, onehot_t]
    onehot_lh = h[rows, onehot_t] + eps
    onehot_lc = c[rows, onehot_t]

    # ---- sorted-subset lane
    l2c = l2 + cat_l2
    eligible = bin_valid & (c >= cat_smooth)
    ctr = jnp.where(eligible, g / (h + cat_smooth), jnp.inf)
    order = jnp.argsort(ctr, axis=1, stable=True)  # eligible first (asc)
    used = eligible.sum(axis=1)  # [F]
    sg = jnp.take_along_axis(g, order, axis=1)
    sh = jnp.take_along_axis(h, order, axis=1)
    sc = jnp.take_along_axis(c, order, axis=1)
    max_num_cat = jnp.minimum(max_cat_thresh, (used + 1) // 2)  # [F]
    # no prefix is longer than half the eligible bins (bin 0 is never one)
    # nor than max_cat_threshold, where the meta knows it statically
    S = max(1, Bmax // 2)
    if getattr(meta, "cat_scan_len", 0):
        S = min(S, meta.cat_scan_len)

    def direction_scan(sgd, shd, scd):
        """Prefix scan in sorted order; returns (best_gain, best_len, best
        left stats) per feature. sgd/shd/scd: [F, S] stats in scan order."""
        clg = jnp.cumsum(sgd, axis=1)
        clh = jnp.cumsum(shd, axis=1) + eps
        clc = jnp.cumsum(scd, axis=1)
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        in_range = (pos < used[:, None]) & (pos < max_num_cat[:, None])
        rh = total_h - clh
        rc = total_cnt - clc
        # the source's `continue` on the left side, `break` on the right
        # (monotone in the prefix, so a break is a mask)
        reach = in_range & (clc >= min_data) & (clh >= min_hess) & \
            (rc >= min_data) & (rc >= min_group) & (rh >= min_hess)
        ok = _group_walk(reach, clc, min_group)
        gains = (leaf_gain(clg, clh, l1, l2c, max_delta)
                 + leaf_gain(total_g - clg, rh, l1, l2c, max_delta))
        gains = jnp.where(ok, gains, neg_inf)
        best_i = jnp.argmax(gains, axis=1)
        best_gain = jnp.take_along_axis(gains, best_i[:, None], axis=1)[:, 0]
        blg = clg[rows, best_i]
        blh = clh[rows, best_i]
        blc = clc[rows, best_i]
        return best_gain, best_i + 1, blg, blh, blc

    fwd = direction_scan(sg[:, :S], sh[:, :S], sc[:, :S])
    # backward lane: reversal puts the ineligible (inf-keyed) padding first,
    # so roll each row back by (Bmax - used) to start at the LAST eligible bin
    shift = (Bmax - used)[:, None]
    idx = (jnp.arange(S, dtype=jnp.int32)[None, :] + shift) % Bmax
    bwd_stats = tuple(jnp.take_along_axis(a, idx, axis=1)
                      for a in (sg[:, ::-1], sh[:, ::-1], sc[:, ::-1]))
    bwd = direction_scan(*bwd_stats)

    use_onehot = meta.nbins <= max_onehot
    lanes_gain = jnp.stack([
        jnp.where(use_onehot, onehot_gain, neg_inf),
        jnp.where(use_onehot, neg_inf, fwd[0]),
        jnp.where(use_onehot, neg_inf, bwd[0]),
    ], axis=1)  # [F, 3]
    lane = jnp.argmax(lanes_gain, axis=1)
    best_gain = jnp.take_along_axis(lanes_gain, lane[:, None], axis=1)[:, 0]

    def pick(a_one, a_fwd, a_bwd):
        stack = jnp.stack([a_one, a_fwd, a_bwd], axis=1)
        return stack[rows, lane]

    thresh = pick(onehot_t.astype(jnp.float32),
                  fwd[1].astype(jnp.float32), bwd[1].astype(jnp.float32))
    lg = pick(onehot_lg, fwd[2], bwd[2])
    lh = pick(onehot_lh, fwd[3], bwd[3])
    lc = pick(onehot_lc, fwd[4], bwd[4])
    cat_dir = pick(jnp.zeros(F, dtype=jnp.float32), jnp.ones(F, dtype=jnp.float32),
                   -jnp.ones(F, dtype=jnp.float32))
    l2_eff = jnp.where(lane == 0, l2, l2c)

    # ---- the left set itself: sorted positions [lo, hi) of the lane's
    # order (the identity for one-hot), as bitset words in bin space
    k = thresh.astype(jnp.int32)
    lo = jnp.where(lane == 0, onehot_t, jnp.where(lane == 1, 0, used - k))
    hi = jnp.where(lane == 0, onehot_t + 1, jnp.where(lane == 1, k, used))
    bin_at = jnp.where((lane == 0)[:, None], bin_ids[None, :], order)
    member = (bin_ids[None, :] >= lo[:, None]) & (bin_ids[None, :] < hi[:, None])
    bit = jnp.where(member, jnp.left_shift(1, bin_at % CAT_WORD_BITS), 0)
    word_of = bin_at // CAT_WORD_BITS
    words = jnp.stack([jnp.sum(jnp.where(word_of == w, bit, 0), axis=1)
                       for w in range(n_words)], axis=1)  # [F, W]

    rg, rh, rc = total_g - lg, total_h - lh, total_cnt - lc
    is_valid = (meta.is_categorical & jnp.isfinite(best_gain)
                & (best_gain > gain_shift))
    if feature_mask is not None:
        is_valid &= feature_mask
    out_gain = jnp.where(is_valid, best_gain - gain_shift, neg_inf)
    if penalty is not None:
        out_gain = jnp.where(is_valid, out_gain - penalty, neg_inf)
    lout = leaf_output(lg, lh, l1, l2_eff, max_delta)
    rout = leaf_output(rg, rh, l1, l2_eff, max_delta)
    if constraint is not None:
        lout = jnp.clip(lout, constraint[0], constraint[1])
        rout = jnp.clip(rout, constraint[0], constraint[1])
    return jnp.concatenate([jnp.stack([
        out_gain,
        jnp.where(is_valid, rows.astype(jnp.float32), -1.0),
        thresh,
        jnp.zeros(F, dtype=jnp.float32),  # default_left = false (CategoricalDecision)
        lg, lh, lc, rg, rh, rc, lout, rout,
        jnp.ones(F, dtype=jnp.float32), cat_dir,
    ], axis=1), words.astype(jnp.float32)], axis=1)


def bins_to_bitset(values: List[int]) -> List[int]:
    """Pack non-negative ints into 32-bit bitset words (Common::ConstructBitset)."""
    vals = [v for v in values if v >= 0]
    if not vals:
        return [0]
    words = [0] * (max(vals) // 32 + 1)
    for v in vals:
        words[v // 32] |= 1 << (v % 32)
    return words


def reduce_best_record(recs: jax.Array) -> jax.Array:
    """[K, len(SPLIT_FIELDS)] -> [len(SPLIT_FIELDS)] by max gain (ties: first,
    matching the reference's SplitInfo operator> sweep order)."""
    return recs[jnp.argmax(recs[:, 0])]


@partial(jax.jit, static_argnames=())
def find_best_split(hist: jax.Array, totals: jax.Array, meta: FeatureMeta,
                    params: jax.Array,
                    feature_mask: Optional[jax.Array] = None,
                    constraint: Optional[jax.Array] = None,
                    penalty: Optional[jax.Array] = None) -> jax.Array:
    """Best split across all features for one leaf.

    hist:   [G, Bg, 3] group histogram for the leaf
    totals: [3] leaf (sum_grad, sum_hess, count)
    feature_mask: optional [F] bool (ColSampler / interaction constraints)
    constraint: optional [2] (min, max) output bounds (monotone constraints)
    penalty: optional [F] per-feature gain penalty (CEGB)
    Returns packed split record [split_record_width(meta)] float32.
    """
    if meta.width_classes is not None:  # static: the layout has a bundle
        with jax.named_scope(SCOPE_BUNDLE_SCAN):
            wcs = meta.width_classes
            recs = jnp.concatenate([
                class_records(hist, totals, wc, params, feature_mask,
                              constraint, penalty) for wc in wcs.classes])
            return reduce_best_record(recs[wcs.order])
    fh = gather_feature_hist(hist, meta, totals)  # [F, Bmax, 3]
    recs = per_feature_best(fh, totals, meta, params, feature_mask,
                            constraint, penalty)
    if meta.has_categorical:  # static flag: skip the scan entirely otherwise
        with jax.named_scope(SCOPE_CAT_SCAN):
            cat_recs = per_feature_best_categorical(
                fh, totals, meta, params, feature_mask, constraint, penalty)
            recs = jnp.concatenate(
                [pad_cat_words(recs, cat_words(fh.shape[1])), cat_recs])
    return reduce_best_record(recs)
