"""Binned training dataset: host construction, device-resident bin matrix.

Counterpart of the reference Dataset/FeatureGroup/DatasetLoader
(include/LightGBM/dataset.h:487-1070, src/io/dataset.cpp,
src/io/dataset_loader.cpp), redesigned for TPU execution:

  * The reference stores column-major per-group Bin objects (dense 4/8/16/32
    bit, sparse delta-encoded) chosen per sparsity. On TPU the histogram
    kernel is a batched one-hot contraction on the MXU (ops/histogram.py), so
    the canonical layout is ONE dense packed matrix `bins[num_groups, N]`
    (uint8/uint16) resident in HBM — the analog of CUDARowData/CUDAColumnData
    (include/LightGBM/cuda/cuda_row_data.hpp) rather than the CPU bins.
  * Feature bundling (EFB, dataset.cpp:111-366) packs mutually-exclusive
    sparse features into one column; bundled features omit their default bin
    (reconstructed from leaf totals at split time, mirroring the reference's
    most_freq_bin/FixHistogram trick, dataset.h:770).
  * Bin mapping runs on host over a sample (DatasetLoader::ConstructFromSampleData,
    dataset_loader.cpp:600), then the whole matrix is binned vectorized and
    shipped to device once.

Construction entry points mirror the C-API surface: from numpy/CSR matrices
or text files (io/parser.py).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .binning import (BIN_TYPE_CATEGORICAL, BIN_TYPE_NUMERICAL, BinMapper)
from .metadata import Metadata
from ..common import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from .. import tracing
from ..config import Config
from ..utils.log import Log
from ..utils.timer import global_timer


class FeatureGroup:
    """One packed bin column — a single feature or an EFB bundle.

    Mirrors include/LightGBM/feature_group.h:26: per-member bin offsets within
    the group's bin range. For bundles (is_multi), each member's default bin is
    omitted; group bin 0 means "all members at default".
    """

    def __init__(self, feature_indices: List[int], mappers: List[BinMapper],
                 is_multi: bool) -> None:
        self.feature_indices = feature_indices
        self.mappers = mappers
        self.is_multi = is_multi
        if not is_multi:
            self.num_total_bin = mappers[0].num_bin
            self.bin_offsets = [0]
        else:
            # bundle: slot 0 = all-default; member j owns
            # [offset_j, offset_j + num_bin_j - 1) (its default bin removed)
            self.num_total_bin = 1
            self.bin_offsets = []
            for m in mappers:
                self.bin_offsets.append(self.num_total_bin)
                self.num_total_bin += m.num_bin - 1

    def bin_for_feature(self, member_idx: int, raw_bins: np.ndarray) -> np.ndarray:
        """Group-space bins for one member's per-feature bins."""
        if not self.is_multi:
            return raw_bins
        m = self.mappers[member_idx]
        off = self.bin_offsets[member_idx]
        out = np.zeros_like(raw_bins)
        nondef = raw_bins != m.default_bin
        # bins above the default shift down one slot (default removed)
        shifted = raw_bins - (raw_bins > m.default_bin).astype(raw_bins.dtype)
        out[nondef] = off + shifted[nondef]
        return out

    def feature_bin_range(self, member_idx: int) -> Tuple[int, int, int]:
        """(group_bin_lo, group_bin_hi, default_bin) for split translation."""
        if not self.is_multi:
            return 0, self.num_total_bin, -1
        m = self.mappers[member_idx]
        off = self.bin_offsets[member_idx]
        return off, off + m.num_bin - 1, m.default_bin


BIN_THREADS = min(8, os.cpu_count() or 1)  # threads binning sparse groups


def _is_sparse(data) -> bool:
    """scipy.sparse duck-check (no hard scipy dependency)."""
    return hasattr(data, "tocsc") and hasattr(data, "nnz")


def _canonical(mat):
    """A CSC or CSR matrix with sorted indices and no duplicate entries
    (duplicates summed, as toarray() sums them); the input is not edited."""
    if not mat.has_canonical_format:
        mat = mat.copy()
        mat.sum_duplicates()
    return mat


def _column(data: np.ndarray, j: int) -> np.ndarray:
    """Column j of a dense matrix. Sparse input never comes here: it is
    read through its entries (`_csc_column`)."""
    return data[:, j]


def _csc_column(csc, j: int) -> Tuple[np.ndarray, np.ndarray]:
    """(rows, float64 values) of the entries column j of a canonical CSC
    matrix stores: O(its entries), never a dense column."""
    s, e = csc.indptr[j], csc.indptr[j + 1]
    return csc.indices[s:e], csc.data[s:e].astype(np.float64)


def _sample_for_binning(col: np.ndarray, sample_cnt: int, rng: np.random.RandomState) -> Tuple[np.ndarray, int]:
    """Sample values (keeping NaNs, dropping zeros implicitly like the
    reference's sparse sample push) for bin finding."""
    n = len(col)
    if n > sample_cnt:
        idx = rng.choice(n, sample_cnt, replace=False)
        sample = col[idx]
        total = sample_cnt
    else:
        sample = col
        total = n
    nonzero = sample[(sample != 0) | np.isnan(sample)]
    return nonzero, total


def find_feature_groups(mappers: List[BinMapper], sample_nonzero: List[np.ndarray],
                        sample_total: int, used_features: List[int],
                        max_conflict_rate: float, enable_bundle: bool,
                        max_bin_per_group: int = 256,
                        stats: Optional[dict] = None) -> List[List[int]]:
    """Exclusive Feature Bundling — greedy conflict-bounded grouping.

    Behavioral counterpart of GetConflictCount/FindGroups (dataset.cpp:64-249):
    sparse numerical features are visited in feature order; each joins the
    first existing bundle whose accumulated conflicts stay under
    max_conflict_rate * sample_total, else starts a new bundle. Conflicts
    are counted over the binning sample: `sample_nonzero[f]` holds the
    sample positions where feature f is non-zero and not NaN, and a bundle
    keeps a mark a position, so a feature costs O(its non-zeros) a bundle
    it tries. A row on which two members of a bundle are non-default
    keeps the LATER member's bin (Dataset._bin_rows): the earlier member
    reads as its default there. `stats`, where given, receives the
    sample's conflicts the bundles admitted (`sample_conflicts`).
    """
    if not enable_bundle or len(used_features) <= 1:
        return [[f] for f in used_features]
    dense: List[int] = []
    sparse_feats: List[int] = []
    for f in used_features:
        # bundling only pays for sparse features; dense ones keep own groups
        if mappers[f].sparse_rate >= 0.8 and mappers[f].bin_type == BIN_TYPE_NUMERICAL:
            sparse_feats.append(f)
        else:
            dense.append(f)
    if len(sparse_feats) <= 1:
        return [[f] for f in used_features]
    max_conflicts = int(max_conflict_rate * sample_total)
    groups: List[List[int]] = []
    group_marks: List[np.ndarray] = []
    group_conflicts: List[int] = []
    group_bins: List[int] = []
    for f in sparse_feats:
        rows = sample_nonzero[f]
        placed = False
        for gi in range(len(groups)):
            if group_bins[gi] + mappers[f].num_bin - 1 > max_bin_per_group:
                continue
            conflict = int(np.count_nonzero(group_marks[gi][rows]))
            if group_conflicts[gi] + conflict <= max_conflicts:
                groups[gi].append(f)
                group_marks[gi][rows] = True
                group_conflicts[gi] += conflict
                group_bins[gi] += mappers[f].num_bin - 1
                placed = True
                break
        if not placed:
            groups.append([f])
            mark = np.zeros(sample_total, dtype=bool)
            mark[rows] = True
            group_marks.append(mark)
            group_conflicts.append(0)
            group_bins.append(1 + mappers[f].num_bin - 1)
    if stats is not None:
        stats["sample_conflicts"] = int(sum(group_conflicts))
    out = [[f] for f in dense]
    out.extend(g for g in groups)
    # keep original feature order inside each bundle for determinism
    for g in out:
        g.sort()
    return out


class Dataset:
    """Binned dataset (internal core — the Python-facing wrapper with lazy
    construction lives in basic.py).

    Public state after construction:
      bins          : np.ndarray [num_groups, num_data] uint8/uint16
      groups        : List[FeatureGroup]
      feature_to_group : feature idx -> (group idx, member idx)
      mappers       : per-original-feature BinMapper
      metadata      : Metadata
    """

    def __init__(self, config: Optional[Config] = None) -> None:
        self.config = config or Config()
        self.num_data = 0
        self.num_total_features = 0
        self.mappers: List[BinMapper] = []
        self.groups: List[FeatureGroup] = []
        self.feature_to_group: Dict[int, Tuple[int, int]] = {}
        self.used_features: List[int] = []
        self.bins: Optional[np.ndarray] = None
        self.metadata = Metadata()
        self.feature_names: List[str] = []
        self.monotone_constraints: List[int] = []
        self._reference: Optional["Dataset"] = None

    # ------------------------------------------------------------ construction

    @classmethod
    def from_matrix(cls, data: np.ndarray, label=None, weight=None, group=None,
                    init_score=None, position=None,
                    config: Optional[Config] = None,
                    categorical_feature: Sequence[int] = (),
                    feature_names: Optional[Sequence[str]] = None,
                    reference: Optional["Dataset"] = None) -> "Dataset":
        config = config or Config()
        self = cls(config)
        if _is_sparse(data):
            # sparse path (DatasetLoader::ConstructFromSampleData with CSR
            # input, c_api.cpp LGBM_DatasetCreateFromCSR): the matrix stays
            # sparse and construction costs O(entries): the binning sample
            # is a row slice of the CSR, each group is binned from its
            # members' CSC entries, and no column is ever made dense
            if data.dtype not in (np.float32, np.float64):
                data = data.astype(np.float64)
        else:
            data = np.asarray(data)
            if data.dtype not in (np.float32, np.float64):
                data = data.astype(np.float64)
        n, f = data.shape
        self.num_data = n
        self.num_total_features = f
        self.metadata = Metadata(n)
        if label is not None:
            self.metadata.set_label(label)
        if weight is not None:
            self.metadata.set_weights(weight)
        if group is not None:
            self.metadata.set_query(group)
        if init_score is not None:
            self.metadata.set_init_score(init_score)
        if position is not None:
            self.metadata.set_positions(position)
        self.feature_names = (list(feature_names) if feature_names
                              else [f"Column_{i}" for i in range(f)])

        if reference is not None:
            # validation set: share the training BinMappers and group layout
            # (DatasetLoader::LoadFromFileAlignWithOtherDataset semantics)
            self._align_with(reference, data)
            return self

        with global_timer.scope("construct_fit"):
            group_lists = self._fit_layout(data, categorical_feature)
        self._build_groups_and_bins(group_lists, data)
        return self

    def _fit_layout(self, data, categorical_feature: Sequence[int] = ()
                    ) -> List[List[int]]:
        """Fit the bin layout (per-feature BinMappers, used features, EFB
        group lists) from `data` WITHOUT binning any rows. Split out of
        from_matrix so the streaming ingest path (streaming/ingest.py) can
        fit on a buffered sample prefix and then bin arbitrary row blocks
        through _bin_rows — concatenated block bins are identical to a
        one-shot construction over the same layout."""
        config = self.config
        n, f = data.shape
        self.num_total_features = f
        rng = np.random.RandomState(config.data_random_seed)
        sample_cnt = min(config.bin_construct_sample_cnt, n)
        cat_set = set(int(c) for c in categorical_feature)

        if config.max_bin_by_feature:
            # reference hard-checks these (dataset.cpp:416-420)
            if len(config.max_bin_by_feature) != f:
                Log.fatal("Size of max_bin_by_feature should be equal to max_feature_idx + 1")
            if min(config.max_bin_by_feature) <= 1:
                Log.fatal("max_bin_by_feature should be greater than 1")

        self.mappers = []
        sample_nonzero_rows: List[np.ndarray] = []
        sample_idx = (rng.choice(n, sample_cnt, replace=False)
                      if n > sample_cnt else np.arange(n))
        # sparse input: the sampled rows, sliced from the CSR and turned
        # into columns (O(the sample's entries))
        sample = (_canonical(data.tocsr()[sample_idx].tocsc())
                  if _is_sparse(data) else None)
        # forcedbins_filename (DatasetLoader::GetForcedBins,
        # src/io/dataset_loader.cpp): JSON [{"feature", "bin_upper_bound"}]
        forced_by_feature: Dict[int, List[float]] = {}
        if config.forcedbins_filename:
            import json as _json

            try:
                with open(config.forcedbins_filename) as fh:
                    for entry in _json.load(fh):
                        forced_by_feature[int(entry["feature"])] = [
                            float(v) for v in entry["bin_upper_bound"]]
            except OSError:
                Log.warning("Could not open %s", config.forcedbins_filename)
        for j in range(f):
            if sample is None:
                col = _column(data, j)[sample_idx]
                nonzero = col[(col != 0) | np.isnan(col)]
                nz_rows = np.flatnonzero((col != 0) & ~np.isnan(col))
            else:  # the same values: find_bin sorts them
                rows, vals = _csc_column(sample, j)
                nonzero = vals[(vals != 0) | np.isnan(vals)]
                nz_rows = rows[(vals != 0) & ~np.isnan(vals)]
            mapper = BinMapper()
            bt = BIN_TYPE_CATEGORICAL if j in cat_set else BIN_TYPE_NUMERICAL
            mb = config.max_bin
            if config.max_bin_by_feature and j < len(config.max_bin_by_feature):
                mb = config.max_bin_by_feature[j]
            mapper.find_bin(nonzero, len(sample_idx), mb,
                            min_data_in_bin=config.min_data_in_bin,
                            min_split_data=config.min_data_in_leaf,
                            pre_filter=config.feature_pre_filter,
                            bin_type=bt,
                            use_missing=config.use_missing,
                            zero_as_missing=config.zero_as_missing,
                            forced_upper_bounds=forced_by_feature.get(j, ()))
            self.mappers.append(mapper)
            sample_nonzero_rows.append(nz_rows)

        self.used_features = [j for j in range(f) if not self.mappers[j].is_trivial]
        if not self.used_features:
            Log.warning("There are no meaningful features which satisfy "
                        "the provided configuration. Decreasing Dataset parameters "
                        "min_data_in_bin or min_data_in_leaf and re-constructing "
                        "Dataset might resolve this warning.")

        stats = {"sample_conflicts": 0}
        group_lists = find_feature_groups(
            self.mappers, sample_nonzero_rows, len(sample_idx),
            self.used_features, self.config.max_conflict_rate,
            enable_bundle=self.config.enable_bundle, stats=stats)
        self._sample_conflicts = stats["sample_conflicts"]
        return group_lists

    def _build_groups_and_bins(self, group_lists: List[List[int]], data: np.ndarray) -> None:
        self._make_groups(group_lists)
        with global_timer.scope("construct_bin"):
            self.bins = self._bin_rows(data)
        self._note_layout(data)

    def _note_layout(self, data) -> None:
        """The `efb_layout` flight note of a construction, and the
        `efb_conflict_rows` counter: what bundling made of the table."""
        bundles = [g for g in self.groups if g.is_multi]
        global_timer.add_count("efb_conflict_rows", self.conflict_rows)
        tracing.note(
            "efb_layout", used_features=len(self.used_features),
            groups=len(self.groups), bundles=len(bundles),
            bundled_features=sum(len(g.feature_indices) for g in bundles),
            max_group_bins=max((g.num_total_bin for g in self.groups),
                               default=0),
            nnz=int(data.nnz if _is_sparse(data)
                    else data.shape[0] * data.shape[1]),
            sample_conflicts=int(getattr(self, "_sample_conflicts", 0)),
            conflict_rows=self.conflict_rows,
            aligned=int(self._reference is not None))

    def _make_groups(self, group_lists: List[List[int]]) -> None:
        """Materialize FeatureGroups + the feature->(group, member) map from
        fitted mappers; row-count independent (no bins touched)."""
        self.groups = []
        self.feature_to_group = {}
        for gi, feats in enumerate(group_lists):
            fg = FeatureGroup(feats, [self.mappers[j] for j in feats],
                              is_multi=len(feats) > 1)
            self.groups.append(fg)
            for mi, j in enumerate(feats):
                self.feature_to_group[j] = (gi, mi)

    def bins_dtype(self) -> np.dtype:
        max_bins = max((g.num_total_bin for g in self.groups), default=1)
        return np.dtype(np.uint8 if max_bins <= 256 else np.uint16)

    def _bin_rows(self, data) -> np.ndarray:
        """Bin an arbitrary row matrix against the FITTED layout into a
        [num_groups, n_rows] plane. Binning is per-row independent, so
        concatenating per-block planes equals one one-shot plane exactly —
        the invariant streaming ingest relies on. A bundle's row keeps the
        later member's bin where two members are non-default (the rows
        where that happened: `conflict_rows`). Sparse input is binned from
        its CSC entries alone: a group's rows start at the bin of 0 and
        only the stored entries are binned and placed."""
        n_rows = data.shape[0]
        dtype = self.bins_dtype()
        bins = np.zeros((len(self.groups), n_rows), dtype=dtype)
        self.conflict_rows = 0
        if _is_sparse(data):
            csc = _canonical(data.tocsc())
            # groups are independent: each binned into its own row of the
            # plane on a thread (numpy lets go of the GIL in the lookups
            # and the scatters); the counts are summed in the groups' order
            with ThreadPoolExecutor(min(BIN_THREADS, len(self.groups)) or 1
                                    ) as pool:
                self.conflict_rows = sum(pool.map(
                    lambda gi: self._bin_group_sparse(
                        self.groups[gi], csc, bins[gi]),
                    range(len(self.groups))))
            return bins
        for gi, fg in enumerate(self.groups):
            if not fg.is_multi:
                j = fg.feature_indices[0]
                bins[gi] = self.mappers[j].values_to_bins(
                    _column(data, j)).astype(dtype)
            else:
                acc = np.zeros(n_rows, dtype=np.int32)
                for mi, j in enumerate(fg.feature_indices):
                    raw = self.mappers[j].values_to_bins(_column(data, j))
                    gb = fg.bin_for_feature(mi, raw)
                    # exclusivity: at most one member non-default per row;
                    # on conflict the later feature wins (matches bundle
                    # push order semantics)
                    self.conflict_rows += int(np.count_nonzero(
                        (gb != 0) & (acc != 0)))
                    acc = np.where(gb != 0, gb, acc)
                bins[gi] = acc.astype(dtype)
        return bins

    def _bin_group_sparse(self, fg: FeatureGroup, csc,
                          acc: np.ndarray) -> int:
        """One group's row of the plane, `acc` (zeros), from the members'
        stored entries: what the dense loop above makes of the same matrix,
        bit for bit. Returns the group's count of conflicting rows."""
        conflicts = 0
        for mi, j in enumerate(fg.feature_indices):
            rows, vals = _csc_column(csc, j)
            mapper = self.mappers[j]
            zero_bin = fg.bin_for_feature(
                mi, mapper.values_to_bins(np.zeros(1)))[0]
            gb = fg.bin_for_feature(mi, mapper.values_to_bins(vals))
            if not fg.is_multi:
                acc[:] = zero_bin
                acc[rows] = gb
                continue
            before = acc[rows]
            if zero_bin != 0:
                acc[:] = zero_bin
            conflicts += int(np.count_nonzero((gb != 0) & (before != 0)))
            acc[rows] = np.where(gb != 0, gb, before)
        return conflicts

    @classmethod
    def from_layout(cls, layout: "Dataset", bins: np.ndarray, num_data: int,
                    label=None, weight=None, group=None, init_score=None,
                    position=None,
                    feature_names: Optional[Sequence[str]] = None) -> "Dataset":
        """Assemble a Dataset from a fitted layout prototype plus a
        pre-binned plane (streaming ingest: RowBlockStore.finalize). The
        layout's mappers/groups are shared, not copied."""
        self = cls(layout.config)
        self.num_data = int(num_data)
        self.num_total_features = layout.num_total_features
        self.mappers = layout.mappers
        self.groups = layout.groups
        self.feature_to_group = layout.feature_to_group
        self.used_features = layout.used_features
        self.monotone_constraints = list(layout.monotone_constraints)
        self.bins = bins
        self.metadata = Metadata(self.num_data)
        if label is not None:
            self.metadata.set_label(label)
        if weight is not None:
            self.metadata.set_weights(weight)
        if group is not None:
            self.metadata.set_query(group)
        if init_score is not None:
            self.metadata.set_init_score(init_score)
        if position is not None:
            self.metadata.set_positions(position)
        self.feature_names = (list(feature_names) if feature_names
                              else [f"Column_{i}"
                                    for i in range(self.num_total_features)])
        return self

    @classmethod
    def load_binary(cls, path: str,
                    config: Optional[Config] = None) -> "Dataset":
        """Rebuild a constructed Dataset from a save_binary npz cache
        (DatasetLoader::LoadFromBinFile analog): bins, mappers, groups, and
        metadata restore directly — no re-parse, no bin finding."""
        import json as _json

        from .binning import BinMapper

        z = np.load(path, allow_pickle=False)
        self = cls(config)
        self.bins = z["bins"]
        self.num_data = int(self.bins.shape[1])
        self.num_total_features = int(z["num_total_features"])
        self.mappers = [BinMapper.from_dict(d)
                        for d in _json.loads(str(z["mappers"]))]
        self.feature_names = _json.loads(str(z["feature_names"]))
        self.used_features = _json.loads(str(z["used_features"]))
        self.monotone_constraints = _json.loads(str(z["monotone"]))
        group_lists = _json.loads(str(z["group_lists"]))
        group_multi = _json.loads(str(z["group_is_multi"]))
        self.groups = []
        self.feature_to_group = {}
        for gi, (feats, multi) in enumerate(zip(group_lists, group_multi)):
            fg = FeatureGroup(feats, [self.mappers[f] for f in feats], multi)
            self.groups.append(fg)
            for mi, f in enumerate(feats):
                self.feature_to_group[f] = (gi, mi)
        self.metadata = Metadata(self.num_data)
        if z["label"].size:
            self.metadata.set_label(z["label"])
        if z["weight"].size:
            self.metadata.set_weights(z["weight"])
        if z["init_score"].size:
            self.metadata.set_init_score(z["init_score"])
        if z["query_boundaries"].size:
            qb = np.asarray(z["query_boundaries"], dtype=np.int32)
            self.metadata.query_boundaries = qb
        if z["positions"].size:
            self.metadata.positions = np.asarray(z["positions"], np.int32)
            self.metadata.position_ids = z["position_ids"]
        raw = z["raw"]
        self._loaded_raw = raw if raw.size else None  # single npz read
        return self

    def _align_with(self, reference: "Dataset", data) -> None:
        self._reference = reference
        self.mappers = reference.mappers
        self.used_features = reference.used_features
        self.monotone_constraints = reference.monotone_constraints
        group_lists = [g.feature_indices for g in reference.groups]
        self._build_groups_and_bins(group_lists, data)

    # ---------------------------------------------------------------- queries

    @property
    def num_features(self) -> int:
        return len(self.used_features)

    @property
    def num_groups(self) -> int:
        return len(self.groups)

    def group_bin_counts(self) -> np.ndarray:
        return np.array([g.num_total_bin for g in self.groups], dtype=np.int32)

    def feature_num_bin(self, feature: int) -> int:
        return self.mappers[feature].num_bin

    def feature_infos(self) -> List[str]:
        return [m.bin_info_string() for m in self.mappers]

    def real_threshold(self, feature: int, bin_threshold: int) -> float:
        """Bin-space threshold -> raw-value threshold for the model tree."""
        return self.mappers[feature].bin_to_value(bin_threshold)

    def subset(self, indices: np.ndarray) -> "Dataset":
        out = Dataset(self.config)
        out.num_data = len(indices)
        out.num_total_features = self.num_total_features
        out.mappers = self.mappers
        out.groups = self.groups
        out.feature_to_group = self.feature_to_group
        out.used_features = self.used_features
        out.bins = self.bins[:, indices]
        out.metadata = self.metadata.subset(indices)
        out.feature_names = self.feature_names
        out.monotone_constraints = self.monotone_constraints
        return out

    # ------------------------------------------------- reference hist (tests)

    def construct_histogram_np(self, group: int, grad: np.ndarray, hess: np.ndarray,
                               row_indices: Optional[np.ndarray] = None) -> np.ndarray:
        """Numpy reference histogram [(num_total_bin), 3] for one group —
        the oracle the device kernels are tested against."""
        fg = self.groups[group]
        bins = self.bins[group]
        if row_indices is not None:
            bins = bins[row_indices]
            grad = grad[row_indices]
            hess = hess[row_indices]
        hist = np.zeros((fg.num_total_bin, 3), dtype=np.float64)
        np.add.at(hist[:, 0], bins, grad)
        np.add.at(hist[:, 1], bins, hess)
        np.add.at(hist[:, 2], bins, 1.0)
        return hist
