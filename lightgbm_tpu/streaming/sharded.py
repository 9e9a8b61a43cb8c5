"""Pod-scale streaming: gang-sharded ingest, merged drift, psum learner.

Three pieces compose the single-device out-of-core stack (ingest.py,
learner.py, drift.py) into the parallel-and-stream regime of ROADMAP
item 3 — a dataset no single device could hold trains continuously
across an elastic gang:

  * `ShardedRowBlockStore` partitions pushed row blocks round-robin
    across shards, the placement pinned at push (`push_index % shards`).
    The caller's `LGBM_DatasetPushRows*` surface is unchanged — sharding
    is internal placement, not an API. Bin mappers are fitted from exact
    per-shard quantile sketches merged across ranks in RANK order
    (drift.merge_ranked) after one small allgather, so the cut points
    reflect the GLOBAL prefix distribution bit-identically no matter
    which shard saw which rows: the merged multiset is reconstructed
    into a surrogate prefix (sorted values scattered back to the true
    nonzero-row positions) and fed through the SAME Dataset._fit_layout
    a one-shot build runs, reproducing mappers AND the EFB group lists
    byte-for-byte whenever the sketches stay exact (k covers the prefix,
    the default here) and bin_sample_rows <= bin_construct_sample_cnt.
  * `PodDriftMonitor` fans DriftMonitor out per shard and merges the
    shard sketches + bin-occupancy windows across ranks at every drift
    check (both are mergeable by construction), so alarm decisions and
    the generation-fenced bin refresh are byte-identical across the
    gang. `reshard()` keeps retired shards' accumulations — only the
    MERGED state is observable, so shrink-to-fit resume stays exact.
  * `ShardedStreamedTreeLearner` shards the device block cache across
    the gang (`block % shards`), giving the fleet D x the single-device
    LGBM_TPU_HBM_BUDGET of resident bins, and merges quantized per-leaf
    histograms with the same psum-over-"data" reduction the resident
    data-parallel learner uses — int32 accumulation makes the merge
    exact under any summation order, so training is bit-identical to the
    single-device streamed learner at matched data order. Float (plain /
    bagged) histograms keep the parent's canonical chunk-order fold
    unchanged: a float psum would reassociate partial sums, and the
    sharding only moves block PLACEMENT, never the numeric sequence.
"""
from __future__ import annotations

import io as _io
from time import perf_counter
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from ..io.dataset import Dataset as CoreDataset
from ..parallel.mesh import data_mesh
from ..utils.log import Log
from ..utils.timer import global_timer
from .. import telemetry
from .drift import DriftMonitor, QuantileSketch, merge_ranked
from .ingest import RowBlockStore
from .learner import StreamedTreeLearner, _BlockCache


# --------------------------------------------------------- gang transport

def _gang_world() -> int:
    try:
        return int(jax.process_count())
    except Exception:  # noqa: BLE001 - backend not initialized yet
        return 1


def _allgather_bytes(payload: bytes) -> List[bytes]:
    """Gather one opaque byte payload from every process, in rank order.

    Single-process returns [payload] without touching the backend. The
    multi-process path pads every rank's payload to the gathered max
    length (allgather needs equal shapes) and prefixes the true length.
    """
    world = _gang_world()
    if world <= 1:
        return [payload]
    from jax.experimental import multihost_utils

    # graftlint: disable=collective-order -- process_count() is uniform across the gang: every rank takes the same arm together, and both allgathers below run unconditionally on that arm in the same order
    length = np.array([len(payload)], dtype=np.int64)
    lengths = np.asarray(multihost_utils.process_allgather(length)).reshape(-1)
    max_len = int(lengths.max())
    buf = np.zeros(max_len, dtype=np.uint8)
    buf[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    gathered = np.asarray(multihost_utils.process_allgather(buf))
    gathered = gathered.reshape(world, max_len)
    return [gathered[r, : int(lengths[r])].tobytes() for r in range(world)]


def _pack_arrays(arrays: Dict[str, np.ndarray]) -> bytes:
    out = _io.BytesIO()
    np.savez(out, **arrays)
    return out.getvalue()


def _unpack_arrays(payload: bytes) -> Dict[str, np.ndarray]:
    with np.load(_io.BytesIO(payload), allow_pickle=False) as z:
        return {k: np.asarray(z[k]) for k in z.files}


def _sketch_to_arrays(sk: QuantileSketch, prefix: str,
                      arrays: Dict[str, np.ndarray]) -> None:
    arrays[prefix + "meta"] = np.array(
        [sk.k, sk.nonzero_n, sk.zero_n, sk.nan_n, sk._parity, len(sk.levels)],
        dtype=np.int64)
    for i, lv in enumerate(sk.levels):
        arrays[f"{prefix}lv{i}"] = np.asarray(lv, dtype=np.float64)


def _sketch_from_arrays(prefix: str, arrays: Dict[str, np.ndarray]
                        ) -> Optional[QuantileSketch]:
    meta = arrays.get(prefix + "meta")
    if meta is None:
        return None
    k, nonzero_n, zero_n, nan_n, parity, n_levels = (int(v) for v in meta)
    sk = QuantileSketch(k)
    sk.levels = [np.asarray(arrays[f"{prefix}lv{i}"], dtype=np.float64)
                 for i in range(n_levels)]
    sk.nonzero_n, sk.zero_n, sk.nan_n = nonzero_n, zero_n, nan_n
    sk._parity = parity
    return sk


# ------------------------------------------------------------- pod drift

class PodDriftMonitor(DriftMonitor):
    """DriftMonitor fanned out per shard with rank-ordered gang merges.

    Blocks route to per-shard child monitors in lockstep with the
    store's round-robin placement; the pod keeps the check cadence.
    At each check (and each refit) the shard sketches fold through
    drift.merge_ranked and the shard occupancy windows sum in rank
    order, so the merged state — and every alarm / refreshed cut point
    derived from it — is a pure function of the pushed stream,
    byte-identical across ranks and across reruns.
    """

    def __init__(self, proto: DriftMonitor, num_shards: int) -> None:
        super().__init__(proto.config, sorted(proto.categorical),
                         threshold=proto.threshold,
                         check_rows=proto.check_rows,
                         sketch_k=proto.sketch_k)
        self.num_shards = max(1, int(num_shards))
        # children never self-check: the pod owns the cadence
        self._children = [
            DriftMonitor(proto.config, sorted(proto.categorical),
                         threshold=proto.threshold, check_rows=2 ** 62,
                         sketch_k=proto.sketch_k)
            for _ in range(self.num_shards)]
        self._push_i = 0
        self._merged_dirty = True

    # ------------------------------------------------------------ routing

    def observe(self, block: np.ndarray, layout) -> None:
        child = self._children[self._push_i % self.num_shards]
        self._push_i += 1
        child.observe(block, layout)
        self._merged_dirty = True
        if layout is not None:
            self._layout = layout
            self._rows_since_check += block.shape[0]
            if self._rows_since_check >= self.check_rows:
                self._merge_shards()
                self._check()

    def set_reference(self, layout, prefix: np.ndarray) -> None:
        super().set_reference(layout, prefix)
        for child in self._children:
            # the (global) ref content is inert in children — their
            # _check never runs — but its keys define which features the
            # child's _cur occupancy window accumulates
            child.set_reference(layout, prefix)

    def after_refresh(self, layout) -> None:
        self._merge_shards()
        super().after_refresh(layout)
        for child in self._children:
            child.after_refresh(layout)

    def refit_mapper(self, j: int, mapper):
        self._merge_shards()
        nm = super().refit_mapper(j, mapper)
        if j < len(self.sketches) and self.sketches[j] is not None \
                and self.sketches[j].nonzero_n == 0:
            # super() discarded a corrupt merged sketch; drop the shard
            # copies too or the garbage re-merges at the next check
            for child in self._children:
                if j < len(child.sketches) and child.sketches[j] is not None \
                        and not child.sketches[j].healthy():
                    child.sketches[j] = QuantileSketch(self.sketch_k)
        return nm

    def reshard(self, num_shards: int) -> None:
        """Shrink-to-fit: future blocks route over the surviving shard
        count; retired children keep their accumulations (only the
        rank-ordered MERGE is observable, so history stays exact)."""
        self.num_shards = max(1, int(num_shards))
        while len(self._children) < self.num_shards:
            ref = self._children[0]
            self._children.append(
                DriftMonitor(ref.config, sorted(ref.categorical),
                             threshold=ref.threshold, check_rows=2 ** 62,
                             sketch_k=ref.sketch_k))
        self._merged_dirty = True

    # -------------------------------------------------------------- merge

    def _shard_payload(self, rank: int) -> bytes:
        child = self._children[rank]
        arrays: Dict[str, np.ndarray] = {"rank": np.array([rank])}
        for j, sk in enumerate(child.sketches):
            if sk is not None:
                _sketch_to_arrays(sk, f"sk{j}_", arrays)
        for j, cur in child._cur.items():
            arrays[f"cur{j}"] = np.asarray(cur, dtype=np.float64)
        return _pack_arrays(arrays)

    def _merge_shards(self) -> None:
        """Fold the shard sketches and occupancy windows into the pod's
        own state, in rank order. Multi-process, rank r is authoritative
        for shard r and one allgather rebuilds the full set everywhere;
        single-process the 'gather' is a local walk over the children."""
        if not self._merged_dirty:
            return
        world = _gang_world()
        t0 = perf_counter()
        if world > 1:
            my = int(jax.process_index())
            payloads = _allgather_bytes(
                self._shard_payload(my % self.num_shards))
        else:
            payloads = [self._shard_payload(r)
                        for r in range(self.num_shards)]
        shards = [_unpack_arrays(p) for p in payloads]
        n_feat = max((len(c.sketches) for c in self._children), default=0)
        merged: List[Optional[QuantileSketch]] = []
        for j in range(n_feat):
            pairs = []
            for arrays in shards:
                sk = _sketch_from_arrays(f"sk{j}_", arrays)
                if sk is not None:
                    pairs.append((int(arrays["rank"][0]), sk))
            merged.append(merge_ranked(pairs) if pairs else None)
        self.sketches = merged
        for j in list(self._cur):
            acc = np.zeros_like(self._cur[j])
            for arrays in shards:  # rank order: payloads land rank-sorted
                cur = arrays.get(f"cur{j}")
                if cur is not None:
                    acc += cur
            self._cur[j] = acc
        self._merged_dirty = False
        global_timer.set_count("stream_sketch_merge_us",
                               int((perf_counter() - t0) * 1e6))
        global_timer.add_count("stream_sketch_merges", 1)


# ---------------------------------------------------------- sharded store

class ShardedRowBlockStore(RowBlockStore):
    """RowBlockStore with round-robin block placement across a gang.

    The push surface (and therefore LGBM_DatasetPushRows* C-API parity)
    is byte-identical to the base store: every block is binned into the
    same global plane in push order, so finalize() snapshots are
    indistinguishable from the single-shard build. What sharding adds:

      * placement pinned at push (`push_index % num_shards`) with
        per-shard row watermarks (`shard_rows`),
      * a bin-layout fit from rank-merged exact sketches instead of the
        raw prefix (see module docstring for the equality argument),
      * the PodDriftMonitor gang merge for drift + bin refresh,
      * `reshard()` for shrink-to-fit resume after a lost worker.
    """

    def __init__(self, *args, num_shards: Optional[int] = None,
                 **kwargs) -> None:
        super().__init__(*args, **kwargs)
        self._num_shards_req = num_shards
        self._num_shards: Optional[int] = None
        self._block_owner: List[int] = []
        self._block_nrows: List[int] = []
        if self._drift is not None:
            self._drift = PodDriftMonitor(self._drift, self.num_shards)

    @property
    def num_shards(self) -> int:
        if self._num_shards is None:
            if self._num_shards_req is not None:
                self._num_shards = max(1, int(self._num_shards_req))
            elif _gang_world() > 1:
                self._num_shards = _gang_world()
            else:
                self._num_shards = int(
                    data_mesh(self.config.num_machines).devices.size)
        return self._num_shards

    # ------------------------------------------------------------- push

    def push_rows(self, data, label=None, weight=None):
        block_rows = (np.asarray(data).shape[0]
                      if np.asarray(data).ndim == 2 else 1)
        with self._lock:
            self._block_owner.append(len(self._block_owner)
                                     % self.num_shards)
            self._block_nrows.append(int(block_rows))
        return super().push_rows(data, label=label, weight=weight)

    def shard_rows(self, rank: int) -> int:
        """Per-shard row watermark: rows pushed into shard `rank` so far
        (same monotone semantics the continuous trainer pins globally)."""
        with self._lock:
            return sum(n for o, n in zip(self._block_owner,
                                         self._block_nrows) if o == rank)

    def reshard(self, num_shards: int) -> None:
        """Re-shard after the gang shrank: surviving ranks re-take the
        pinned placements round-robin over the new world. The plane and
        merged drift state are placement-independent, so a resumed refit
        stays byte-identical."""
        with self._lock:
            self._num_shards = max(1, int(num_shards))
            self._num_shards_req = self._num_shards
            self._block_owner = [i % self._num_shards
                                 for i in range(len(self._block_owner))]
            if isinstance(self._drift, PodDriftMonitor):
                self._drift.reshard(self._num_shards)
        Log.info("streaming: re-sharded block store over %d shards",
                 self._num_shards)

    # -------------------------------------------------------------- fit

    def _fit_and_drain(self) -> None:
        """Sketch-merged global layout fit. Called under self._lock.

        Each shard folds its owned prefix blocks into one exact sketch
        per feature (k = 2 * bin_sample_rows: level 0 never compacts, so
        the sketch IS the multiset) plus the nonzero-position mask; one
        allgather + rank-ordered merge rebuilds the global multiset, and
        a surrogate prefix (sorted values scattered to the true mask
        positions) flows through the stock Dataset._fit_layout — cut
        points AND EFB bundles match the one-shot fit byte-for-byte,
        independent of which shard saw which rows.
        """
        n_prefix = min(self.bin_sample_rows,
                       sum(b.shape[0] for b in self._raw_blocks))
        f = int(self.n_features)
        world = _gang_world()
        shard_ranks = ([int(jax.process_index()) % self.num_shards]
                       if world > 1 else list(range(self.num_shards)))
        with global_timer.scope("stream_fit_layout"):
            local = {r: self._shard_fit_payload(r, n_prefix, f)
                     for r in shard_ranks}
            if world > 1:
                payloads = _allgather_bytes(local[shard_ranks[0]])
            else:
                payloads = [local[r] for r in range(self.num_shards)]
            t0 = perf_counter()
            surrogate = self._merge_fit_payloads(payloads, n_prefix, f)
            global_timer.set_count("stream_sketch_merge_us",
                                   int((perf_counter() - t0) * 1e6))
            global_timer.add_count("stream_sketch_merges", 1)
            layout = CoreDataset(self.config)
            group_lists = layout._fit_layout(surrogate,
                                             self.categorical_feature)
            layout._make_groups(group_lists)
        self._layout = layout
        self._group_lists = group_lists
        if self._drift is not None:
            # surrogate carries the identical per-feature marginals, so
            # the occupancy baseline matches the raw-prefix reference
            self._drift.set_reference(layout, surrogate)
        for blk in self._raw_blocks:
            self._bin_blocks.append(
                np.ascontiguousarray(layout._bin_rows(blk)))
        self._raw_blocks = []
        self._buffered = 0
        if telemetry.enabled():
            telemetry.emit("stream_layout_fitted",
                           sample_rows=int(n_prefix),
                           num_groups=len(layout.groups),
                           num_shards=self.num_shards)

    def _shard_fit_payload(self, rank: int, n_prefix: int, f: int) -> bytes:
        """Pack shard `rank`'s view of the prefix: exact per-feature
        sketches over its owned rows plus the (nonzero|NaN) mask and the
        global row offsets those rows came from."""
        k_exact = max(8, 2 * n_prefix)
        sketches = [QuantileSketch(k_exact) for _ in range(f)]
        seg_starts: List[int] = []
        seg_lens: List[int] = []
        masks: List[np.ndarray] = []
        row0 = 0
        for i, blk in enumerate(self._raw_blocks):
            take = min(blk.shape[0], n_prefix - row0)
            if take > 0 and self._block_owner[i] == rank:
                part = blk[:take]
                for j in range(f):
                    sketches[j].update(part[:, j])
                masks.append((part != 0) | np.isnan(part))
                seg_starts.append(row0)
                seg_lens.append(take)
            row0 += blk.shape[0]
            if row0 >= n_prefix:
                break
        arrays: Dict[str, np.ndarray] = {
            "rank": np.array([rank]),
            "seg_starts": np.asarray(seg_starts, dtype=np.int64),
            "seg_lens": np.asarray(seg_lens, dtype=np.int64),
            "mask": (np.concatenate(masks, axis=0) if masks
                     else np.zeros((0, f), dtype=bool)),
        }
        for j in range(f):
            _sketch_to_arrays(sketches[j], f"sk{j}_", arrays)
        return _pack_arrays(arrays)

    @staticmethod
    def _merge_fit_payloads(payloads: List[bytes], n_prefix: int,
                            f: int) -> np.ndarray:
        """Rank-ordered merge of the gathered shard payloads into the
        surrogate prefix matrix Dataset._fit_layout consumes."""
        shards = sorted((_unpack_arrays(p) for p in payloads),
                        key=lambda a: int(a["rank"][0]))
        mask = np.zeros((n_prefix, f), dtype=bool)
        for arrays in shards:
            local0 = 0
            for start, length in zip(arrays["seg_starts"],
                                     arrays["seg_lens"]):
                mask[start:start + length] = \
                    arrays["mask"][local0:local0 + length]
                local0 += length
        surrogate = np.zeros((n_prefix, f), dtype=np.float64)
        for j in range(f):
            sk = merge_ranked([(int(a["rank"][0]),
                                _sketch_from_arrays(f"sk{j}_", a))
                               for a in shards
                               if a.get(f"sk{j}_meta") is not None])
            pos = np.flatnonzero(mask[:, j])
            vals, wts = sk.weighted()
            expanded = np.sort(np.repeat(vals, wts.astype(np.int64)))
            if len(expanded) != sk.nonzero_n:
                # compacted sketch (prefix outgrew k): rank-uniform
                # resample — approximate, like the reference's sampled fit
                expanded = np.sort(sk.quantile_sample(sk.nonzero_n))
            n_fill = min(len(expanded), len(pos))
            surrogate[pos[:n_fill], j] = expanded[:n_fill]
            if len(pos) > n_fill:  # remaining masked rows were NaN
                surrogate[pos[n_fill:], j] = np.nan
        return surrogate


# --------------------------------------------------------- sharded cache

class _ShardedBlockCache:
    """_BlockCache surface routed over per-rank sub-caches.

    Block b lives on rank `b % num_shards`; every rank's cache gets the
    full per-device LGBM_TPU_HBM_BUDGET, so the gang holds num_shards x
    the single-device resident working set — the 'dataset no single
    device could hold' leg. Values are untouched (the sub-caches slice
    the same plane), so every consumer of get()/prefetch() sees the
    exact arrays the single cache would serve.
    """

    def __init__(self, plane: np.ndarray, block_rows: int, capacity: int,
                 upload_dtype, num_shards: int) -> None:
        self.plane = plane
        self.block_rows = int(block_rows)
        self.num_rows = int(plane.shape[1])
        self.n_blocks = max(1, -(-self.num_rows // self.block_rows))
        self.num_shards = max(1, int(num_shards))
        self.capacity = max(1, int(capacity)) * self.num_shards
        self.upload_dtype = upload_dtype
        self._shards = [
            _BlockCache(plane, block_rows, capacity, upload_dtype)
            for _ in range(self.num_shards)]

    def owner(self, b: int) -> int:
        return int(b) % self.num_shards

    def block_range(self, b: int):
        lo = b * self.block_rows
        return lo, min(self.num_rows, lo + self.block_rows)

    def prefetch(self, b: int) -> None:
        self._shards[self.owner(b)].prefetch(b)

    def get(self, b: int):
        return self._shards[self.owner(b)].get(b)

    @property
    def upload_s(self) -> float:
        return sum(s.upload_s for s in self._shards)


# -------------------------------------------------------- sharded learner

class ShardedStreamedTreeLearner(StreamedTreeLearner):
    """StreamedTreeLearner whose block cache and quantized histogram
    reduction span the data mesh.

    Float (plain / bagged) training inherits the parent's canonical
    chunk-order fold untouched — sharding moves block placement and
    caching, never the floating-point summation sequence — so those
    paths are trivially bit-identical to the single-device streamed
    learner for ANY shard count, including after a shrink. Quantized
    training computes one per-rank partial histogram over each rank's
    owned blocks and merges them with the same psum-over-"data" the
    resident data-parallel learner uses: int32 accumulation is exact
    under any order, so the merged histogram equals the canonical fold
    bit-for-bit (the test_sharded_device.py precedent). The per-wave
    wire cost is one [G, B, 3] int32 histogram per rank — independent
    of N — recorded as stream_ici_bytes_per_wave.
    """

    def __init__(self, config, dataset, budget_bytes=None,
                 block_rows=None) -> None:
        self.mesh = data_mesh(config.num_machines)
        self.num_shards = int(self.mesh.devices.size)
        self._psum_hist = None
        super().__init__(config, dataset, budget_bytes=budget_bytes,
                         block_rows=block_rows)

    def _device_bins(self, dataset) -> None:
        super()._device_bins(dataset)
        base = self._cache
        if self.num_shards > 1:
            self._cache = _ShardedBlockCache(
                base.plane, base.block_rows, base.capacity,
                base.upload_dtype, self.num_shards)
            global_timer.set_count(
                "stream_resident_blocks",
                min(self._cache.capacity, self._cache.n_blocks))
        global_timer.set_count("stream_shards", self.num_shards)
        return None

    def _make_psum_hist(self):
        if self._psum_hist is None:
            from jax.sharding import PartitionSpec as P

            self._psum_hist = jax.jit(shard_map(
                lambda h: jax.lax.psum(h[0], "data"),
                mesh=self.mesh, in_specs=P("data"), out_specs=P(),
                check_vma=False))
        return self._psum_hist

    def _leaf_hist(self, leaf: int):
        if not (self.quantized and self.num_shards > 1) \
                or _gang_world() > 1:
            # float paths keep the parent's canonical fold (a float psum
            # would reassociate partial sums); a multi-process gang also
            # folds canonically — its local [D, ...] partial stack is not
            # globally addressable, and the canonical order is already
            # the bit-identity baseline
            return super()._leaf_hist(leaf)
        idx = np.asarray(self.partition.indices(leaf))
        vi = idx[idx < self.num_data].astype(np.int64)
        mode = self._ragged_mode()
        num_bins = self.group_bin_padded
        G = len(self.dataset.groups)
        owner = (vi // self._cache.block_rows) % self.num_shards
        zeros = jnp.zeros((G, num_bins, 3), dtype=jnp.int32)
        parts = []
        for r in range(self.num_shards):
            sub = vi[owner == r]
            if sub.size == 0:
                parts.append(zeros)
            elif mode is not None:
                parts.append(self._ragged_over_indices(
                    sub, interpret=mode == "interpret"))
            else:
                parts.append(self._hist_over_indices(sub))
        merged = self._make_psum_hist()(jnp.stack(parts))
        global_timer.set_count("stream_ici_bytes_per_wave",
                               G * num_bins * 3 * 4)
        global_timer.set_count("device_ici_bytes_per_wave",
                               G * num_bins * 3 * 4)
        return merged
