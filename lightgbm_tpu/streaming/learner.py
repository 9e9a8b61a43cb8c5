"""Out-of-core tree learner: train with a bounded device-resident plane.

StreamedTreeLearner subclasses the host-driven SerialTreeLearner but never
uploads the full [G, N] bin plane. Instead the plane stays host-side and a
`_BlockCache` keeps at most `LGBM_TPU_HBM_BUDGET` bytes of fixed
[G, block_rows] slices device-resident (LRU), prefetching the next
histogram chunk's blocks while the current chunk's one-hot contraction is
still in flight — PR 5's double-buffered async-copy machinery run in the
H2D direction (jax.device_put/jnp.asarray dispatches are async; the python
driver runs ahead of the device queue).

Bit-identity with the resident learner (the acceptance bar):

  * `_leaf_hist` mirrors ops/histogram.py `_build_histogram_rows_xla`'s
    bracketing exactly — one `_hist_chunk` when the padded leaf index set
    fits DEFAULT_ROW_CHUNK, otherwise a zero-seeded accumulation over the
    same chunk boundaries in the same order. Chunk bin buffers are
    assembled from cached blocks (per-block gather + inverse-permutation
    scatter) and carry the identical integer bin values the resident
    gather would produce; padded positions carry bin 0 with gh == 0, a
    contribution of exactly 0.0 to the same accumulator cells. The chunk
    sums therefore reassociate nothing and the histogram is bitwise equal
    on the XLA path. On TPU (or under LGBM_TPU_STREAM_RAGGED) the per-
    block path routes through pallas_histogram_slots_ragged instead —
    `_leaf_hist_ragged` — which is bit-identical for quantized training
    (int32 accumulation) and carries the resident Pallas path's per-tile
    reassociation caveat for float training.
  * `_partition_split` uploads the chosen group's host plane row — the
    same values `bins_dev[gi]` would hold — so RowPartition's stable
    3-way-key argsort compaction sees identical inputs.
  * Train-score updates traverse trees block-by-block
    (`add_tree_to_score_blocked`): each valid row is scattered exactly
    once with the identical leaf value, so the score vector matches the
    resident single-scatter path bit for bit.

When the budget covers the whole plane the cache simply pins every block
(hbm_resident_fraction == 1.0) and the same code path is exercised — there
is no separate resident branch to drift.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from functools import partial
from time import perf_counter
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import Dataset
from ..ops.hist_pallas import (DEFAULT_TILE_ROWS, hist_force_f32,
                               pallas_histogram_slots_ragged,
                               tile_slot_pairs)
from ..ops.histogram import DEFAULT_ROW_CHUNK, _acc_dtype, _hist_chunk
from ..ops.partition import pad_indices
from ..ops.score import binned_leaf_index, binned_tree_arrays
from ..treelearner.serial import SerialTreeLearner
from ..utils.backend import on_tpu
from ..utils.timer import global_timer

BUDGET_ENV = "LGBM_TPU_HBM_BUDGET"
BLOCK_ROWS_ENV = "LGBM_TPU_STREAM_BLOCK_ROWS"
# per-block histogram kernel routing: "" auto (ragged Pallas wherever the
# resident learner would take Pallas, i.e. TPU), "0" force XLA scatter,
# "1" force the compiled ragged kernel, "interpret" force the kernel in
# Pallas interpret mode (CPU-testable bit-exactness harness)
RAGGED_ENV = "LGBM_TPU_STREAM_RAGGED"
DEFAULT_BLOCK_ROWS = 65536
# per-split group-row uploads kept warm for repeated splits on one group
_ROW_CACHE_SLOTS = 4


def parse_budget_bytes(text: Optional[str]) -> Optional[int]:
    """'64m' / '1g' / '512k' / plain bytes -> int bytes; None/empty/0 ->
    None (streaming off)."""
    if not text:
        return None
    text = text.strip().lower()
    mult = 1
    if text and text[-1] in "kmg":
        mult = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30}[text[-1]]
        text = text[:-1]
    try:
        val = int(float(text) * mult)
    except ValueError:
        return None
    return val if val > 0 else None


def stream_budget_bytes() -> Optional[int]:
    return parse_budget_bytes(os.environ.get(BUDGET_ENV))


def streaming_requested() -> bool:
    """Whether LGBM_TPU_HBM_BUDGET asks for out-of-core training — the
    factory seam create_tree_learner checks (before device growth: a plane
    that needs a budget by definition should not be uploaded whole)."""
    return stream_budget_bytes() is not None


# graftlint: disable=R6 -- no input matches the [G, B, 3] output shape/dtype, nothing is aliasable; the chunk temps free at dispatch end
@partial(jax.jit, static_argnames=("num_bins", "compute_dtype"))
def _hist_chunk_seed(bins_c: jax.Array, gh_c: jax.Array, num_bins: int,
                     compute_dtype=jnp.float32) -> jax.Array:
    """Single-chunk leaf histogram over an assembled chunk buffer —
    mirrors _build_histogram_rows_xla's P <= row_chunk branch."""
    return _hist_chunk(bins_c.astype(jnp.int32), gh_c, num_bins,
                       compute_dtype)


@partial(jax.jit, static_argnames=("num_bins", "compute_dtype"),
         donate_argnums=(0,))
def _hist_chunk_accum(acc: jax.Array, bins_c: jax.Array, gh_c: jax.Array,
                      num_bins: int, compute_dtype=jnp.float32) -> jax.Array:
    """acc + one chunk — the body of _build_histogram_rows_xla's scan,
    with the accumulator donated so the rotating partial sums never
    double-buffer (the chunk bin/gh temps cannot alias the output)."""
    return acc + _hist_chunk(bins_c.astype(jnp.int32), gh_c, num_bins,
                             compute_dtype)


# reservation marker for a prefetch whose H2D dispatch is still outside the
# lock — distinguishable from any real jax.Array
_PENDING = object()


class _BlockCache:
    """LRU device cache over fixed-width column blocks of the host plane.

    `prefetch(b)` dispatches the H2D copy without blocking; a later
    `get(b)` promotes the in-flight array into the resident set. The
    prefetched/cold split feeds `stream_h2d_overlap_pct`.

    Thread safety: map mutation (resident/inflight insert, LRU eviction)
    happens only under `_lock`; the jitted `jnp.asarray` upload dispatch
    always runs OUTSIDE it (R13 discipline — a compile under the lock
    would stall every concurrent reader). A prefetch first parks a
    `_PENDING` reservation under the lock, uploads, then fills the
    reservation only if a racing `get` has not claimed the key; a `get`
    that pops a still-pending reservation simply takes the cold path and
    the prefetcher's late fill is dropped.
    """

    def __init__(self, plane: np.ndarray, block_rows: int, capacity: int,
                 upload_dtype) -> None:
        self.plane = plane
        self.block_rows = int(block_rows)
        self.num_rows = int(plane.shape[1])
        self.n_blocks = max(1, -(-self.num_rows // self.block_rows))
        self.capacity = max(1, int(capacity))
        self.upload_dtype = upload_dtype
        self._resident: "OrderedDict[int, jax.Array]" = OrderedDict()
        self._inflight: Dict[int, object] = {}
        self._lock = threading.Lock()
        self.upload_s = 0.0

    def block_range(self, b: int):
        lo = b * self.block_rows
        return lo, min(self.num_rows, lo + self.block_rows)

    def _upload(self, b: int) -> jax.Array:
        lo, hi = self.block_range(b)
        blk = self.plane[:, lo:hi]
        t0 = perf_counter()
        arr = (jnp.asarray(blk, dtype=self.upload_dtype)
               if self.upload_dtype is not None else jnp.asarray(blk))
        self.upload_s += perf_counter() - t0
        global_timer.add_count("stream_h2d_blocks", 1)
        global_timer.add_count("stream_h2d_bytes", int(arr.nbytes))
        global_timer.set_count("stream_h2d_us", int(self.upload_s * 1e6))
        return arr

    def prefetch(self, b: int) -> None:
        with self._lock:
            if b in self._resident or b in self._inflight:
                return
            if self.capacity < 2:
                return  # one slot: prefetching would evict the working block
            if len(self._resident) + len(self._inflight) >= self.capacity:
                if not self._resident:
                    return
                self._resident.popitem(last=False)
            self._inflight[b] = _PENDING
        arr = self._upload(b)  # jitted dispatch: lock released
        with self._lock:
            if self._inflight.get(b) is _PENDING:
                self._inflight[b] = arr
            # else a racing get() claimed (and cold-loaded) the block while
            # the upload was in flight — drop this copy on the floor

    def get(self, b: int) -> jax.Array:
        with self._lock:
            arr = self._resident.pop(b, None)
            if arr is not None:
                self._resident[b] = arr  # LRU refresh
                global_timer.add_count("stream_cache_hits", 1)
                return arr
            arr = self._inflight.pop(b, None)
            if arr is _PENDING:
                arr = None  # reservation not yet filled: go cold
        if arr is not None:
            global_timer.add_count("stream_h2d_prefetched", 1)
        else:
            global_timer.add_count("stream_h2d_cold", 1)
            arr = self._upload(b)  # jitted dispatch: lock released
        with self._lock:
            self._resident[b] = arr
            while (len(self._resident) + len(self._inflight) > self.capacity
                   and len(self._resident) > 1):
                self._resident.popitem(last=False)
        return arr


class StreamedTreeLearner(SerialTreeLearner):
    """SerialTreeLearner with the bin plane host-resident and block-cached.

    `bins_dev` is None — models/gbdt.py reads that as the signal to route
    train-score tree traversal through add_tree_to_score_blocked. Every
    other hook (split search, colsampler, CEGB, quantized gradients,
    checkpoint snapshot/restore) is inherited unchanged; snapshot state
    never touched the plane, so kill@K resume works as-is.
    """

    def __init__(self, config: Config, dataset: Dataset,
                 budget_bytes: Optional[int] = None,
                 block_rows: Optional[int] = None) -> None:
        self._budget_bytes = (int(budget_bytes) if budget_bytes is not None
                              else (stream_budget_bytes() or 0))
        env_rows = os.environ.get(BLOCK_ROWS_ENV, "")
        self._block_rows_req = (int(block_rows) if block_rows is not None
                                else int(env_rows) if env_rows
                                else DEFAULT_BLOCK_ROWS)
        self._cache: Optional[_BlockCache] = None
        self._row_cache: "OrderedDict[int, jax.Array]" = OrderedDict()
        super().__init__(config, dataset)

    # ------------------------------------------------------------ plane

    def _device_bins(self, dataset: Dataset) -> None:
        plane = dataset.bins
        # mirror the resident upload's LGBM_TPU_BINS_I32 escape hatch so
        # cached blocks hold the same dtype bins_dev would
        upload_dtype = (jnp.int32
                        if (plane.dtype.itemsize == 1
                            and os.environ.get("LGBM_TPU_BINS_I32", "") == "1")
                        else None)
        itemsize = 4 if upload_dtype is not None else plane.dtype.itemsize
        n = max(1, int(plane.shape[1]))
        block_rows = max(256, min(self._block_rows_req, n))
        block_bytes = max(1, plane.shape[0] * block_rows * itemsize)
        if self._budget_bytes > 0:
            capacity = max(1, self._budget_bytes // block_bytes)
        else:
            capacity = -(-n // block_rows)  # no budget: pin everything
        self._cache = _BlockCache(plane, block_rows, capacity, upload_dtype)
        global_timer.set_count("stream_blocks_total", self._cache.n_blocks)
        global_timer.set_count("stream_resident_blocks",
                               min(self._cache.capacity,
                                   self._cache.n_blocks))
        return None

    # ------------------------------------------------------- histograms

    def _ragged_mode(self) -> Optional[str]:
        """Resolve RAGGED_ENV at call time (the unjitted dispatch contract
        of ops.histogram): None = XLA scatter, else 'compiled'|'interpret'."""
        mode = os.environ.get(RAGGED_ENV, "")
        if mode == "0":
            return None
        if mode == "interpret":
            return "interpret"
        if mode == "1":
            return "compiled"
        return "compiled" if on_tpu() else None

    def _leaf_hist(self, leaf: int) -> jax.Array:
        mode = self._ragged_mode()
        if mode is not None:
            return self._leaf_hist_ragged(leaf, interpret=mode == "interpret")
        # the padded leaf index set is already host-materialized inside
        # RowPartition; this pull does not sync any new device work
        idx = np.asarray(self.partition.indices(leaf))
        return self._hist_over_indices(idx)

    def _hist_over_indices(self, idx: np.ndarray) -> jax.Array:
        """The canonical chunk-order histogram fold over an explicit row
        index set — `_leaf_hist`'s body, split out so the sharded learner
        can fold per-rank subsets through the identical bracketing."""
        compute_dtype = jnp.int8 if self.quantized else jnp.float32
        num_bins = self.group_bin_padded
        chunk = DEFAULT_ROW_CHUNK
        if idx.shape[0] <= chunk:
            self._prefetch_for(idx)
            buf = self._gather_chunk(idx)
            gh_c = jnp.take(self._gh, jnp.asarray(idx), axis=0)
            return _hist_chunk_seed(buf, gh_c, num_bins, compute_dtype)
        n_chunks = -(-idx.shape[0] // chunk)
        pad = n_chunks * chunk - idx.shape[0]
        if pad:
            idx = np.concatenate(
                [idx, np.full(pad, self.num_data, dtype=idx.dtype)])
        chunks = idx.reshape(n_chunks, chunk)
        acc = jnp.zeros((len(self.dataset.groups), num_bins, 3),
                        dtype=_acc_dtype(compute_dtype))
        self._prefetch_for(chunks[0])
        for k in range(n_chunks):
            buf = self._gather_chunk(chunks[k])
            if k + 1 < n_chunks:
                # next chunk's H2D rides behind this chunk's gather in the
                # device queue — the double buffer
                self._prefetch_for(chunks[k + 1])
            gh_c = jnp.take(self._gh, jnp.asarray(chunks[k]), axis=0)
            acc = _hist_chunk_accum(acc, buf, gh_c, num_bins, compute_dtype)
        return acc

    def _leaf_hist_ragged(self, leaf: int, interpret: bool = False
                          ) -> jax.Array:
        """Per-block leaf histogram through the ragged Pallas slots kernel.

        Each cached block slab is fed to pallas_histogram_slots_ragged
        whole (padded to the tile grid) with a 1-slot table: rows of this
        leaf carry slot 0, every other row the dump slot, and the pair
        table restricts the grid to the tiles the leaf actually
        touches — per-block cost is O(tiles overlapping the leaf), not
        O(block_rows). The next block's H2D prefetch is dispatched while
        the current block's kernel is in flight (the same double buffer
        as the XLA chunk fold). Quantized histograms accumulate int32 and
        are bit-identical to the scatter path in any block order; float
        histograms reassociate per-tile partial sums, the same caveat the
        resident Pallas path carries.
        """
        idx = np.asarray(self.partition.indices(leaf))
        vi = idx[idx < self.num_data].astype(np.int64)
        return self._ragged_over_indices(vi, interpret=interpret)

    def _ragged_over_indices(self, vi: np.ndarray,
                             interpret: bool = False) -> jax.Array:
        num_bins = self.group_bin_padded
        G = len(self.dataset.groups)
        CH = int(self._gh.shape[1])
        acc_dtype = jnp.int32 if self.quantized else jnp.float32
        acc = jnp.zeros((G, num_bins, CH), dtype=acc_dtype)
        if vi.size == 0:
            return acc
        vi = np.asarray(vi).astype(np.int64)
        cache = self._cache
        tr = DEFAULT_TILE_ROWS
        bid = vi // cache.block_rows
        blocks = np.unique(bid)  # ascending: deterministic fold order
        global_timer.add_count("stream_ragged_leaves", 1)
        for i, b in enumerate(blocks):
            bins_b = cache.get(int(b))
            if i + 1 < len(blocks):
                # next block's H2D rides behind this block's kernel in the
                # device queue — the double buffer
                cache.prefetch(int(blocks[i + 1]))
            sel = vi[bid == b]
            lo, hi = cache.block_range(int(b))
            width = hi - lo
            padded = -(-width // tr) * tr
            if bins_b.shape[1] < padded:
                bins_b = jnp.pad(bins_b,
                                 ((0, 0), (0, padded - bins_b.shape[1])))
            loc = jnp.asarray((sel - lo).astype(np.int32))
            slot = jnp.ones((padded,), jnp.int32).at[loc].set(0)
            gh_rows = jnp.take(self._gh, jnp.asarray(sel),
                               axis=0).astype(jnp.float32)
            gh = jnp.zeros((CH, padded), jnp.float32).at[:, loc].set(
                gh_rows.T)  # the kernel takes its per-row operands [k, N]
            tiles, slots, n_pairs, _ = tile_slot_pairs(
                jnp.asarray([sel[0] - lo], jnp.int32),
                jnp.asarray([sel[-1] - lo + 1], jnp.int32),
                jnp.asarray([True]), padded // tr, tr)
            part = pallas_histogram_slots_ragged(
                bins_b, gh, slot, tiles, slots, n_pairs, num_bins, 1,
                tile_rows=tr,
                quantized=self.quantized, f32=hist_force_f32(),
                interpret=interpret)
            acc = acc + part.astype(acc_dtype)
        return acc

    def _prefetch_for(self, idx_chunk: np.ndarray) -> None:
        cache = self._cache
        vi = idx_chunk[idx_chunk < self.num_data]
        if vi.size == 0:
            return
        for b in np.unique(vi // cache.block_rows):
            cache.prefetch(int(b))

    def _gather_chunk(self, idx_chunk: np.ndarray) -> jax.Array:
        """Assemble the [G, C] bin buffer for one chunk of (possibly
        sentinel-padded, possibly unsorted) row indices from cached
        blocks. Valid columns carry the exact plane values; sentinel
        columns stay bin 0 (their gh is the zero row, so they contribute
        exactly nothing to the histogram)."""
        cache = self._cache
        C = idx_chunk.shape[0]
        out_dtype = (jnp.int32 if cache.upload_dtype is not None
                     else cache.plane.dtype)
        valid = idx_chunk < self.num_data
        if not valid.any():
            return jnp.zeros((cache.plane.shape[0], C), dtype=out_dtype)
        vi = idx_chunk[valid]
        bid = vi // cache.block_rows
        order = np.argsort(bid, kind="stable")
        vi_sorted = vi[order]
        bid_sorted = bid[order]
        bounds = np.flatnonzero(np.diff(bid_sorted)) + 1
        starts = np.concatenate([[0], bounds])
        ends = np.concatenate([bounds, [len(vi_sorted)]])
        parts = []
        for s, e in zip(starts, ends):
            b = int(bid_sorted[s])
            lo, _ = cache.block_range(b)
            local = (vi_sorted[s:e] - lo).astype(np.int32)
            parts.append(jnp.take(cache.get(b), jnp.asarray(local), axis=1))
        gathered = parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                                    axis=1)
        pos = np.flatnonzero(valid)[order]
        if pos.shape[0] == C and np.array_equal(pos, np.arange(C)):
            return gathered
        buf = jnp.zeros((cache.plane.shape[0], C), dtype=gathered.dtype)
        return buf.at[:, jnp.asarray(pos.astype(np.int32))].set(gathered)

    # ------------------------------------------------------- compaction

    def _partition_split(self, leaf: int, new_leaf: int, gi: int,
                         decision: jax.Array, cat_mask=None):
        return self.partition.split(leaf, new_leaf, self._group_row(gi),
                                    decision, cat_mask)

    def _group_row(self, gi: int) -> jax.Array:
        """One group's full bin row [N] for partition compaction — the
        only per-split whole-dataset transfer (N bytes at uint8), kept in
        a tiny LRU since consecutive splits often reuse a group."""
        row = self._row_cache.pop(gi, None)
        if row is None:
            host = self._cache.plane[gi]
            row = (jnp.asarray(host, dtype=jnp.int32)
                   if self._cache.upload_dtype is not None
                   else jnp.asarray(host))
            global_timer.add_count("stream_h2d_rows", 1)
            global_timer.add_count("stream_h2d_bytes", int(row.nbytes))
        self._row_cache[gi] = row
        while len(self._row_cache) > _ROW_CACHE_SLOTS:
            self._row_cache.popitem(last=False)
        return row

    # ------------------------------------------------------ score update

    def add_tree_to_score_blocked(self, tree, score: jax.Array,
                                  row_idx, max_depth: int = 0) -> jax.Array:
        """Block-sharded ops/score.py add_tree_to_score: traverse each
        cached block with block-local indices, scatter into the global
        score. Each valid row is scattered exactly once with the identical
        leaf value, so the result matches the resident path bitwise."""
        if tree.num_leaves <= 1:
            return score.at[row_idx].add(float(tree.leaf_value[0]),
                                         mode="drop")
        ta = binned_tree_arrays(tree, self.dataset)
        bound = max_depth if max_depth > 0 else int(tree.max_depth)
        cache = self._cache
        rows = np.asarray(row_idx)
        vi = rows[rows < self.num_data].astype(np.int64)
        if vi.size == 0:
            return score
        bid = vi // cache.block_rows
        blocks = np.unique(bid)
        for i, b in enumerate(blocks):
            if i + 1 < len(blocks):
                cache.prefetch(int(blocks[i + 1]))
            sel = vi[bid == b]
            lo, hi = cache.block_range(int(b))
            local_p = pad_indices(
                (sel - lo).astype(np.int32), hi - lo)
            global_p = np.full(local_p.shape[0], self.num_data,
                               dtype=np.int64)
            global_p[: sel.shape[0]] = sel
            leaf = binned_leaf_index(ta, cache.get(int(b)),
                                     jnp.asarray(local_p), hi - lo, bound)
            score = score.at[jnp.asarray(global_p)].add(
                ta.leaf_value[leaf], mode="drop")
        return score
