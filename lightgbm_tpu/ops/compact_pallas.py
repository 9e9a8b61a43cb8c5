"""Pallas TPU leaf-contiguous row compaction (stable 2-way partition).

The device tree learner keeps every per-row array (bin columns + gradient
rows) in a LEAF-CONTIGUOUS permutation so each histogram wave can read only
the rows of the leaves it is splitting (ops/hist_pallas.py ragged tiles)
instead of all N rows. This module moves the rows: given the forward
destination map of a stable 2-way partition restricted to a set of disjoint
leaf ranges, it produces the re-permuted arrays in one sequential-grid
Pallas pass.

Counterpart of CUDADataPartition::SplitInner (cuda_data_partition.cu):
there, a bitvector + block prefix-scan + global scatter. TPUs have no fast
global scatter, so the same data movement is phrased as dense tile algebra:

  1. XLA side (range_partition_dst): ONE global exclusive scan, of the left
     mask. A wave's ranges are disjoint position ranges and every row of a
     range is left or right, so with lext the length-(N+1) prefix count of
     left rows a left row j lands at starts[k] - lext[starts[k]] + lext[j]
     and a right row at j + lext[ends[k]] - lext[j]: the per-row bases come
     from one [2, K] @ [K, N] range-membership matmul -> forward map dst[j]
     (a permutation of [0, N); rows outside every range keep their
     position). The scan is sampled at the tile boundaries and the range
     ends (LeftCounts) for step 2.
  2. XLA side (build_pair_tables): each INPUT tile's rows land in at most a
     handful of OUTPUT tiles — per (range, side) the destinations are
     contiguous, so a tile's class rows span <= 2 output tiles. Both ends of
     that run follow from the samples of lext alone, so the tables are
     [K, T] integer arithmetic and read no per-row array. The pair list
     (in_tile -> out_tile) is sorted by out_tile; one searchsorted of the
     boundaries of the runs of R consecutive output tiles over it gives the
     kernel's run offsets (run_offsets, [T/R + 1]).
  3. Pallas kernel (_pallas_compact_call): a sequential grid over the runs
     of output tiles, ceil(T / R) steps for T tiles of `tile` rows; R
     (compact_blocks) is 8 wherever the run's output blocks fit the VMEM
     budget. Every per-row operand has
     the ROWS ON THE LANES: the bin plane [Gp, N], the f32 payload [rc, N]
     (rc a multiple of 8) and dst [1, N]. A custom call's operand layout is
     fixed, and XLA carries it back into the glue that makes the operand:
     an [N, 1] or [N, rc] operand pads its minor dimension to 128 lanes
     (2.15 GB for 4M int32, not 16 MB) and the whole wave's routing
     arithmetic then runs at 8 useful values a vector register. A step's
     output blocks, (Gp, R*tile) and (rc, R*tile), are zeroed, filled and written
     back once. The inputs stay in HBM (pl.ANY): a fori_loop inside the
     step walks the run's pairs, and the kernel's own DMAs copy each live
     pair's input tiles into a VMEM ring, ring - 1 pairs ahead, across the
     step boundaries too, so no pair pays a pipeline step or waits on its
     fetch. A duplicate pair is neither fetched nor worked; a copy pair
     (a tile no range touches) is a plain VPU copy into its tile of the
     run; a permute pair builds the one-hot PT[o, i] = (o == dst[i] -
     out*tile) from the lane-major dst (a sublane broadcast, no relayout),
     stacks the payload's four limbs and the plane's limb(s) into ONE
     operand X [4*rc + Gp*(1|2), tile] and accumulates
     out[c, o] += sum_i X[c, i] * PT[o, i] — one matmul contracting the
     last dimension of both (the q @ k^T form), so payload and plane move
     the same way — into its tile of the run.

Exactness: values transit the MXU as 8-bit limbs of their raw bits (bf16
operands — 0/1 one-hot and limbs <= 255 are exact in bf16, and each output
row receives exactly ONE source row: dst is injective, so every row of PT
holds at most one 1), and the limbs recombine and accumulate as integers, so
payloads are moved bit-exactly (-0.0 and denormals included) at full bf16
MXU rate: f32 rows as four limbs, the bin plane as two limbs for int32
(values < 2**16) or ONE limb when the plane is already 8-bit (uint8 bins,
values <= 255) — a 2x cut in the plane's transport rows on top of the 4x HBM
cut of the narrow plane itself. The only lax.sort is the single
composite-key sort ordering the pair list; no row-wise sort anywhere — at
10.5M rows a global row sort costs more than the histograms it would save
(docs/PERF_NOTES.md).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from ..utils import sanitize

# Compaction tile: independent of the histogram tile (DEFAULT_TILE_ROWS);
# the one-hot P is [tile, tile] so smaller tiles keep VMEM + per-pair FLOPs
# down. N must be padded to a multiple of lcm(COMPACT_TILE, hist tile).
COMPACT_TILE = 512

# the recompile watcher splits this entry's cache misses into the
# kernel_compiles counter (kernel-flag experiments show their compile cost)
telemetry.register_kernel_fn("_pallas_compact_call")


def exclusive_cumsum(x: jax.Array) -> jax.Array:
    """[N] -> [N] exclusive prefix sum (int32)."""
    x = x.astype(jnp.int32)
    return jnp.cumsum(x) - x


class LeftCounts(NamedTuple):
    """The samples of lext, the length-(N+1) exclusive prefix count of left
    rows, that build_pair_tables needs: every end of a (range, tile) overlap
    is a tile boundary or a range end."""
    tiles: jax.Array   # [T + 1] int32: lext[t * tile]
    starts: jax.Array  # [K] int32: lext[starts[k]]
    ends: jax.Array    # [K] int32: lext[starts[k] + counts[k]]


def range_partition_dst(go_left: jax.Array, match: jax.Array,
                        in_any: jax.Array, starts: jax.Array,
                        counts: jax.Array, valid: jax.Array, tile: int
                        ) -> Tuple[jax.Array, jax.Array, LeftCounts]:
    """Forward destination map of a stable 2-way partition of K disjoint
    position ranges.

    go_left [N] bool, match [K, N] bool (row-in-range membership, already
    masked by `valid`; rows on the minor axis like every per-row array of
    the wave), in_any [N] bool (= match.any(axis=0), which the caller has),
    starts/counts [K] int32, valid [K] bool, tile the compaction tile
    (N % tile == 0).
    Returns (dst [N] int32, n_left [K] int32, LeftCounts). Rows outside
    every valid range keep their position; rows of range k land stably in
    [starts[k], starts[k]+n_left[k]) or [starts[k]+n_left[k], ends[k]).

    All vectorized: ONE global scan (of the left mask; a right row's rank is
    its position less the left rows before it), K-sized gathers, one
    [2, K] @ [K, N] matmul for the per-row base (gathers at N scale
    serialize on TPU; the matmul does not). Positions must be < 2**24
    (exact in f32).
    """
    K, N = match.shape
    pos = jnp.arange(N, dtype=jnp.int32)
    lmask = in_any & go_left
    lcum = exclusive_cumsum(lmask)
    # length-(N+1) inclusive tail so ends[k] == N indexes safely
    lext = jnp.concatenate(
        [lcum, (lcum[-1] + lmask[-1].astype(jnp.int32))[None]])
    ends = starts + counts
    lefts = LeftCounts(lext[::tile], jnp.take(lext, starts),
                       jnp.take(lext, ends))
    n_left = lefts.ends - lefts.starts
    # left row j of range k -> base_l[k] + lext[j]; right row j ->
    # j + lext[ends[k]] - lext[j]
    base_l = starts - lefts.starts
    bases = jax.lax.dot(jnp.stack([base_l, lefts.ends]).astype(jnp.float32),
                        match.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)  # [2, N]
    dst = jnp.where(
        lmask, bases[0].astype(jnp.int32) + lcum,
        jnp.where(in_any, pos + bases[1].astype(jnp.int32) - lcum, pos))
    # dst and the left counts as one step before the pair tables and the
    # kernel. The kernel reads dst by its own DMAs, so where dst lives no
    # longer matters; the order does: without the barrier the scheduler
    # keeps more of the wave's arrays alive around the kernel (AOT for a
    # described v5e: temp 1.04 -> 1.60 GB in MSLR's tree program, 3.71 ->
    # 6.07 GB in Expo's; PERF.md, PR 42).
    dst, lefts = jax.lax.optimization_barrier((dst, lefts))
    return dst, jnp.where(valid, n_left, 0), lefts


def max_pairs_bound(n_tiles: int, n_classes: int) -> int:
    """Static upper bound on the pair-list length (skip pairs included) for
    the left|right classes of disjoint position ranges (n_classes = 2 * K).

    identity pairs: n_tiles. A class whose destinations are one contiguous
    run lists, per input tile it touches, one pair plus one more where the
    run crosses an output-tile boundary inside that tile: <= tiles_touched +
    out_tiles of them. The two classes of a range are disjoint in ROWS but
    interleaved in every tile the range overlaps, so both touch all of its
    tiles: over all ranges tiles_touched sums to <= 2 * (n_tiles +
    n_classes), and out_tiles to <= n_tiles + n_classes. (Counting each
    tile once, 3 * n_tiles, overflows as soon as one range spans more than
    ~4 * n_classes tiles — the root split of any tree over ~90k rows — and
    the truncated list drops the last output tiles' rows.)
    """
    return 4 * n_tiles + 4 * n_classes + 8


def _check_pairs_fit(mp: int, n_pairs) -> None:
    # graftlint: disable=R1 -- host side of jax.debug.callback: n_pairs arrives as a concrete value, and the callback exists only under LGBM_TPU_SANITIZE
    needed = int(np.asarray(n_pairs))  # not a counted sync (utils/sanitize)
    if needed > mp:
        raise ValueError(
            f"compaction pair list needs {needed} pairs, "
            f"max_pairs_bound allows {mp}: the truncated list drops rows "
            "(the ranges must be disjoint position ranges)")


def build_pair_tables(lefts: LeftCounts, starts: jax.Array,
                      counts: jax.Array, valid: jax.Array, tile: int):
    """Pair list (in_tile -> out_tile) covering every row movement of
    range_partition_dst's permutation, from its LeftCounts and the K ranges
    alone: no per-row operand.

    The rows of range k in tile t are the positions [a, b) =
    [max(t*tile, starts[k]), min((t+1)*tile, ends[k])); lext[b] - lext[a] of
    them go left, to the contiguous run that starts at
    starts[k] - lext[starts[k]] + lext[a], and the rest right, to the run
    from a + lext[ends[k]] - lext[a]. Each run lists its first output tile
    and, where it crosses a boundary, its last. a and b are tile boundaries
    or range ends, so every lext[.] is one of lefts' samples.

    Returns (pair_in, pair_out, pcopy, n_pairs[1]) with static length
    max_pairs_bound(T, 2 * K); the kernel walks the first n_pairs, and the
    entries past them, never walked, repeat the last real pair. pcopy
    per pair: 0 = one-hot permute, 1 = raw block copy (untouched identity
    tile), 2 = SKIP (duplicate of the previous pair — processing it would
    double-count rows). Sorted by out_tile, so the pairs of each run of
    output tiles the kernel's grid step writes are one contiguous slice.

    One fused lax.sort: candidate pairs (with duplicates still in) are
    sorted by the composite key out_tile*T + in_tile, so duplicates —
    which always share an input tile AND an output tile — land adjacent
    and are demoted to skip pairs by one post-sort compare.
    """
    T = lefts.tiles.shape[0] - 1
    K = starts.shape[0]
    if T * T + T >= 2 ** 30:
        raise ValueError("pair sort key would overflow int32; use a larger "
                         "compaction tile for this row count")
    big = jnp.int32(2 ** 30)
    ids = jnp.arange(T, dtype=jnp.int32)
    lo = (ids * tile)[None, :]  # [1, T]; everything below is [K, T]
    s_k = starts[:, None]
    e_k = (starts + counts)[:, None]
    a = jnp.maximum(lo, s_k)
    b = jnp.minimum(lo + tile, e_k)
    overlap = valid[:, None] & (b > a)
    lext_a = jnp.where(lo >= s_k, lefts.tiles[None, :-1],
                       lefts.starts[:, None])
    lext_b = jnp.where(lo + tile <= e_k, lefts.tiles[None, 1:],
                       lefts.ends[:, None])
    n_l = lext_b - lext_a
    n_r = (b - a) - n_l
    first_l = s_k - lefts.starts[:, None] + lext_a
    first_r = a + lefts.ends[:, None] - lext_a
    cands = [ids[None, :]]  # identity pair for every tile: full coverage
    for first, n in ((first_l, n_l), (first_r, n_r)):
        any_m = overlap & (n > 0)
        dmin = first // tile
        dmax = (first + n - 1) // tile
        cands.append(jnp.where(any_m, dmin, T))
        cands.append(jnp.where(any_m & (dmax > dmin), dmax, T))
    cand = jnp.concatenate(cands, axis=0)  # [1 + 4 * K, T]
    out_flat = cand.reshape(-1)
    in_flat = jnp.tile(ids, cand.shape[0])
    ok = out_flat < T
    key = jnp.where(ok, out_flat * T + in_flat, big)
    key = jax.lax.sort(key)
    n_pairs = ok.sum().astype(jnp.int32)
    # duplicate pairs (same in AND out tile => equal keys, now adjacent)
    # become skip pairs: they stay in the list so the length stays static,
    # but the kernel must not process them (double-counted rows). They
    # share both blocks with their predecessor, so they cost no extra DMA.
    dup = jnp.concatenate([jnp.zeros(1, bool), key[1:] == key[:-1]])
    mp = max_pairs_bound(T, 2 * K)
    if sanitize.enabled():
        # the bound is derived for disjoint ranges; ranges that overlap
        # list a tile's rows more than once, outgrow it, and the truncated
        # list would lose rows below without a sign
        jax.debug.callback(partial(_check_pairs_fit, mp), n_pairs)
    if key.shape[0] < mp:
        pad_n = mp - key.shape[0]
        key = jnp.concatenate([key, jnp.full(pad_n, big, jnp.int32)])
        dup = jnp.concatenate([dup, jnp.zeros(pad_n, bool)])
    key = key[:mp]
    dup = dup[:mp]
    last = jnp.take(key, jnp.maximum(n_pairs - 1, 0))
    live = jnp.arange(mp, dtype=jnp.int32) < n_pairs
    key = jnp.where(live, key, last)
    pair_in = key % T
    pair_out = key // T
    # untouched tiles: identity pair does a raw block copy, no matmul.
    # (A tile receiving rows from elsewhere necessarily lost rows too —
    # dst is a permutation — so untouched tiles exchange nothing.)
    touched = overlap.any(axis=0)
    is_copy = (pair_in == pair_out) & ~jnp.take(touched, pair_in)
    pcopy = jnp.where(dup & live, 2, is_copy.astype(jnp.int32))
    return pair_in, pair_out, pcopy, n_pairs[None]


# What one compaction call cost, as compact_rows hands it back beside the
# arrays: the live pairs of its list, those that are a raw block copy, those
# that are a one-hot permute (the rest of the live pairs are skipped
# duplicates), and the steps the kernel's grid walks: one a run of output
# tiles, ceil(T / run_tiles), whatever the pairs.
COMPACT_WORK_FIELDS = ("compact_pairs", "compact_copy_pairs",
                       "compact_permute_pairs", "compact_grid_steps")


def pair_work_counts(pcopy: jax.Array, n_pairs: jax.Array,
                     grid_steps: int) -> jax.Array:
    """[4] int32, COMPACT_WORK_FIELDS of one pair list (build_pair_tables'
    pcopy and n_pairs) and the kernel's grid_steps: two masked sums over
    the table, nothing per row."""
    live = jnp.arange(pcopy.shape[0], dtype=jnp.int32) < n_pairs[0]
    return jnp.stack([
        n_pairs[0], jnp.sum(live & (pcopy == 1), dtype=jnp.int32),
        jnp.sum(live & (pcopy == 0), dtype=jnp.int32),
        jnp.int32(grid_steps)])


def _limbs(x_int: jax.Array, n: int) -> jax.Array:
    """Split int32 values [c, T] into n 8-bit limbs stacked along the rows,
    [n*c, T] (each limb <= 255: exact as a bf16 matmul operand)."""
    parts = [jnp.bitwise_and(jax.lax.shift_right_logical(x_int, 8 * i), 255)
             for i in range(n)]
    return jnp.concatenate(parts, axis=0)


# A grid step writes a run of up to RUN_TILES_MAX consecutive output tiles,
# and the kernel's own DMAs keep up to RING_MAX pairs' input tiles in VMEM
# (RING_MAX - 1 in flight while one is worked on). Each halves, down to 1
# and 2, until the run's double-buffered output blocks and the ring each
# fit half of BLOCK_VMEM_BYTES, beside the permute's [tile, tile] one-hot.
RUN_TILES_MAX = 8
RING_MAX = 8
BLOCK_VMEM_BYTES = 6 << 20


def compact_blocks(gp: int, plane_bytes: int, rc: int,
                   tile: int) -> Tuple[int, int]:
    """(run_tiles, ring): the output tiles of a grid step and the pairs'
    input tiles the kernel keeps in VMEM, for a plane of gp rows of
    plane_bytes bytes and a float32 payload of rc rows."""
    col = gp * plane_bytes + 4 * rc  # a row's bytes across plane and payload
    run, ring = RUN_TILES_MAX, RING_MAX
    while run > 1 and 2 * run * tile * col > BLOCK_VMEM_BYTES // 2:
        run //= 2
    # an input slot also holds the pair's dst, [1, tile] padded to 8 rows
    while ring > 2 and ring * tile * (col + 32) > BLOCK_VMEM_BYTES // 2:
        ring //= 2
    return run, ring


def _make_compact_kernel(tile: int, gp: int, rc: int, plane8: bool,
                         packed: bool, run_tiles: int, ring: int,
                         n_runs: int):
    """Grid step b writes output tiles [b * run_tiles, (b + 1) * run_tiles):
    it zeroes its output blocks, then a fori_loop walks the run's pairs,
    [off[b], off[b + 1]) of the out-sorted table. Pair p's input tiles
    (plane, payload and, for a permute, dst) are copied by the kernel's
    own DMAs into ring slot p % ring, started ring - 1 pairs ahead; the
    sequential grid keeps the ring and its semaphores from one step to the
    next, so the last pairs of run b start the fetches of run b + 1's
    first. A duplicate pair is neither fetched nor worked; a copy pair
    writes its input tile into its tile of the run; a permute pair moves
    its rows by the one-hot limb product. plane8: the bin plane is an
    8-bit dtype (uint8). Its values fit one bf16 limb, so the plane rides
    the transport matmul as Gp rows instead of 2*Gp, and the accumulate
    widens to i32 in-register (Mosaic has no elementwise 8-bit vectors)
    before narrowing back to the 8-bit output block. packed: the pair's
    kind rides the input tile's table word (`pair_tables_packed`), else it
    is a table of its own."""
    in_mask = (1 << PCOPY_SHIFT) - 1

    def kernel(*refs):
        if packed:
            pin_ref, pout_ref, off_ref = refs[:3]
        else:
            pin_ref, pout_ref, pcopy_ref, off_ref = refs[:4]
        (bins_hbm, row_hbm, dst_hbm, bins_out, row_out,
         bins_buf, row_buf, dst_buf, sems) = refs[-9:]
        b = pl.program_id(0)
        n_live = off_ref[n_runs]

        def kind(p):
            # 0 = one-hot permute, 1 = raw copy, 2 = duplicate (skipped)
            return pin_ref[p] >> PCOPY_SHIFT if packed else pcopy_ref[p]

        def fetches(p):
            slot = p % ring
            cols = pl.ds(pl.multiple_of(
                (pin_ref[p] & in_mask if packed else pin_ref[p]) * tile,
                tile), tile)
            return [pltpu.make_async_copy(src.at[:, cols], buf.at[slot],
                                          sems.at[i, slot])
                    for i, (src, buf) in enumerate(
                        ((bins_hbm, bins_buf), (row_hbm, row_buf),
                         (dst_hbm, dst_buf)))]

        def start(p):
            k = kind(p)
            plane, payload, dst = fetches(p)

            @pl.when(k < 2)
            def _():
                plane.start()
                payload.start()

            @pl.when(k == 0)  # a copy pair needs no destinations
            def _():
                dst.start()

        @pl.when(b == 0)
        def _prime():
            for q in range(ring - 1):
                @pl.when(q < n_live)
                def _():
                    start(q)

        bins_out[...] = jnp.zeros_like(bins_out)
        row_out[...] = jnp.zeros_like(row_out)

        def pair(p, carry):
            ahead = p + (ring - 1)  # its slot held pair p - 1, now done

            @pl.when(ahead < n_live)
            def _():
                start(ahead)

            k = kind(p)
            slot = p % ring
            out_t = pout_ref[p]
            sub = pl.ds(pl.multiple_of((out_t - b * run_tiles) * tile, tile),
                        tile)
            plane, payload, dst = fetches(p)

            @pl.when(k == 1)
            def _copy():  # untouched tile: the one pair of its output tile
                plane.wait()
                payload.wait()
                bins_out[:, sub] = bins_buf[slot]
                row_out[:, sub] = row_buf[slot]

            @pl.when(k == 0)
            def _permute():
                plane.wait()
                payload.wait()
                dst.wait()
                rel = dst_buf[slot] - out_t * tile  # [1, tile]: in-rows on lanes
                iota = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
                # PT[o, i] = 1 iff in-row i lands at out-row o of this tile.
                # dst is injective => every row has at most one 1, so each
                # output row below receives exactly one source row: the
                # limb matmul is exact bit transport, not a sum.
                PT = (iota == rel).astype(jnp.bfloat16)
                rbits = jax.lax.bitcast_convert_type(row_buf[slot], jnp.int32)
                # single limb for an 8-bit plane: values <= 255 are exact bf16
                plane_in = bins_buf[slot]
                plane_x = (plane_in.astype(jnp.int32) if plane8
                           else _limbs(plane_in, 2))
                X = jnp.concatenate([_limbs(rbits, 4), plane_x],
                                    axis=0).astype(jnp.bfloat16)
                out = jax.lax.dot_general(
                    X, PT, dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [4*rc + planes, tile]
                # The low three limbs recombine in f32 (a sum below 2**24 is
                # exact), the top one by the only integer shift. NOT
                # `limb2 << 16`: on a v5e (my chip run, PR 22) Mosaic's i32
                # shift-left by 16 zeroes every value under 128 — what a
                # bf16 -> f32 widening does to a bf16 denormal — so payloads
                # lost bits 16..22 wherever bit 23 was clear, and interpret
                # mode never showed it.
                low = (out[:rc] + 256.0 * out[rc:2 * rc]
                       + 65536.0 * out[2 * rc:3 * rc]).astype(jnp.int32)
                obits = low | (out[3 * rc:4 * rc].astype(jnp.int32) << 24)
                # rows not sourced by this pair recombine to bits 0, and the
                # accumulate ORs bits, so no float operation ever touches a
                # payload: -0.0 and denormals ride along exactly
                row_out[:, sub] = jax.lax.bitcast_convert_type(
                    jax.lax.bitcast_convert_type(row_out[:, sub], jnp.int32)
                    | obits, jnp.float32)
                obl = out[4 * rc:].astype(jnp.int32)
                if plane8:
                    bins_out[:, sub] = (bins_out[:, sub].astype(jnp.int32)
                                        + obl).astype(bins_out.dtype)
                else:
                    bins_out[:, sub] += obl[:gp] | (obl[gp:] << 8)

            return carry

        jax.lax.fori_loop(off_ref[b], off_ref[b + 1], pair, 0)

    return kernel


# The kernel's tables are scalar-prefetched into SMEM: one int32 a pair
# each for the input tile, the output tile and the pair's kind, and the run
# offsets, one int32 a grid step and one more. The v5e's SMEM holds 1 MiB;
# three pair tables of max_pairs_bound(T, 2K) words (T = N / 512 tiles) and
# the offsets fit to ~10.9 M rows a chip, and three alone take 1.18 MiB at
# 13,184,290 (AOT, the compiler's own refusal). Past the budget below the
# kind rides the input tile's word above PCOPY_SHIFT: two tables, to
# ~16.7 M rows. Three where they fit: the packed word costs a pair a shift
# and a mask.
PCOPY_SHIFT = 28
SMEM_TABLE_BYTES = (1 << 20) - (8 << 10)


def pair_tables_packed(max_pairs: int, n_runs: int) -> bool:
    """Whether the kind rides the input tile's word: three tables of
    max_pairs int32 words and the n_runs + 1 run offsets would not fit
    SMEM_TABLE_BYTES."""
    return 4 * (3 * max_pairs + n_runs + 1) > SMEM_TABLE_BYTES


def run_offsets(pair_out: jax.Array, n_pairs: jax.Array, run_tiles: int,
                n_runs: int) -> jax.Array:
    """[n_runs + 1] int32: the first pair of each run of run_tiles output
    tiles in the out-sorted table, and n_pairs last. One searchsorted of
    the run boundaries: the table's tail repeats its last live pair, whose
    output tile lies below the last boundary, so the clamp to n_pairs
    holds every offset inside the live pairs."""
    bounds = jnp.arange(n_runs + 1, dtype=jnp.int32) * run_tiles
    return jnp.minimum(jnp.searchsorted(pair_out, bounds).astype(jnp.int32),
                       n_pairs[0])


@partial(jax.jit, static_argnames=("tile", "interpret", "run_tiles", "ring"))
def _pallas_compact_call(bins_p, row_p, dst, pair_in, pair_out, is_copy,
                         n_pairs, tile: int, interpret: bool,
                         run_tiles: int = 0, ring: int = 0):
    """run_tiles, ring: compact_blocks' choice where 0."""
    Gp, N = bins_p.shape
    rc = row_p.shape[0]
    mp = pair_in.shape[0]
    plane8 = bins_p.dtype.itemsize == 1
    auto_run, auto_ring = compact_blocks(Gp, bins_p.dtype.itemsize, rc, tile)
    run_tiles, ring = run_tiles or auto_run, ring or auto_ring
    n_runs = -(-(N // tile) // run_tiles)
    off = run_offsets(pair_out, n_pairs, run_tiles, n_runs)
    packed = pair_tables_packed(mp, n_runs)
    if packed:
        if N // tile >= 1 << PCOPY_SHIFT:
            raise ValueError(f"{N // tile} compaction tiles do not leave "
                             "the pair table's kind its bits")
        tables = (pair_in | (is_copy << PCOPY_SHIFT), pair_out, off)
    else:
        tables = (pair_in, pair_out, is_copy, off)

    def out_map(b, *_):
        return 0, b
    # the grid walks the runs of output tiles, each visited once (every
    # tile's identity pair is live, so no run is empty); the last run is
    # short where T % run_tiles != 0, and its block's tail is not written
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(tables),
        grid=(n_runs,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=[pl.BlockSpec((Gp, run_tiles * tile), out_map),
                   pl.BlockSpec((rc, run_tiles * tile), out_map)],
        scratch_shapes=[pltpu.VMEM((ring, Gp, tile), bins_p.dtype),
                        pltpu.VMEM((ring, rc, tile), jnp.float32),
                        pltpu.VMEM((ring, 1, tile), jnp.int32),
                        pltpu.SemaphoreType.DMA((3, ring))],
    )
    return pl.pallas_call(
        _make_compact_kernel(tile, Gp, rc, plane8, packed, run_tiles, ring,
                             n_runs),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Gp, N), bins_p.dtype),
            jax.ShapeDtypeStruct((rc, N), jnp.float32),
        ],
        # the ring and its semaphores carry from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the jitted wrapper's own name, not compact_rows': the trace names
        # the custom call after it, and the benchmark's
        # train.compact_kernel_ms_per_tree finds it by ^_pallas_compact_call
        name="_pallas_compact_call",
    )(*tables, bins_p, row_p, dst.reshape(1, N))


def compact_rows(bins_p: jax.Array, row_p: jax.Array, dst: jax.Array,
                 lefts: LeftCounts, starts: jax.Array, counts: jax.Array,
                 valid: jax.Array, *, tile: int = COMPACT_TILE,
                 use_pallas: bool = True, interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply the forward permutation dst [N] to bins_p [Gp, N] (uint8, or
    int32 with values < 2**16) and row_p [rc, N] (f32 payload, one row per
    channel, moved bit-exactly). The output bin plane keeps bins_p's dtype.
    Returns (bins, rows, work): work [4] int32 is COMPACT_WORK_FIELDS of the
    kernel call, zeros on the XLA path.

    dst and lefts are range_partition_dst's, for the same K ranges
    (starts, counts, valid) and the same tile.
    Pallas path requirements: N % tile == 0, rc % 8 == 0 (the payload's
    limbs stack on whole sublane tiles), Gp % 8 == 0 for int32 planes and
    Gp % 32 == 0 for 8-bit planes (Mosaic (32, 128) tiling), the ranges
    disjoint.
    The XLA path is a plain permutation scatter — exact on CPU, used when
    no TPU backend is live.
    """
    if not use_pallas:
        bins_o = jnp.zeros_like(bins_p).at[:, dst].set(
            bins_p, unique_indices=True)
        row_o = jnp.zeros_like(row_p).at[:, dst].set(
            row_p, unique_indices=True)
        return bins_o, row_o, jnp.zeros(len(COMPACT_WORK_FIELDS), jnp.int32)
    if row_p.shape[0] % 8:
        raise ValueError("compaction kernel needs the payload's channel "
                         f"count padded to 8, got {row_p.shape[0]}")
    if (lefts.tiles.shape[0] - 1) * tile != dst.shape[0]:
        raise ValueError(
            f"LeftCounts holds {lefts.tiles.shape[0] - 1} tiles, "
            f"{dst.shape[0]} rows at tile {tile} need {dst.shape[0] // tile}")
    pair_in, pair_out, is_copy, n_pairs = build_pair_tables(
        lefts, starts, counts, valid, tile)
    row_f32 = row_p.astype(jnp.float32)
    dst_i32 = dst.astype(jnp.int32)
    bins_o, row_o = _pallas_compact_call(
        bins_p, row_f32, dst_i32, pair_in, pair_out, is_copy, n_pairs, tile,
        interpret)
    run_tiles, _ = compact_blocks(bins_p.shape[0], bins_p.dtype.itemsize,
                                  row_p.shape[0], tile)
    n_runs = -(-(dst.shape[0] // tile) // run_tiles)
    return bins_o, row_o, pair_work_counts(is_copy, n_pairs, n_runs)
