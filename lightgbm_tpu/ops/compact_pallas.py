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
     (in_tile -> out_tile), sorted by out_tile, is the kernel's grid.
  3. Pallas kernel (_pallas_compact_call): sequential grid over pairs.
     Every per-row operand has the ROWS ON THE LANES: the bin plane
     [Gp, N] (block (Gp, T)), the f32 payload [rc, N] (block (rc, T), rc a
     multiple of 8) and dst [1, N] (block (1, T)). A custom call's operand
     layout is fixed, and XLA carries it back into the glue that makes the
     operand: an [N, 1] or [N, rc] operand pads its minor dimension to 128
     lanes (2.15 GB for 4M int32, not 16 MB) and the whole wave's routing
     arithmetic then runs at 8 useful values a vector register. Per pair
     the kernel builds the one-hot PT[o, i] = (o == dst[i] - out*T) from
     the lane-major dst (a sublane broadcast, no relayout), stacks the
     payload's four limbs and the plane's limb(s) into ONE operand
     X [4*rc + Gp*(1|2), T] and accumulates
     out[c, o] += sum_i X[c, i] * PT[o, i] — one matmul contracting the
     last dimension of both (the q @ k^T form), so payload and plane move
     the same way. Consecutive pairs share the output block (sorted
     order), so accumulation stays in VMEM; a scalar-prefetched copy flag
     routes untouched tiles through a plain VPU copy with no matmul.

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

from .. import perfmodel, telemetry
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
    # dst before the pair tables: they no longer read it, and a scheduler
    # left free makes it last, straight into the kernel from HBM. Made
    # first, the compiler has the tables' sort to prefetch it into fast
    # memory (its layout gains S(1)), where the kernel's per-pair (1, tile)
    # block DMAs cost 11 % less of the whole kernel (PERF.md, PR 33).
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
    max_pairs_bound(T, 2 * K); entries past n_pairs repeat the last real
    pair (same blocks -> the kernel skips DMA and compute for them). pcopy
    per pair: 0 = one-hot permute, 1 = raw block copy (untouched identity
    tile), 2 = SKIP (duplicate of the previous pair — processing it would
    double-count rows). Sorted by out_tile so the kernel revisits each
    output block in one consecutive run.

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
# duplicates), and the static grid length the kernel steps through.
COMPACT_WORK_FIELDS = ("compact_pairs", "compact_copy_pairs",
                       "compact_permute_pairs", "compact_grid_steps")


def pair_work_counts(pcopy: jax.Array, n_pairs: jax.Array) -> jax.Array:
    """[4] int32, COMPACT_WORK_FIELDS of one pair list (build_pair_tables'
    pcopy and n_pairs): two masked sums over the table, nothing per row."""
    live = jnp.arange(pcopy.shape[0], dtype=jnp.int32) < n_pairs[0]
    return jnp.stack([
        n_pairs[0], jnp.sum(live & (pcopy == 1), dtype=jnp.int32),
        jnp.sum(live & (pcopy == 0), dtype=jnp.int32),
        jnp.int32(pcopy.shape[0])])


def _limbs(x_int: jax.Array, n: int) -> jax.Array:
    """Split int32 values [c, T] into n 8-bit limbs stacked along the rows,
    [n*c, T] (each limb <= 255: exact as a bf16 matmul operand)."""
    parts = [jnp.bitwise_and(jax.lax.shift_right_logical(x_int, 8 * i), 255)
             for i in range(n)]
    return jnp.concatenate(parts, axis=0)


def _make_compact_kernel(tile: int, gp: int, rc: int, plane8: bool):
    """plane8: the bin plane is an 8-bit dtype (uint8). Its values fit one
    bf16 limb, so the plane rides the transport matmul as Gp rows instead
    of 2*Gp, and the accumulate widens to i32 in-register (Mosaic has no
    elementwise 8-bit vectors) before narrowing back to the 8-bit output
    block."""

    def kernel(pin_ref, pout_ref, pcopy_ref, npair_ref,
               bins_ref, row_ref, dst_ref, bins_out, row_out):
        p = pl.program_id(0)
        out_t = pout_ref[p]
        first = (p == 0) | (out_t != pout_ref[jnp.maximum(p - 1, 0)])
        # pcopy == 2: duplicate pair demoted to a skip by build_pair_tables
        # (a duplicate is never the first pair of its output block, so the
        # zero-init below cannot be skipped by accident)
        active = (p < npair_ref[0]) & (pcopy_ref[p] < 2)
        is_copy = pcopy_ref[p] == 1

        @pl.when(active & is_copy)
        def _copy():  # untouched tile: single pair for this block, plain copy
            bins_out[...] = bins_ref[...]
            row_out[...] = row_ref[...]

        @pl.when(active & jnp.logical_not(is_copy))
        def _permute():
            @pl.when(first)
            def _zero():
                bins_out[...] = jnp.zeros_like(bins_out)
                row_out[...] = jnp.zeros_like(row_out)

            rel = dst_ref[...] - out_t * tile  # [1, tile]: in-rows on lanes
            iota = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
            # PT[o, i] = 1 iff in-row i lands at out-row o of this block.
            # dst is injective => every row has at most one 1, so each
            # output row below receives exactly one source row: the limb
            # matmul is exact bit transport, not a sum.
            PT = (iota == rel).astype(jnp.bfloat16)
            rbits = jax.lax.bitcast_convert_type(row_ref[...], jnp.int32)
            # single limb for an 8-bit plane: values <= 255 are exact bf16
            plane = (bins_ref[...].astype(jnp.int32) if plane8
                     else _limbs(bins_ref[...], 2))
            X = jnp.concatenate([_limbs(rbits, 4), plane],
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
            row_out[...] = jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(row_out[...], jnp.int32) | obits,
                jnp.float32)
            obl = out[4 * rc:].astype(jnp.int32)
            if plane8:
                bins_out[...] = (bins_out[...].astype(jnp.int32)
                                 + obl).astype(bins_out.dtype)
            else:
                bins_out[...] += obl[:gp] | (obl[gp:] << 8)

    return kernel


@partial(jax.jit, static_argnames=("tile", "interpret"))
def _pallas_compact_call(bins_p, row_p, dst, pair_in, pair_out, is_copy,
                         n_pairs, tile: int, interpret: bool):
    Gp, N = bins_p.shape
    rc = row_p.shape[0]
    mp = pair_in.shape[0]
    plane8 = bins_p.dtype.itemsize == 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(mp,),
        in_specs=[
            pl.BlockSpec((Gp, tile), lambda p, pi, po, pc, npr: (0, pi[p])),
            pl.BlockSpec((rc, tile), lambda p, pi, po, pc, npr: (0, pi[p])),
            pl.BlockSpec((1, tile), lambda p, pi, po, pc, npr: (0, pi[p])),
        ],
        out_specs=[
            pl.BlockSpec((Gp, tile), lambda p, pi, po, pc, npr: (0, po[p])),
            pl.BlockSpec((rc, tile), lambda p, pi, po, pc, npr: (0, po[p])),
        ],
    )
    return pl.pallas_call(
        _make_compact_kernel(tile, Gp, rc, plane8),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Gp, N), bins_p.dtype),
            jax.ShapeDtypeStruct((rc, N), jnp.float32),
        ],
        interpret=interpret,
        # the jitted wrapper's own name, not compact_rows': the trace names
        # the custom call after it, and the benchmark's
        # train.compact_kernel_ms_per_tree finds it by ^_pallas_compact_call
        name="_pallas_compact_call",
    )(pair_in, pair_out, is_copy, n_pairs, bins_p, row_p,
      dst.reshape(1, N))


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
    if telemetry.enabled():
        # one-time capture (works at trace time too: tracers carry the
        # shape/dtype perfmodel's AOT cost_analysis re-lower needs)
        perfmodel.note_dispatch("compact", _pallas_compact_call,
                                bins_p, row_f32, dst_i32, pair_in, pair_out,
                                is_copy, n_pairs, tile, interpret)
    bins_o, row_o = _pallas_compact_call(
        bins_p, row_f32, dst_i32, pair_in, pair_out, is_copy, n_pairs, tile,
        interpret)
    return bins_o, row_o, pair_work_counts(is_copy, n_pairs)
