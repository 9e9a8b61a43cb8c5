"""Pallas TPU histogram kernels: the one-hot stays in VMEM.

The XLA formulation in ops/histogram.py materializes the [G, chunk, B]
one-hot operand of the contraction unless XLA fuses it into the dot; at
HIGGS scale (N=10.5M, B=256) a materialized one-hot costs G*N*B*4 bytes of
HBM traffic per histogram — catastrophically bandwidth-bound. These kernels
generate each one-hot tile INSIDE the kernel (VMEM-resident, never touches
HBM) and feed the MXU directly, so HBM traffic drops to the irreducible
G*N*(bins + gh) bytes. The wave kernel (pallas_histogram_slots_ragged: the
device learner's and the streamed learner's) walks a table of (row tile,
slot) pairs (tile_slot_pairs); per grid step (group block, pair) and per
REAL group of the block:

    X[L*CHp, TN]   = bf16_limbs(gh_tile * (slot_tile == slot_of_pair))
    onehot[Bp, TN] = (iota_sublane == bins_tile[g][None, :])  # VPU, bf16
    acc[L*CHp, Bp] = X . onehot^T          # MXU: bf16 x bf16 -> f32
    out[slot_of_pair, g] += acc[0:CH] + acc[CHp:CHp+CH] + ...  # [CH, Bp]

The bin row is lane-major as it arrives, so the one-hot is a sublane
broadcast and a compare (no lanes-to-sublanes relayout), and the
contraction runs over the last dimension of both operands (the q @ k^T
form, as ops/compact_pallas.py). X is the gradient tile of the pair's ONE
slot as L bfloat16 limbs stacked on the sublanes (bf16_limbs), CHp = CH up
to a packed bfloat16 tile's 16 rows: its height is set by the dtype policy
and the channel count alone, so a wave tile costs what a root tile costs
(the rows are leaf-contiguous: a tile holds one slot except at a range's
ends, and is listed once for each it holds). L = 3 holds a float32
exactly, so f32=True is the float32 histogram of the unrounded gradients in
ONE MXU pass where float32 operands at Precision.HIGHEST cost six, three of
them against the one-hot's all-zero low parts; L = 1 is the bfloat16
default and the quantized path. Bp is num_bins rounded up to whole 128-lane
tiles. The pairs are slot-major, so the output block (1, GB, CH, Bp) of a
slot is resident for that slot's run of pairs and written once (the
grouped-matmul form). The dense kernel of the host learners
(pallas_histogram) still builds onehot[TN, B] and contracts gh_tile^T @
onehot, [CH, B].

GB is chosen per call by _prep_bins/_group_block: as large as the output
block fits comfortably in VMEM (32 -> 16 -> 8; bigger blocks amortize
per-grid-step work), never below 8 — Mosaic requires the second-to-last
block dim to be a multiple of 8 (or the full array dim); a (1, TN) bins
block fails to lower on real TPU hardware. 8-bit bin planes (uint8) pass
through unwidened — 4x less HBM traffic for the dominant [G, N] array —
with GB pinned to 32 (Mosaic tiles 8-bit as (32, 128)) and the group row
widened to i32 in-register for the compare. The dense kernel's output
block for a group slab is revisited across the N tiles (TPU grids run
sequentially), accumulating in VMEM; step 0 zero-initializes.

Counterpart of the CUDA shared-memory scatter kernels
(src/treelearner/cuda/cuda_histogram_constructor.cu:20-513) — same
"accumulate in fast memory, flush once" structure, with the TPU twist that
the accumulation is an MXU contraction instead of atomic scatters.

Used on TPU backends (ops/histogram.py routes here from on_tpu()); the XLA
path is the CPU's. Correctness is pinned by tests running the kernels in
interpret mode against the XLA path and a numpy reference.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry

# classify these entries' jit cache misses as kernel compiles (telemetry's
# recompile watcher keeps them in a counter separate from XLA churn)
for _fn in ("pallas_histogram", "pallas_histogram_slots_ragged"):
    telemetry.register_kernel_fn(_fn)

DEFAULT_TILE_ROWS = 1024  # best of {512, 1024, 2048, 4096} on v5e
MIN_GROUP_BLOCK = 8  # Mosaic minimum for the second-to-last block dim


def _group_block(n_groups: int, n_channels: int, num_bins: int,
                 acc_bytes: int = 4) -> int:
    """Largest useful group block whose output block stays comfortably in
    VMEM. Bigger blocks amortize the per-grid-step work (the gradient
    operand's build runs once per (block, tile)): 8 -> 32 measured +13%
    end-to-end training throughput on v5e. Clamped to the group count
    rounded up to 8 so small-G datasets don't pay for dead padded groups."""
    cap = max(-(-n_groups // MIN_GROUP_BLOCK) * MIN_GROUP_BLOCK,
              MIN_GROUP_BLOCK)
    for gb in (32, 16):
        if gb <= cap and gb * n_channels * num_bins * acc_bytes <= (4 << 20):
            return gb
    return MIN_GROUP_BLOCK


def _prep_bins(bins: jax.Array, n_channels: int, num_bins: int):
    """Bin-plane dtype + group-block policy shared by the two wrappers.

    8-bit planes (uint8 bins) pass through UNWIDENED — the dominant [G, N]
    array moves 4x fewer HBM bytes — and the kernels widen each group row
    to i32 in-register for the one-hot compare (Mosaic has no elementwise
    8-bit vectors). Mosaic tiles 8-bit arrays as (32, 128), so the bins
    block's group dim is pinned to 32; when the matching (32, CH, B) f32
    output block would blow the VMEM budget, widen to int32 up front and
    let _group_block pick a smaller block instead."""
    if (bins.dtype.itemsize == 1
            and 32 * n_channels * num_bins * 4 <= (4 << 20)):
        return bins, 32
    return bins.astype(jnp.int32), _group_block(
        bins.shape[0], n_channels, num_bins)


def _make_kernel(num_bins: int, tile_rows: int, compute_dtype, acc_dtype,
                 group_block: int):
    def kernel(bins_ref, gh_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        gh = gh_ref[...].astype(compute_dtype)
        iota = jax.lax.broadcasted_iota(jnp.int32, (tile_rows, num_bins), 1)
        for gi in range(group_block):  # unrolled: static VMEM indices
            b = bins_ref[gi, :].astype(jnp.int32)  # widen 8-bit in-register
            onehot = (b[:, None] == iota).astype(compute_dtype)  # VMEM only
            # [CH, B] orientation: B rides the 128-lane dim. The [B, CH]
            # orientation pads CH (2-6) up to 128 output lanes — a 20x+ FLOP
            # inflation that made histogram time scale with num_bins*128
            # instead of num_bins*CH.
            acc = jax.lax.dot_general(
                gh, onehot,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=acc_dtype,
                # without HIGHEST the MXU decomposes f32 operands into bf16
                # passes, silently giving f32-mode the bf16 noise floor
                precision=(jax.lax.Precision.HIGHEST
                           if compute_dtype == jnp.float32 else
                           jax.lax.Precision.DEFAULT))  # [CH, B]
            out_ref[gi] += acc

    return kernel


def hist_force_f32() -> bool:
    """LGBM_TPU_HIST_F32=1 asks for the float32 histogram: the wave kernel
    then carries the gradients as three exact bfloat16 limbs (hist_operand
    "bf16x3"), the dense kernel takes float32 operands. Resolved by the
    unjitted dispatch wrappers in ops.histogram so it enters the jit cache
    key as the `f32` static arg — but outer jitted callers
    (grow_tree_on_device) bake the value into their own trace, so set it
    BEFORE the first training call, not mid-run."""
    return os.environ.get("LGBM_TPU_HIST_F32", "").lower() not in (
        "", "0", "false", "off")


def hist_operand(quantized: bool, f32: bool) -> str:
    """What pallas_histogram_slots_ragged feeds the MXU as its gradient
    operand under a dtype policy: "int" (quantized: small exact ints in one
    bfloat16 limb, int32 accumulation), "bf16x3" (f32: three exact bfloat16
    limbs) or "bf16" (the default: one limb, the bfloat16 rounding)."""
    if quantized:
        return "int"
    return "bf16x3" if f32 else "bf16"


@partial(jax.jit, static_argnames=("num_bins", "tile_rows", "quantized",
                                   "f32", "interpret"))
def pallas_histogram(bins: jax.Array, gh: jax.Array, num_bins: int,
                     tile_rows: int = DEFAULT_TILE_ROWS,
                     quantized: bool = False,
                     f32: bool = False,
                     interpret: bool = False) -> jax.Array:
    """[G, N] bins + [N, CH] gh -> [G, num_bins, CH] histogram.

    quantized: int8 one-hot x int8 gh with exact int32 accumulation
    (MXU-native). Float path: bf16 operands with f32 accumulation — the MXU
    runs bf16 at full rate while f32 matmuls cost multiple passes; the
    one-hot is exactly representable and only the gh operand rounds (well
    under the reference's own single-precision histogram noise floor,
    feature_histogram.hpp hist_t=float). f32=True forces f32 operands.
    Rows are padded to the tile size with zero gh (contributes nothing).
    """
    G, N = bins.shape
    CH = gh.shape[1]
    if quantized:
        compute_dtype, acc_dtype = jnp.int8, jnp.int32
    elif f32:
        compute_dtype, acc_dtype = jnp.float32, jnp.float32
    else:
        compute_dtype, acc_dtype = jnp.bfloat16, jnp.float32
    n_tiles = max(-(-N // tile_rows), 1)
    pad = n_tiles * tile_rows - N
    bins, GB = _prep_bins(bins, CH, num_bins)
    if pad:
        bins = jnp.pad(bins, ((0, 0), (0, pad)), constant_values=0)
        gh = jnp.pad(gh, ((0, pad), (0, 0)))  # zero gh => no contribution
    g_blocks = max(-(-G // GB), 1)
    g_pad = g_blocks * GB - G
    if g_pad:  # padded groups accumulate into rows sliced off below
        bins = jnp.pad(bins, ((0, g_pad), (0, 0)), constant_values=0)
    out = pl.pallas_call(
        _make_kernel(num_bins, tile_rows, compute_dtype, acc_dtype, GB),
        grid=(g_blocks, n_tiles),
        in_specs=[
            pl.BlockSpec((GB, tile_rows), lambda g, t: (g, t)),
            pl.BlockSpec((tile_rows, CH), lambda g, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((GB, CH, num_bins),
                               lambda g, t: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g_blocks * GB, CH, num_bins),
                                       acc_dtype),
        interpret=interpret,
        name="pallas_histogram",
    )(bins, gh)
    return out[:G].transpose(0, 2, 1)  # [G, B, CH]; 172KB, free vs the dot


def tile_slot_pairs(starts: jax.Array, ends: jax.Array, valid: jax.Array,
                    n_tiles: int, tile_rows: int):
    """(row tile, slot) pair table for the ragged wave histogram.

    starts/ends [K] int32 half-open row ranges, DISJOINT (leaf-contiguous
    layout), valid [K] bool; slot k is range k. Returns (tiles [P], slots
    [P], n_pairs [1], n_active [1]) int32 with P = n_tiles + 2 * K: every
    (tile, slot) whose tile overlaps that slot's range, slot-major with the
    tiles ascending within a slot, so each slot's pairs are one run. A slot
    whose range is empty or invalid is listed ONCE, with tile 0: the kernel
    writes an output block only where a pair visits it, and that visit
    finds no row of the slot and writes zeros. n_active counts the distinct
    tiles among the live ranges' pairs: disjoint ranges share a tile only
    where one starts in the tile another ends in, so n_pairs <= n_tiles +
    K. The kernel's pair axis is n_pairs long; the entries past it, never
    walked, repeat the last pair.
    """
    K = starts.shape[0]
    live = valid & (ends > starts)
    first = jnp.where(live, jnp.clip(starts // tile_rows, 0, n_tiles - 1), 0)
    last = jnp.where(live, jnp.clip((ends - 1) // tile_rows, 0, n_tiles - 1),
                     0)
    cnt = last - first + 1  # a dead slot: its one visit
    off = jnp.cumsum(cnt) - cnt  # [K] where each slot's run begins
    n_pairs = off[-1] + cnt[-1]
    p = jnp.minimum(jnp.arange(n_tiles + 2 * K, dtype=jnp.int32),
                    n_pairs - 1)
    slots = jnp.searchsorted(off, p, side="right", method="compare_all") - 1
    tiles = jnp.take(first - off, slots) + p
    # a live range's first tile is another's last: listed twice, one tile
    shared = (live[:, None] & live[None, :]
              & (first[:, None] == last[None, :])
              & (starts[:, None] > starts[None, :])).any(axis=1)
    n_active = jnp.sum(jnp.where(live, cnt, 0)) - jnp.sum(shared)
    return tiles, slots, n_pairs[None], n_active[None]


def bf16_limbs(x: jax.Array, n: int) -> jax.Array:
    """f32 [r, T] -> bf16 [n * r, T]: x as n bfloat16 limbs stacked along
    the rows, limb i the bfloat16 rounding of what limbs < i left over.
    Three limbs hold a float32 exactly (24 significand bits = 3 x 8, the
    same exponent range; a limb under bfloat16's smallest normal, that of
    an |x| below ~2e-31, is the one exception a TPU flushes): hi + mid + lo
    == x bit for bit (-0.0 comes back +0.0), every subtraction below exact.
    One limb is the plain bfloat16 rounding."""
    parts = []
    for i in range(n):
        limb = x.astype(jnp.bfloat16)
        parts.append(limb)
        if i + 1 < n:
            x = x - limb.astype(jnp.float32)
    return parts[0] if n == 1 else jnp.concatenate(parts, axis=0)


def _make_slots_ragged_kernel(bins_p: int, tile_rows: int, ch: int,
                              limbs: int, acc_dtype, group_block: int,
                              n_groups: int):
    """bins_p: the bin axis padded to whole 128-lane tiles. limbs: how many
    bfloat16 limbs carry the gradient operand (3 = exact float32, 1 = the
    bfloat16 rounding or the quantized path's small ints).
    n_groups: the plane's real groups; the groups behind them in the last
    group block are padding and get no one-hot and no contraction."""
    g_blocks = -(-n_groups // group_block)
    last_block_groups = n_groups - (g_blocks - 1) * group_block
    chp = -(-ch // 16) * 16  # a limb block starts on a packed bf16 tile
    quantized = jnp.issubdtype(jnp.dtype(acc_dtype), jnp.integer)

    def kernel(tiles_ref, slots_ref, bins_ref, gh_ref, slot_ref, out_ref):
        del tiles_ref  # the index maps' alone
        p = pl.program_id(1)
        full_block = pl.program_id(0) < g_blocks - 1
        k = slots_ref[p]

        # the output block is slot k's: zeroed where the slot's run of
        # pairs begins, written back by the pipeline where it ends
        @pl.when((p == 0) | (slots_ref[jnp.maximum(p - 1, 0)] != k))
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        s = slot_ref[...]  # [1, TN] int32: rows on the lanes
        ghc = gh_ref[...]  # [ch, TN] f32 (quantized: exact small ints)
        # this slot's gradient tile, row j = channel, built [chp, TN] f32 in
        # VMEM straight from the lane-major payload rows (a sublane
        # broadcast each; rows past ch stay zero). Strictly 2D broadcasts:
        # per-channel masked adds, not a concat (which lowers to a serial
        # copy chain in Mosaic).
        row = jax.lax.broadcasted_iota(jnp.int32, (chp, 1), 0)
        gsum = jnp.zeros((chp, tile_rows), jnp.float32)
        for c in range(ch):
            gsum += ghc[c:c + 1, :] * (row == c).astype(jnp.float32)
        # the MXU multiplies bfloat16: the slot's rows go in as exact
        # bfloat16 limbs stacked on the sublanes, [limbs * chp, TN]; a row
        # of another slot (or the dump slot) is a zero column
        X = bf16_limbs(gsum * (s == k).astype(jnp.float32), limbs)
        iota = jax.lax.broadcasted_iota(jnp.int32, (bins_p, tile_rows), 0)

        def group(gi):
            # onehot[b, n] from the lane-major bin row: a sublane broadcast,
            # no relayout; 0 and 1 are exact in bfloat16
            b = bins_ref[gi:gi + 1, :].astype(jnp.int32)  # [1, TN]
            onehot = (iota == b).astype(jnp.bfloat16)
            # one MXU pass contracting the last dimension of both (the
            # q @ k^T form): a limb times 0 or 1 is exact and the
            # accumulation is float32, so the limb blocks of acc sum to the
            # float32 histogram at the gradients' full 24 bits
            acc = jax.lax.dot_general(
                X, onehot, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [limbs*chp, Bp]
            h = acc[:ch]
            for i in range(1, limbs):
                h = h + acc[i * chp:i * chp + ch]
            # quantized: per-tile partial sums are exact ints in f32
            # (<= tile_rows * 127 * 255 < 2**24); accumulate int32
            out_ref[0, gi] += h.astype(acc_dtype) if quantized else h

        for gi in range(last_block_groups):
            group(gi)
        if g_blocks > 1 and last_block_groups < group_block:
            @pl.when(full_block)
            def _rest():
                for gi in range(last_block_groups, group_block):
                    group(gi)

    return kernel


@partial(jax.jit, static_argnames=("num_bins", "n_slots", "tile_rows",
                                   "quantized", "f32", "n_groups",
                                   "interpret"))
def pallas_histogram_slots_ragged(bins: jax.Array, gh: jax.Array,
                                  slot: jax.Array, tiles: jax.Array,
                                  slots: jax.Array, n_pairs: jax.Array,
                                  num_bins: int, n_slots: int,
                                  tile_rows: int = DEFAULT_TILE_ROWS,
                                  quantized: bool = False,
                                  f32: bool = False,
                                  n_groups: int | None = None,
                                  interpret: bool = False) -> jax.Array:
    """Per-slot histograms over a table of (row tile, slot) pairs:
    [G, N] bins + [CH, N] gh + [N] slot ids -> [G, num_bins, n_slots*CH],
    where row n adds its gh to channel block slot[n] and a row whose slot
    is outside [0, n_slots) adds nowhere.

    The rows-in-leaf wave histogram: `tiles`/`slots` (from
    tile_slot_pairs) name every row tile that overlaps a selected leaf
    range beside that range's slot; the grid walks ONLY those pairs via
    scalar-prefetched index maps (the grouped-matmul form: slot-major, the
    output block a pair accumulates into is its slot's), and a pair
    contracts the rows of ITS slot alone, an operand one slot high: a tile
    costs what the slots it holds cost, not what n_slots would. So per-wave
    cost is O(rows in selected leaves) instead of O(N). A row adds to its
    slot only under a listed pair of that slot, so every slot < n_slots
    must appear in `slots` (its block is otherwise never written) and rows
    outside every selected range carry slot >= n_slots (the dump slot).
    `n_pairs` is a traced [1] int32, the pair axis's bound: the grid
    walks the first n_pairs entries of the tables and no others. The root
    pass is the same call with one slot.

    gh is ALWAYS [CH, N] f32 here: the gh rows of the leaf-contiguous
    payload, rows on the lanes like bins (block (CH, tile_rows)), and slot
    [N] rides as [1, N] (block (1, tile_rows)). An [N, CH] / [N, 1]
    operand would pad its minor dimension to 128 lanes in HBM and drag
    that layout into the caller's glue (ops/compact_pallas.py, step 3).

    Both matmul operands are bfloat16 on every path, one MXU pass a group
    with float32 accumulation; the dtype policy says how many bfloat16
    limbs carry the gradients (bf16_limbs). f32=True: three, which hold a
    float32 exactly, so the result is the float32 histogram of the
    unrounded gradients. Default: one, the gradients rounded to bfloat16.
    quantized=True: gh holds small exact ints (<= 255, exact in one limb),
    per-tile partials are exact in f32 and accumulate int32 — bit-identical
    to the int8 dense path.

    n_groups: the plane's real group count where the caller padded the
    plane (Mosaic tiles 8-bit as (32, 128)): the groups behind it cost
    nothing and the result is [n_groups, num_bins, n_slots*CH].
    """
    G, N = bins.shape
    n_groups = G if n_groups is None else n_groups
    CH = gh.shape[0]
    if N % tile_rows:
        raise ValueError("ragged histogram requires N padded to tile_rows")
    if not 0 < n_groups <= G:
        raise ValueError(f"n_groups {n_groups} outside the plane's {G} rows")
    limbs = 3 if hist_operand(quantized, f32) == "bf16x3" else 1
    acc_dtype = jnp.int32 if quantized else jnp.float32
    bins_p = -(-num_bins // 128) * 128  # whole lane tiles; no bin >= num_bins
    bins, GB = _prep_bins(bins, CH, bins_p)
    slot = slot.reshape(1, N).astype(jnp.int32)
    g_blocks = -(-n_groups // GB)
    g_pad = g_blocks * GB - G  # negative where the caller padded past it
    if g_pad > 0:
        bins = jnp.pad(bins, ((0, g_pad), (0, 0)), constant_values=0)
    # the pair axis walks the live pairs alone: its bound is the traced
    # n_pairs, an operand of the kernel call (every slot is listed, so
    # n_pairs >= n_slots and every slot's block is written)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g_blocks, jnp.maximum(n_pairs[0], 1)),
        in_specs=[
            pl.BlockSpec((GB, tile_rows), lambda g, p, tr, sl: (g, tr[p])),
            pl.BlockSpec((CH, tile_rows), lambda g, p, tr, sl: (0, tr[p])),
            pl.BlockSpec((1, tile_rows), lambda g, p, tr, sl: (0, tr[p])),
        ],
        out_specs=pl.BlockSpec((1, GB, CH, bins_p),
                               lambda g, p, tr, sl: (sl[p], g, 0, 0)),
    )
    out = pl.pallas_call(
        _make_slots_ragged_kernel(bins_p, tile_rows, CH, limbs, acc_dtype,
                                  GB, n_groups),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, g_blocks * GB, CH, bins_p),
                                       acc_dtype),
        interpret=interpret,
        name="pallas_histogram_slots_ragged",
    )(tiles.astype(jnp.int32), slots.astype(jnp.int32), bins,
      gh.astype(jnp.float32), slot)
    # [S, G, CH, B] -> [G, B, S*CH]
    return out[:, :n_groups, :, :num_bins].transpose(1, 3, 0, 2).reshape(
        n_groups, num_bins, n_slots * CH)
