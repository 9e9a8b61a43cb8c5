"""Pallas histogram kernel correctness (interpret mode on CPU) vs the XLA
path and the numpy reference."""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.hist_pallas import (bf16_limbs, hist_operand,
                                          pallas_histogram,
                                          pallas_histogram_slots_ragged,
                                          tile_slot_pairs)
from lightgbm_tpu.ops.histogram import build_histogram


def _ref_hist(bins, gh, num_bins):
    G, N = bins.shape
    out = np.zeros((G, num_bins, gh.shape[1]))
    for g in range(G):
        for b in range(num_bins):
            out[g, b] = gh[bins[g] == b].sum(axis=0)
    return out


@pytest.mark.parametrize("n,tile", [(500, 128), (4096, 2048), (3000, 2048)])
def test_pallas_histogram_float(rng, n, tile):
    G, B = 5, 16
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    gh = rng.randn(n, 3).astype(np.float32)
    ours = np.asarray(pallas_histogram(
        jnp.asarray(bins), jnp.asarray(gh), B, tile_rows=tile, f32=True,
        interpret=True))
    np.testing.assert_allclose(ours, _ref_hist(bins, gh, B), rtol=1e-5,
                               atol=1e-4)
    xla = np.asarray(build_histogram(jnp.asarray(bins), jnp.asarray(gh), B))
    np.testing.assert_allclose(ours, xla, rtol=1e-5, atol=1e-4)


def test_pallas_histogram_bf16_default(rng):
    """The TPU default path: bf16 operands, f32 accumulation — sums must
    track the exact histogram to bf16 operand-rounding tolerance."""
    G, B, n = 4, 32, 20_000
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    gh = rng.randn(n, 3).astype(np.float32)
    ours = np.asarray(pallas_histogram(
        jnp.asarray(bins), jnp.asarray(gh), B, interpret=True))
    assert ours.dtype == np.float32
    ref = _ref_hist(bins, gh, B)
    np.testing.assert_allclose(ours, ref, rtol=2e-2, atol=2e-1)


def _slots_every_tile(bins, gh, slot, num_bins, n_slots, tile=512, **kw):
    """The wave kernel with EVERY (row tile, slot) pair listed: the
    per-slot histograms of the whole row set, the slots scattered over the
    rows as no leaf-contiguous layout would (rows padded to the tile with
    the dump slot, as the learner pads them)."""
    n = bins.shape[1]
    n_pad = -(-n // tile) * tile
    bins = np.pad(bins, ((0, 0), (0, n_pad - n)))
    gh = np.pad(gh.astype(np.float32), ((0, n_pad - n), (0, 0)))
    slot = np.pad(slot, (0, n_pad - n), constant_values=n_slots)
    return np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot),
        *_pairs_of_rows(slot, n_slots, tile), num_bins, n_slots,
        tile_rows=tile, interpret=True, **kw))


def _pairs_of_rows(slot, n_slots, tile):
    """The pair table of a slot row that is no set of ranges (the streamed
    learner's is not): slot-major, each tile that holds a row of the slot,
    and tile 0 for a slot with none."""
    pairs = []
    by_tile = slot.reshape(-1, tile)
    for s in range(n_slots):
        hit = np.flatnonzero((by_tile == s).any(axis=1))
        pairs += [(int(t), s) for t in hit] if hit.size else [(0, s)]
    tiles, slots = (jnp.asarray(a, jnp.int32) for a in zip(*pairs))
    return tiles, slots, jnp.full(1, len(pairs), jnp.int32)


def _ref_slots(bins, gh, slot, num_bins, s):
    return _ref_hist(bins, np.where((slot == s)[:, None], gh, 0), num_bins)


def test_pallas_histogram_slots(rng):
    """Slot-expanded wave histogram == per-slot masked histograms."""
    G, B, n, S = 3, 16, 3000, 4
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    gh = rng.randn(n, 3).astype(np.float32)
    slot = rng.randint(0, S + 2, size=n).astype(np.int32)  # S+ = dump
    ours = _slots_every_tile(bins, gh, slot, B, S, f32=True)
    assert ours.shape == (G, B, S * 3)
    for s in range(S):
        np.testing.assert_allclose(ours[..., s * 3:(s + 1) * 3],
                                   _ref_slots(bins, gh, slot, B, s),
                                   rtol=1e-5, atol=1e-4)


def test_pallas_histogram_slots_bf16_default(rng):
    """The default TPU wave path: bf16 operands, f32 accumulation."""
    G, B, n, S = 3, 16, 4000, 4
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    gh = rng.randn(n, 3).astype(np.float32)
    slot = rng.randint(0, S + 2, size=n).astype(np.int32)
    ours = _slots_every_tile(bins, gh, slot, B, S)
    assert ours.dtype == np.float32
    for s in range(S):
        np.testing.assert_allclose(ours[..., s * 3:(s + 1) * 3],
                                   _ref_slots(bins, gh, slot, B, s),
                                   rtol=2e-2, atol=2e-1)


def test_pallas_histogram_slots_quantized_exact(rng):
    """Quantized wave path: f32 gh rows holding small ints, bf16 matmul
    operands (exact up to 255), exact int32 accumulation."""
    G, B, n, S = 3, 16, 4000, 4
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    gh = np.stack([rng.randint(-4, 5, n), rng.randint(0, 6, n),
                   np.ones(n)], axis=1).astype(np.int64)
    slot = rng.randint(0, S + 2, size=n).astype(np.int32)
    ours = _slots_every_tile(bins, gh, slot, B, S, quantized=True)
    assert ours.dtype == np.int32
    for s in range(S):
        np.testing.assert_array_equal(
            ours[..., s * 3:(s + 1) * 3],
            _ref_slots(bins, gh, slot, B, s).astype(np.int64))


def _quantized_gh(rng, n):
    """[n, 3] float32 holding the quantized path's small exact ints."""
    return np.stack([rng.randint(-4, 5, n), rng.randint(0, 6, n),
                     np.ones(n)], axis=1).astype(np.float32)


def _coarse_grid_gh(rng, n):
    """[n, 3] float32 values k / 2**10, |k| < 2**12: a tile's partial sum
    is exact in float32, so the float32 sum in tile order is one number."""
    return (rng.randint(-(2 ** 12) + 1, 2 ** 12, size=(n, 3))
            / 2.0 ** 10).astype(np.float32)


def _ragged_setup(rng, n, tile, ranges, S, quantized=False):
    """Leaf-contiguous layout: slot < S only inside the given ranges."""
    G, B = 3, 16
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    if quantized:
        gh = _quantized_gh(rng, n)
    else:
        gh = rng.randn(n, 3).astype(np.float32)
    slot = np.full(n, S, dtype=np.int32)  # dump by default
    for k, (s, e) in enumerate(ranges):
        slot[s:e] = k
    return G, B, bins, gh, slot, _pairs(ranges, S, n // tile, tile)


def _pairs(ranges, n_slots, n_tiles, tile, valid=None):
    """tile_slot_pairs' three kernel operands for `ranges`, one a slot;
    the slots behind them are invalid."""
    pad = n_slots - len(ranges)
    assert pad >= 0
    valid = [True] * len(ranges) if valid is None else list(valid)
    return tile_slot_pairs(
        jnp.asarray([s for s, _ in ranges] + [0] * pad, jnp.int32),
        jnp.asarray([e for _, e in ranges] + [0] * pad, jnp.int32),
        jnp.asarray(valid + [False] * pad), n_tiles, tile)[:3]


@pytest.mark.parametrize("ranges", [
    [(0, 700), (1024, 1100), (2000, 3000)],
    [(512, 1024)],                      # tile-aligned single range
    [(100, 101), (3500, 4096)],         # tiny + tail
])
def test_pallas_histogram_slots_ragged(rng, ranges):
    from lightgbm_tpu.ops.hist_pallas import pallas_histogram_slots_ragged

    n, tile, S = 4096, 512, 4
    G, B, bins, gh, slot, pairs = _ragged_setup(rng, n, tile, ranges, S)
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), *pairs,
        B, S, tile_rows=tile, f32=True, interpret=True))
    assert ours.shape == (G, B, S * 3)
    # the grid walks only overlapping tiles, and each unused slot once
    assert int(pairs[2][0]) <= n // tile + S
    for s in range(S):
        ref = _ref_hist(bins, np.where((slot == s)[:, None], gh, 0.0), B)
        np.testing.assert_allclose(ours[..., s * 3:(s + 1) * 3], ref,
                                   rtol=1e-5, atol=1e-4)


def test_pallas_histogram_slots_ragged_quantized_exact(rng):
    """Quantized ragged path: f32 gh holding small ints, bf16 operands,
    int32 accumulation — the exact integer histogram of each range."""
    from lightgbm_tpu.ops.hist_pallas import pallas_histogram_slots_ragged

    n, tile, S = 4096, 512, 3
    ranges = [(0, 900), (1500, 2600), (3000, 4000)]
    G, B, bins, gh, slot, pairs = _ragged_setup(
        rng, n, tile, ranges, S, quantized=True)
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), *pairs,
        B, S, tile_rows=tile, quantized=True, interpret=True))
    assert ours.dtype == np.int32
    for s in range(S):
        np.testing.assert_array_equal(
            ours[..., s * 3:(s + 1) * 3],
            _ref_slots(bins, gh.astype(np.int64), slot, B, s).astype(np.int64))


def _brute_pairs(starts, ends, valid, n_tiles, tile):
    """The [T, K] overlap matrix, read slot-major: the pairs of the live
    slots, and one (tile 0) visit for each dead one."""
    lo = np.arange(n_tiles)[:, None] * tile
    over = ((lo < np.asarray(ends)[None, :])
            & (lo + tile > np.asarray(starts)[None, :])
            & np.asarray(valid)[None, :]
            & (np.asarray(ends) > np.asarray(starts))[None, :])
    pairs = []
    for k in range(over.shape[1]):
        hit = np.flatnonzero(over[:, k])
        pairs += [(int(t), k) for t in hit] if hit.size else [(0, k)]
    return pairs, int(over.any(axis=1).sum())


PAIR_CASES = {
    # name: (ranges, valid, n_tiles, tile)
    "apart_and_one_invalid": ([(0, 512), (1024, 1536), (4000, 4096)],
                              [True, True, False], 8, 512),
    "straddles_three_tiles": ([(500, 1030)], [True], 4, 512),
    "boundary_tile_holds_four_slots": (
        [(0, 1030), (1030, 1040), (1040, 1100), (1100, 3000)],
        [True] * 4, 8, 512),
    "all_ranges_inside_one_tile": (
        [(1030, 1100), (1100, 1101), (1200, 1500), (1500, 1530)],
        [True] * 4, 6, 512),
    "empty_and_invalid_among_valid": (
        [(0, 700), (900, 900), (1024, 1100), (2000, 3000), (3100, 3200)],
        [True, True, True, False, True], 8, 512),
    "slots_not_in_row_order": ([(3000, 4096), (0, 5), (600, 2900)],
                               [True] * 3, 8, 512),
    "one_slot_every_tile": ([(0, 8192)], [True], 8, 1024),
    "one_slot_none": ([(0, 0)], [True], 8, 1024),
    "whole_tiles_back_to_back": ([(0, 1024), (1024, 2048), (2048, 4096)],
                                 [True] * 3, 8, 512),
    "last_row_of_the_last_tile": ([(4095, 4096), (0, 1)], [True] * 2, 8, 512),
}


@pytest.mark.parametrize("case", sorted(PAIR_CASES))
def test_tile_slot_pairs_equal_the_brute_force_overlap(case):
    """Every (tile, slot) overlap listed exactly once, slot-major with the
    tiles ascending; a dead slot exactly once; n_pairs within the static
    bound; the tail repeats the last pair; the distinct tiles counted."""
    ranges, valid, T, tile = PAIR_CASES[case]
    K = len(ranges)
    starts, ends = [s for s, _ in ranges], [e for _, e in ranges]
    tiles, slots, n_pairs, n_active = (np.asarray(a) for a in tile_slot_pairs(
        jnp.asarray(starts, jnp.int32), jnp.asarray(ends, jnp.int32),
        jnp.asarray(valid), T, tile))
    assert tiles.shape == slots.shape == (T + 2 * K,)
    assert n_pairs.shape == n_active.shape == (1,)
    want, distinct = _brute_pairs(starts, ends, valid, T, tile)
    n = int(n_pairs[0])
    assert K <= n <= T + K
    assert list(zip(tiles[:n].tolist(), slots[:n].tolist())) == want
    assert (tiles[n:] == tiles[n - 1]).all() and (slots[n:] == slots[n - 1]).all()
    assert int(n_active[0]) == distinct
    assert sorted(set(slots[:n].tolist())) == list(range(K))


def test_tile_slot_pairs_random_disjoint_ranges(rng):
    """Twenty draws of K = 21 disjoint ranges over 64 tiles, some dead."""
    T, tile, K = 64, 128, 21
    for _ in range(20):
        cuts = np.sort(rng.choice(T * tile + 1, size=2 * K, replace=False))
        order = rng.permutation(K)
        starts, ends = cuts[0::2][order], cuts[1::2][order]
        valid = rng.rand(K) > 0.2
        ends = np.where(rng.rand(K) > 0.9, starts, ends)
        tiles, slots, n_pairs, n_active = (
            np.asarray(a) for a in tile_slot_pairs(
                jnp.asarray(starts, jnp.int32), jnp.asarray(ends, jnp.int32),
                jnp.asarray(valid), T, tile))
        want, distinct = _brute_pairs(starts, ends, valid, T, tile)
        n = int(n_pairs[0])
        assert n <= T + K
        assert list(zip(tiles[:n].tolist(), slots[:n].tolist())) == want
        assert int(n_active[0]) == distinct


def _f32_sum_in_tile_order(bins, gh, slot, s, num_bins, tile):
    """Slot s's histogram as the kernel must accumulate it: a tile's
    partial sum (exact here: gradients on a coarse grid) added in float32,
    tiles ascending."""
    G, n = bins.shape
    out = np.zeros((G, num_bins, gh.shape[1]), np.float32)
    for t in range(n // tile):
        rows = slice(t * tile, (t + 1) * tile)
        m = (slot[rows] == s)[:, None]
        part = _ref_hist(bins[:, rows],
                         np.where(m, gh[rows].astype(np.float64), 0.0),
                         num_bins)
        out = out + part.astype(np.float32)
    return out


KERNEL_LAYOUTS = {
    # name: (ranges, valid) over 4,096 rows in tiles of 512
    "boundary_tile_holds_four_slots": (
        [(0, 1030), (1030, 1040), (1040, 1100), (1100, 3000)], [True] * 4),
    "all_ranges_inside_one_tile": (
        [(1030, 1100), (1100, 1101), (1200, 1500), (1500, 1530)], [True] * 4),
    "empty_and_invalid_among_valid": (
        [(0, 700), (900, 900), (1024, 1100), (2000, 3000), (3100, 3200)],
        [True, True, True, False, True]),
    "one_slot": ([(300, 3900)], [True]),
}


@pytest.mark.parametrize("policy", ["bf16x3", "bf16", "int"])
@pytest.mark.parametrize("layout", sorted(KERNEL_LAYOUTS))
def test_pair_kernel_equals_float64_histogram(rng, layout, policy):
    """The kernel under the pair table against a float64 numpy histogram,
    slot by slot: a dead slot's block reads zeros (it is visited once and
    written), a boundary tile gives each slot its own rows and no other's.
    bf16x3: bit-equal to the float32 sum in tile order; int: bit-equal in
    int32; bf16: to the operand's rounding."""
    ranges, valid = KERNEL_LAYOUTS[layout]
    n, tile, G, B, S = 4096, 512, 3, 32, len(ranges)
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    gh = (_quantized_gh if policy == "int" else _coarse_grid_gh)(rng, n)
    slot = np.full(n, S, dtype=np.int32)
    for k, ((s, e), ok) in enumerate(zip(ranges, valid)):
        if ok:
            slot[s:e] = k
    pairs = _pairs(ranges, S, n // tile, tile, valid)
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), *pairs,
        B, S, tile_rows=tile, quantized=policy == "int",
        f32=policy == "bf16x3", interpret=True))
    assert ours.shape == (G, B, S * 3)
    for s in range(S):
        got = ours[..., s * 3:(s + 1) * 3]
        ref64 = _ref_slots(bins, gh.astype(np.float64), slot, B, s)
        if policy == "int":
            assert got.dtype == np.int32
            np.testing.assert_array_equal(got, ref64.astype(np.int64))
        elif policy == "bf16x3":
            np.testing.assert_array_equal(
                got.view(np.uint32),
                _f32_sum_in_tile_order(bins, gh, slot, s, B, tile)
                .view(np.uint32))
            np.testing.assert_allclose(got, ref64, rtol=1e-6, atol=1e-5)
        else:
            np.testing.assert_allclose(got, ref64, rtol=2e-2, atol=2e-1)
        if not (valid[s] and ranges[s][1] > ranges[s][0]):
            assert not got.any()


@pytest.mark.parametrize("policy", ["bf16x3", "int"])
@pytest.mark.parametrize("groups", [28, 136])
def test_pair_kernel_at_the_cells_group_counts(rng, groups, policy):
    """28 groups (Higgs: one 32-group block of a uint8 plane, four of them
    padding) and 136 (MSLR: five blocks, the last with 8 real groups),
    three slots of which the middle one is invalid."""
    n, tile, B, S = 1024, 256, 16, 3
    ranges, valid = [(0, 300), (300, 500), (500, 1000)], [True, False, True]
    padded = -(-groups // 32) * 32
    bins = np.zeros((padded, n), np.uint8)
    bins[:groups] = rng.randint(0, B, size=(groups, n))
    gh = (_quantized_gh if policy == "int" else _coarse_grid_gh)(rng, n)
    slot = np.full(n, S, dtype=np.int32)
    slot[0:300], slot[500:1000] = 0, 2
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot),
        *_pairs(ranges, S, n // tile, tile, valid), B, S, tile_rows=tile,
        quantized=policy == "int", f32=policy == "bf16x3", n_groups=groups,
        interpret=True))
    assert ours.shape == (groups, B, S * 3)
    for s in range(S):
        got = ours[..., s * 3:(s + 1) * 3]
        if policy == "int":
            want = _ref_slots(bins[:groups], gh.astype(np.float64), slot, B,
                              s).astype(np.int32)
        else:
            want = _f32_sum_in_tile_order(bins[:groups], gh, slot, s, B, tile)
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))


def test_a_slot_no_pair_lists_adds_nowhere(rng):
    """A row adds to its slot only under a pair of that slot: with slot 1
    called invalid the table lists it once, with tile 0, where it has no
    row; its rows in tile 1 add to no block, and slot 0's block is what it
    is with them."""
    n, tile, G, B = 1024, 512, 3, 16
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    gh = rng.randn(n, 3).astype(np.float32)
    slot = (np.arange(n) >= 600).astype(np.int32)
    both = pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot),
        *_pairs([(0, 600), (600, 1024)], 2, 2, tile), B, 2, tile_rows=tile,
        f32=True, interpret=True)
    only0 = pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot),
        *_pairs([(0, 600), (600, 1024)], 2, 2, tile, [True, False]), B, 2,
        tile_rows=tile, f32=True, interpret=True)
    both, only0 = np.asarray(both), np.asarray(only0)
    np.testing.assert_array_equal(both[..., :3].view(np.uint32),
                                  only0[..., :3].view(np.uint32))
    assert both[..., 3:].any() and not only0[..., 3:].any()


def test_pallas_histogram_uint8_bins_bit_identical(rng):
    """The 8-bit plane path (uint8 bins pass through unwidened, kernel
    widens the group row in-register) is bit-identical to int32 bins."""
    G, B, n = 5, 256, 3000
    bins8 = rng.randint(0, B, size=(G, n)).astype(np.uint8)
    gh = rng.randn(n, 3).astype(np.float32)
    for f32 in (True, False):
        ours8 = np.asarray(pallas_histogram(
            jnp.asarray(bins8), jnp.asarray(gh), B, f32=f32, interpret=True))
        ours32 = np.asarray(pallas_histogram(
            jnp.asarray(bins8.astype(np.int32)), jnp.asarray(gh), B,
            f32=f32, interpret=True))
        np.testing.assert_array_equal(ours8.view(np.uint32),
                                      ours32.view(np.uint32))


def test_pallas_histogram_slots_ragged_uint8_bit_identical(rng):
    """Wave (ragged) kernel: uint8 bins bit-identical to int32 bins, float
    and quantized variants."""
    from lightgbm_tpu.ops.hist_pallas import pallas_histogram_slots_ragged

    n, tile, S = 4096, 512, 3
    ranges = [(0, 900), (1500, 2600), (3000, 4000)]
    for quant in (False, True):
        G, B, bins, gh, slot, pairs = _ragged_setup(
            rng, n, tile, ranges, S, quantized=quant)
        bins8 = bins.astype(np.uint8)
        a = np.asarray(pallas_histogram_slots_ragged(
            jnp.asarray(bins8), jnp.asarray(gh.T), jnp.asarray(slot), *pairs,
            B, S, tile_rows=tile, quantized=quant, interpret=True))
        b = np.asarray(pallas_histogram_slots_ragged(
            jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), *pairs,
            B, S, tile_rows=tile, quantized=quant, interpret=True))
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


def test_pallas_histogram_slots_uint8_bit_identical(rng):
    """Every tile active, the bf16 default: uint8 bins == int32 bins."""
    G, B, n, S = 3, 16, 3000, 4
    bins8 = rng.randint(0, B, size=(G, n)).astype(np.uint8)
    gh = rng.randn(n, 3).astype(np.float32)
    slot = rng.randint(0, S + 2, size=n).astype(np.int32)
    a = _slots_every_tile(bins8, gh, slot, B, S)
    b = _slots_every_tile(bins8.astype(np.int32), gh, slot, B, S)
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


def test_pallas_histogram_quantized_exact(rng):
    G, B, n = 4, 32, 5000
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    gh = np.stack([rng.randint(-2, 3, n), rng.randint(0, 5, n),
                   np.ones(n)], axis=1).astype(np.int8)
    ours = np.asarray(pallas_histogram(
        jnp.asarray(bins), jnp.asarray(gh), B, tile_rows=1024,
        quantized=True, interpret=True))
    assert ours.dtype == np.int32
    ref = _ref_hist(bins, gh.astype(np.int64), B)
    np.testing.assert_array_equal(ours, ref.astype(np.int64))


# ---- the float32 path's operand: three exact bfloat16 limbs (PR 31) ----

def _full_mantissa(rng, n, lo_exp, hi_exp):
    """Random float32 values with all 23 mantissa bits drawn, either sign,
    binary exponents in [lo_exp, hi_exp)."""
    bits = ((rng.randint(0, 2, n).astype(np.uint32) << 31)
            | ((rng.randint(lo_exp, hi_exp, n) + 127).astype(np.uint32) << 23)
            | rng.randint(0, 1 << 23, n).astype(np.uint32))
    return bits.view(np.float32)


def _limb_cases(rng):
    wide = _full_mantissa(rng, 200_000, -99, 100)  # 1.6e-30 .. 6e29
    mid0 = wide.view(np.uint32) & np.uint32(0xFFFF00FF)  # zero middle byte
    return {
        "full_mantissa_1e-30_to_1e30": wide,
        "signed_zeros_and_ones": np.asarray(
            [0.0, -0.0, 1.0, -1.0, 1.0 + 2.0 ** -23, 2.0 ** -99, 1e30],
            np.float32),
        "zero_middle_byte": mid0.view(np.float32),
        "zero_low_byte": (wide.view(np.uint32)
                          & np.uint32(0xFFFFFF00)).view(np.float32),
    }


@pytest.mark.parametrize("case", ["full_mantissa_1e-30_to_1e30",
                                  "signed_zeros_and_ones",
                                  "zero_middle_byte", "zero_low_byte"])
def test_three_bf16_limbs_hold_a_float32_exactly(rng, case):
    """hi + mid + lo == x bit for bit, and two limbs do not: the split the
    f32=True kernel feeds the MXU loses nothing of the gradient."""
    x = _limb_cases(rng)[case][None, :]
    limbs = np.asarray(bf16_limbs(jnp.asarray(x), 3).astype(jnp.float32))
    assert limbs.shape == (3, x.shape[1])
    back = (limbs[0] + limbs[1]) + limbs[2]  # float32 adds, each exact
    nz = x[0] != 0  # -0.0 comes back +0.0: the same addend to a sum
    np.testing.assert_array_equal(back[nz].view(np.uint32),
                                  x[0][nz].view(np.uint32))
    np.testing.assert_array_equal(back[~nz], 0.0)
    if case == "full_mantissa_1e-30_to_1e30":
        two = np.asarray(bf16_limbs(jnp.asarray(x), 2).astype(jnp.float32))
        assert ((two[0] + two[1]) != x[0]).mean() > 0.9
    one = np.asarray(bf16_limbs(jnp.asarray(x), 1).astype(jnp.float32))
    np.testing.assert_array_equal(
        one, np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                        .astype(jnp.float32)))


def test_hist_operand_names_the_dtype_policy():
    assert hist_operand(quantized=False, f32=True) == "bf16x3"
    assert hist_operand(quantized=False, f32=False) == "bf16"
    assert hist_operand(quantized=True, f32=False) == "int"
    assert hist_operand(quantized=True, f32=True) == "int"


def _one_row_a_cell(rng, tile=512):
    """Every (group, bin, slot) receives at most one row: within a slot's
    B consecutive rows each group's bins are a rotation of 0..B-1."""
    G, B, S = 3, 64, 4
    n = S * B * 2  # the second half rides the dump slot
    bins = np.stack([(np.arange(n) + 5 * g) % B for g in range(G)]
                    ).astype(np.int32)
    slot = np.minimum(np.arange(n) // B, S).astype(np.int32)
    gh = np.stack([_full_mantissa(rng, n, -20, 20) for _ in range(3)], axis=1)
    ranges = [(s * B, (s + 1) * B) for s in range(S)]
    return G, B, S, bins, slot, gh, _pairs(ranges, S, n // tile, tile)


@pytest.mark.parametrize("f32", [True, False])
def test_one_row_a_bin_comes_back_bit_for_bit_only_with_three_limbs(rng,
                                                                    f32):
    """With a single row a cell nothing is summed, so the f32=True kernel
    must return each gradient's 24 significand bits untouched; the default
    (one limb) is the failing control: it returns the bfloat16 rounding."""
    G, B, S, bins, slot, gh, pairs = _one_row_a_cell(rng)
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), *pairs,
        B, S, tile_rows=512, f32=f32, interpret=True))
    live = slot < S
    want = np.zeros((G, B, S * 3), np.float32)
    for g in range(G):
        for c in range(3):
            want[g, bins[g, live], slot[live] * 3 + c] = gh[live, c]
    same = ours.view(np.uint32) == want.view(np.uint32)
    if f32:
        assert same.all()
    else:
        assert same.mean() < 0.05  # full-mantissa values: bf16 rounds them
        np.testing.assert_allclose(ours, want, rtol=2 ** -8)


@pytest.mark.parametrize("ranges", [
    [(0, 700), (1024, 1100), (2000, 3000)],
    [(300, 3900)],
])
def test_ragged_f32_equals_float64_reference_on_exactly_summable_grid(
        rng, ranges):
    """Gradients k / 2**19 with |k| < 2**19 (19 bits: past two bfloat16
    limbs) and few enough rows a cell that every partial sum is exact in
    float32 in ANY order: the f32=True kernel must equal the float64 sum
    bit for bit, over ranges that straddle the 512-row tiles."""
    n, tile, S, G, B = 4096, 512, 4, 3, 128
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    k = rng.randint(-(2 ** 19) + 1, 2 ** 19, size=(n, 3))
    gh = (k / 2.0 ** 19).astype(np.float32)
    assert (gh.astype(np.float64) * 2 ** 19 == k).all()
    slot = np.full(n, S, dtype=np.int32)
    for i, (s, e) in enumerate(ranges):
        slot[s:e] = (np.arange(s, e) * S // n + i) % S
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot),
        *_pairs_of_rows(slot, S, tile), B, S, tile_rows=tile, f32=True,
        interpret=True))
    for s in range(S):
        mask = (slot == s)[:, None]
        ref = _ref_hist(bins, np.where(mask, gh.astype(np.float64), 0.0), B)
        mass = _ref_hist(bins, np.where(mask, np.abs(k), 0), B)
        assert mass.max() < 2 ** 24  # every partial sum is exact in float32
        np.testing.assert_array_equal(
            ours[..., s * 3:(s + 1) * 3].view(np.uint32),
            ref.astype(np.float32).view(np.uint32))


@pytest.mark.parametrize("policy", [{"f32": True}, {}, {"quantized": True}])
def test_padded_plane_with_real_group_count_equals_unpadded(rng, policy):
    """HIGGS's plane: 28 groups padded to 32 for Mosaic's 8-bit tiling. With
    n_groups=28 the four zero rows get no work and the result is the
    unpadded plane's, bit for bit, under every dtype policy."""
    n, tile, S = 2048, 512, 3
    ranges = [(0, 900), (1100, 2000)]
    _, B, bins3, gh, slot, pairs = _ragged_setup(
        rng, n, tile, ranges, S, quantized="quantized" in policy)
    bins = rng.randint(0, B, size=(28, n)).astype(np.uint8)
    padded = np.concatenate([bins, np.zeros((4, n), np.uint8)])

    def run(plane, **kw):
        return np.asarray(pallas_histogram_slots_ragged(
            jnp.asarray(plane), jnp.asarray(gh.T), jnp.asarray(slot), *pairs,
            B, S, tile_rows=tile, interpret=True, **policy, **kw))

    a, b = run(bins), run(padded, n_groups=28)
    assert a.shape == b.shape == (28, B, S * 3)
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
    whole = run(padded)  # no count given: the padding is histogrammed too
    assert whole.shape == (32, B, S * 3)
    np.testing.assert_array_equal(whole[:28].view(np.uint32),
                                  a.view(np.uint32))
    with pytest.raises(ValueError, match="n_groups"):
        run(padded, n_groups=33)


def test_int32_plane_skips_the_last_blocks_padding(rng):
    """A plane wider than 8 bits takes group blocks of 8 or 16: with 19
    groups in 24 rows the first block is whole and the last holds 3 real
    groups; the result equals the every-group reference."""
    n, tile, S, G = 1024, 512, 2, 19
    _, B, _, gh, slot, pairs = _ragged_setup(
        rng, n, tile, [(0, 600), (600, 1024)], S)
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    padded = np.concatenate([bins, np.zeros((5, n), np.int32)])
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(padded), jnp.asarray(gh.T), jnp.asarray(slot), *pairs,
        B, S, tile_rows=tile, f32=True, n_groups=G, interpret=True))
    assert ours.shape == (G, B, S * 3)
    for s in range(S):
        np.testing.assert_allclose(ours[..., s * 3:(s + 1) * 3],
                                   _ref_slots(bins, gh, slot, B, s),
                                   rtol=1e-5, atol=1e-4)
