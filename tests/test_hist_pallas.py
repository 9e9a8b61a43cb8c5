"""Pallas histogram kernel correctness (interpret mode on CPU) vs the XLA
path and the numpy reference."""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.hist_pallas import (active_tile_table, bf16_limbs,
                                          hist_operand, pallas_histogram,
                                          pallas_histogram_slots_ragged)
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
    """The wave kernel with EVERY row tile active: the slot-expanded
    histogram of the whole row set (rows padded to the tile with the dump
    slot, as the learner pads them)."""
    from lightgbm_tpu.ops.hist_pallas import (active_tile_table,
                                              pallas_histogram_slots_ragged)

    n = bins.shape[1]
    n_pad = -(-n // tile) * tile
    bins = np.pad(bins, ((0, 0), (0, n_pad - n)))
    gh = np.pad(gh.astype(np.float32), ((0, n_pad - n), (0, 0)))
    slot = np.pad(slot, (0, n_pad - n), constant_values=n_slots)
    tiles, n_act = active_tile_table(
        jnp.zeros(1, jnp.int32), jnp.full(1, n_pad, jnp.int32),
        jnp.ones(1, bool), n_pad // tile, tile)
    assert int(n_act[0]) == n_pad // tile
    return np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), tiles,
        n_act, num_bins, n_slots, tile_rows=tile, interpret=True, **kw))


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


def _ragged_setup(rng, n, tile, ranges, S, quantized=False):
    """Leaf-contiguous layout: slot < S only inside the given ranges."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_pallas import active_tile_table

    G, B = 3, 16
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    if quantized:
        gh = np.stack([rng.randint(-4, 5, n), rng.randint(0, 6, n),
                       np.ones(n)], axis=1).astype(np.float32)
    else:
        gh = rng.randn(n, 3).astype(np.float32)
    slot = np.full(n, S, dtype=np.int32)  # dump by default
    for k, (s, e) in enumerate(ranges):
        slot[s:e] = k % S
    starts = jnp.asarray([s for s, _ in ranges], jnp.int32)
    ends = jnp.asarray([e for _, e in ranges], jnp.int32)
    tiles, n_act = active_tile_table(starts, ends,
                                     jnp.ones(len(ranges), bool),
                                     n // tile, tile)
    return G, B, bins, gh, slot, tiles, n_act


@pytest.mark.parametrize("ranges", [
    [(0, 700), (1024, 1100), (2000, 3000)],
    [(512, 1024)],                      # tile-aligned single range
    [(100, 101), (3500, 4096)],         # tiny + tail
])
def test_pallas_histogram_slots_ragged(rng, ranges):
    from lightgbm_tpu.ops.hist_pallas import pallas_histogram_slots_ragged

    n, tile, S = 4096, 512, 4
    G, B, bins, gh, slot, tiles, n_act = _ragged_setup(rng, n, tile, ranges,
                                                       S)
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), tiles, n_act,
        B, S, tile_rows=tile, f32=True, interpret=True))
    assert ours.shape == (G, B, S * 3)
    covered = int(np.asarray(n_act)[0]) * tile
    assert covered <= n  # ragged grid walks only overlapping tiles
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
    G, B, bins, gh, slot, tiles, n_act = _ragged_setup(
        rng, n, tile, ranges, S, quantized=True)
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), tiles, n_act,
        B, S, tile_rows=tile, quantized=True, interpret=True))
    assert ours.dtype == np.int32
    for s in range(S):
        np.testing.assert_array_equal(
            ours[..., s * 3:(s + 1) * 3],
            _ref_slots(bins, gh.astype(np.int64), slot, B, s).astype(np.int64))


def test_active_tile_table():
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_pallas import active_tile_table

    tiles, n_act = active_tile_table(
        jnp.asarray([0, 1024, 4000], jnp.int32),
        jnp.asarray([512, 1536, 4096], jnp.int32),
        jnp.asarray([True, True, False]), 8, 512)
    # [0,512) -> tile 0; [1024,1536) -> tile 2; third range invalid
    assert int(n_act[0]) == 2
    np.testing.assert_array_equal(np.asarray(tiles)[:3], [0, 2, 2])
    # boundary straddle: [500, 1030) touches tiles 0, 1, 2
    tiles, n_act = active_tile_table(
        jnp.asarray([500], jnp.int32), jnp.asarray([1030], jnp.int32),
        jnp.asarray([True]), 4, 512)
    assert int(n_act[0]) == 3
    np.testing.assert_array_equal(np.asarray(tiles), [0, 1, 2, 2])


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
        G, B, bins, gh, slot, tiles, n_act = _ragged_setup(
            rng, n, tile, ranges, S, quantized=quant)
        bins8 = bins.astype(np.uint8)
        a = np.asarray(pallas_histogram_slots_ragged(
            jnp.asarray(bins8), jnp.asarray(gh.T), jnp.asarray(slot), tiles,
            n_act, B, S, tile_rows=tile, quantized=quant, interpret=True))
        b = np.asarray(pallas_histogram_slots_ragged(
            jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), tiles,
            n_act, B, S, tile_rows=tile, quantized=quant, interpret=True))
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
    tiles, n_act = active_tile_table(
        jnp.zeros(1, jnp.int32), jnp.full(1, n, jnp.int32),
        jnp.ones(1, bool), n // tile, tile)
    return G, B, S, bins, slot, gh, tiles, n_act


@pytest.mark.parametrize("f32", [True, False])
def test_one_row_a_bin_comes_back_bit_for_bit_only_with_three_limbs(rng,
                                                                    f32):
    """With a single row a cell nothing is summed, so the f32=True kernel
    must return each gradient's 24 significand bits untouched; the default
    (one limb) is the failing control: it returns the bfloat16 rounding."""
    G, B, S, bins, slot, gh, tiles, n_act = _one_row_a_cell(rng)
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), tiles, n_act,
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
    starts = jnp.asarray([s for s, _ in ranges], jnp.int32)
    ends = jnp.asarray([e for _, e in ranges], jnp.int32)
    tiles, n_act = active_tile_table(starts, ends,
                                     jnp.ones(len(ranges), bool),
                                     n // tile, tile)
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(bins), jnp.asarray(gh.T), jnp.asarray(slot), tiles, n_act,
        B, S, tile_rows=tile, f32=True, interpret=True))
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
    _, B, bins3, gh, slot, tiles, n_act = _ragged_setup(
        rng, n, tile, ranges, S, quantized="quantized" in policy)
    bins = rng.randint(0, B, size=(28, n)).astype(np.uint8)
    padded = np.concatenate([bins, np.zeros((4, n), np.uint8)])

    def run(plane, **kw):
        return np.asarray(pallas_histogram_slots_ragged(
            jnp.asarray(plane), jnp.asarray(gh.T), jnp.asarray(slot), tiles,
            n_act, B, S, tile_rows=tile, interpret=True, **policy, **kw))

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
    _, B, _, gh, slot, tiles, n_act = _ragged_setup(
        rng, n, tile, [(0, 1024)], S)
    bins = rng.randint(0, B, size=(G, n)).astype(np.int32)
    padded = np.concatenate([bins, np.zeros((5, n), np.int32)])
    ours = np.asarray(pallas_histogram_slots_ragged(
        jnp.asarray(padded), jnp.asarray(gh.T), jnp.asarray(slot), tiles,
        n_act, B, S, tile_rows=tile, f32=True, n_groups=G, interpret=True))
    assert ours.shape == (G, B, S * 3)
    for s in range(S):
        np.testing.assert_allclose(ours[..., s * 3:(s + 1) * 3],
                                   _ref_slots(bins, gh, slot, B, s),
                                   rtol=1e-5, atol=1e-4)
