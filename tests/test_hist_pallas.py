"""Pallas histogram kernel correctness (interpret mode on CPU) vs the XLA
path and the numpy reference."""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.hist_pallas import pallas_histogram
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
