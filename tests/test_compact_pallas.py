"""Leaf-contiguous compaction: forward-map helper + Pallas pair kernel
(interpret mode on CPU) vs the argsort-stable partition oracle, bit-exact."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.compact_pallas import (
    COMPACT_TILE, build_pair_tables, compact_rows, max_pairs_bound,
    range_partition_dst)


def _np_dst(go_left, ranges, n):
    """Stable 2-way partition forward map, built from the argsort oracle:
    within each range, rows ordered by (right-flag, original position)."""
    dst = np.arange(n)
    for s, c in ranges:
        order = np.argsort(~go_left[s:s + c], kind="stable") + s  # old idx
        dst[order] = np.arange(s, s + c)
    return dst


def _masks(go_left, ranges, n):
    match = np.zeros((len(ranges), n), dtype=bool)  # [K, N]: rows minor
    for k, (s, c) in enumerate(ranges):
        match[k, s:s + c] = True
    cm = [match[k] & go_left for k in range(len(ranges))]
    cm += [match[k] & ~go_left for k in range(len(ranges))]
    return match, cm


def _dst(go_left, ranges, n):
    match, cm = _masks(go_left, ranges, n)
    starts = jnp.asarray([s for s, _ in ranges], jnp.int32)
    counts = jnp.asarray([c for _, c in ranges], jnp.int32)
    valid = jnp.ones(len(ranges), bool)
    dst, n_left = range_partition_dst(
        jnp.asarray(go_left), jnp.asarray(match), starts, counts, valid)
    return np.asarray(dst), np.asarray(n_left), cm, match


CASES = [
    ("multi", [(64, 300), (512, 512), (1100, 180), (1280, 250)]),
    ("adjacent_tiny", [(0, 7), (7, 9), (16, 3), (19, 501)]),
    ("tile_aligned", [(0, 512), (1024, 512)]),
    ("full", [(0, 2048)]),
]


@pytest.mark.parametrize("name,ranges", CASES)
def test_range_partition_dst_matches_oracle(rng, name, ranges):
    n = 2048
    go_left = rng.rand(n) < 0.4
    dst, n_left, _, _ = _dst(go_left, ranges, n)
    np.testing.assert_array_equal(dst, _np_dst(go_left, ranges, n))
    for k, (s, c) in enumerate(ranges):
        assert n_left[k] == go_left[s:s + c].sum()


@pytest.mark.parametrize("name,ranges", CASES)
@pytest.mark.parametrize("tile", [256, 512])
def test_compact_pallas_bit_exact(rng, name, ranges, tile):
    n, gp, rc = 2048, 8, 8  # payload [rc, n]: one row a channel
    go_left = rng.rand(n) < 0.5
    dst, _, cm, match = _dst(go_left, ranges, n)
    bins = rng.randint(0, 60000, size=(gp, n)).astype(np.int32)
    row = rng.randn(rc, n).astype(np.float32)
    row[3] = np.arange(n)  # a perm-style integer row rides along
    # bit patterns a float accumulate would not carry: the kernel ORs bits
    row[0, ::5], row[0, 1::5] = -0.0, 1e-39
    moved = match.any(axis=0)
    ours_b, ours_r = compact_rows(
        jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst),
        [jnp.asarray(m) for m in cm], jnp.asarray(moved),
        tile=tile, use_pallas=True, interpret=True)
    ref_b = np.zeros_like(bins)
    ref_b[:, dst] = bins
    ref_r = np.zeros_like(row)
    ref_r[:, dst] = row
    np.testing.assert_array_equal(np.asarray(ours_b), ref_b)
    # bit-exact: limb transport must preserve f32 payloads exactly
    np.testing.assert_array_equal(
        np.asarray(ours_r).view(np.uint32), ref_r.view(np.uint32))


def test_pair_list_holds_a_range_spanning_many_tiles(rng):
    """A tree's root split is ONE range over every tile, its left and right
    rows interleaved in each: close to 4 pairs per tile. A pair list sized
    3 per tile truncated there, dropping the last output tiles' rows — on
    every tree over ~90k rows, and in no test, since none spanned more
    than 8 tiles."""
    n, gp, rc, tile = 16384, 32, 8, 256
    go_left = rng.rand(n) < 0.5
    dst, _, cm, match = _dst(go_left, [(0, n)], n)
    masks = [jnp.asarray(m) for m in cm]
    moved = jnp.asarray(match.any(axis=0))
    *_, n_pairs = build_pair_tables(jnp.asarray(dst), masks, moved, tile)
    assert 3 * (n // tile) < int(n_pairs[0]) <= max_pairs_bound(n // tile, 2)
    bins = rng.randint(0, 256, size=(gp, n)).astype(np.uint8)
    row = rng.randn(rc, n).astype(np.float32)
    row[3] = np.arange(n)
    ours_b, ours_r = compact_rows(
        jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst), masks, moved,
        tile=tile, use_pallas=True, interpret=True)
    ref_b = np.zeros_like(bins)
    ref_b[:, dst] = bins
    ref_r = np.zeros_like(row)
    ref_r[:, dst] = row
    np.testing.assert_array_equal(np.asarray(ours_b), ref_b)
    np.testing.assert_array_equal(
        np.asarray(ours_r).view(np.uint32), ref_r.view(np.uint32))


@pytest.mark.parametrize("name,ranges", CASES)
def test_compact_pallas_uint8_plane(rng, name, ranges):
    """8-bit bin plane rides the single-limb path, output stays uint8 and
    matches both the permutation oracle and the int32 2-limb result."""
    n, gp, rc, tile = 2048, 32, 8, 256  # gp % 32 == 0 for the 8-bit tile
    go_left = rng.rand(n) < 0.5
    dst, _, cm, match = _dst(go_left, ranges, n)
    bins8 = rng.randint(0, 256, size=(gp, n)).astype(np.uint8)
    row = rng.randn(rc, n).astype(np.float32)
    moved = match.any(axis=0)
    args = ([jnp.asarray(m) for m in cm], jnp.asarray(moved))
    b8, r8 = compact_rows(
        jnp.asarray(bins8), jnp.asarray(row), jnp.asarray(dst), *args,
        tile=tile, use_pallas=True, interpret=True)
    assert np.asarray(b8).dtype == np.uint8
    ref_b = np.zeros_like(bins8)
    ref_b[:, dst] = bins8
    np.testing.assert_array_equal(np.asarray(b8), ref_b)
    b32, r32 = compact_rows(
        jnp.asarray(bins8.astype(np.int32)), jnp.asarray(row),
        jnp.asarray(dst), *args, tile=tile, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(b8).astype(np.int32),
                                  np.asarray(b32))
    np.testing.assert_array_equal(
        np.asarray(r8).view(np.uint32), np.asarray(r32).view(np.uint32))


def test_compact_xla_fallback_uint8(rng):
    n, gp = 1024, 4
    ranges = [(100, 500)]
    go_left = rng.rand(n) < 0.3
    dst, _, cm, match = _dst(go_left, ranges, n)
    bins = rng.randint(0, 256, size=(gp, n)).astype(np.uint8)
    row = rng.randn(3, n).astype(np.float32)
    ours_b, _ = compact_rows(
        jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst),
        [jnp.asarray(m) for m in cm], jnp.asarray(match.any(axis=0)),
        use_pallas=False)
    assert np.asarray(ours_b).dtype == np.uint8
    ref_b = np.zeros_like(bins)
    ref_b[:, dst] = bins
    np.testing.assert_array_equal(np.asarray(ours_b), ref_b)


def test_compact_xla_fallback_exact(rng):
    n, gp, rc = 1024, 3, 5
    ranges = [(100, 500), (700, 300)]
    go_left = rng.rand(n) < 0.3
    dst, _, cm, match = _dst(go_left, ranges, n)
    bins = rng.randint(0, 256, size=(gp, n)).astype(np.int32)
    row = rng.randn(rc, n).astype(np.float32)
    ours_b, ours_r = compact_rows(
        jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst),
        [jnp.asarray(m) for m in cm], jnp.asarray(match.any(axis=0)),
        use_pallas=False)
    ref_b = np.zeros_like(bins)
    ref_b[:, dst] = bins
    ref_r = np.zeros_like(row)
    ref_r[:, dst] = row
    np.testing.assert_array_equal(np.asarray(ours_b), ref_b)
    np.testing.assert_array_equal(np.asarray(ours_r), ref_r)


def test_compact_one_sided(rng):
    """Empty-left and empty-right partitions stay identity permutations."""
    n, tile = 1024, 256
    for flag in (True, False):
        go_left = np.full(n, flag)
        ranges = [(0, 600)]
        dst, n_left, cm, match = _dst(go_left, ranges, n)
        np.testing.assert_array_equal(dst, np.arange(n))
        assert n_left[0] == (600 if flag else 0)
        bins = np.arange(2 * n, dtype=np.int32).reshape(2, n) % 256
        bins = np.vstack([bins] * 4)  # gp=8
        row = np.arange(n * 8, dtype=np.float32).reshape(8, n)
        ob, orr = compact_rows(
            jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst),
            [jnp.asarray(m) for m in cm], jnp.asarray(match.any(axis=0)),
            tile=tile, use_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(ob), bins)
        np.testing.assert_array_equal(np.asarray(orr), row)


def test_pair_table_bound_and_coverage(rng):
    """n_pairs respects the static bound; every output tile is produced."""
    n, tile = 4096, 256
    ranges = [(0, 900), (1000, 200), (1200, 64), (1500, 2000)]
    go_left = rng.rand(n) < 0.5
    dst, _, cm, match = _dst(go_left, ranges, n)
    pi, po, copy, npairs = build_pair_tables(
        jnp.asarray(dst), [jnp.asarray(m) for m in cm],
        jnp.asarray(match.any(axis=0)), tile)
    t = n // tile
    mp = max_pairs_bound(t, len(cm))
    assert pi.shape == (mp,)
    assert int(npairs[0]) <= mp
    # all T output tiles covered, pairs sorted by out tile
    live = np.asarray(po)[:int(npairs[0])]
    assert set(live.tolist()) == set(range(t))
    assert (np.diff(live) >= 0).all()
    # pcopy semantics: 1 = raw copy of an untouched identity tile,
    # 2 = duplicate pair demoted to a skip (must repeat its predecessor's
    # blocks and never open an output block), 0 = one-hot permute.
    touched = match.any(axis=0).reshape(t, tile).any(axis=1)
    live_in = np.asarray(pi)[:int(npairs[0])]
    live_copy = np.asarray(copy)[:int(npairs[0])]
    for p in range(int(npairs[0])):
        if live_copy[p] == 1:
            assert live_in[p] == live[p] and not touched[live_in[p]]
        elif live_copy[p] == 2:
            assert p > 0
            assert live_in[p] == live_in[p - 1] and live[p] == live[p - 1]
    # after dropping skip pairs, (in, out) pairs are unique
    keep = live_copy < 2
    pairs = list(zip(live_in[keep].tolist(), live[keep].tolist()))
    assert len(pairs) == len(set(pairs))


def test_pair_list_overflow_is_loud_under_sanitize(rng, monkeypatch):
    """Masks that break the per-tile-contiguity contract (three classes
    scattered by a random permutation: 7 pairs per tile) outgrow the static
    bound. The list is truncated either way; LGBM_TPU_SANITIZE=1 says so
    instead of dropping the rows in silence."""
    n, tile = 8192, 256
    dst = jnp.asarray(rng.permutation(n).astype(np.int32))
    masks = [jnp.arange(n) % 3 == c for c in range(3)]
    moved = jnp.ones(n, bool)
    *_, n_pairs = build_pair_tables(dst, masks, moved, tile)
    assert int(n_pairs[0]) > max_pairs_bound(n // tile, len(masks))
    monkeypatch.setenv("LGBM_TPU_SANITIZE", "1")
    with pytest.raises(Exception, match="the truncated list drops rows"):
        jax.block_until_ready(build_pair_tables(dst, masks, moved, tile))
