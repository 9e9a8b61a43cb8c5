"""Leaf-contiguous compaction: forward-map helper + Pallas pair kernel
(interpret mode on CPU) vs the argsort-stable partition oracle, bit-exact;
the glue (one left scan, [K, T] pair tables) vs the row-derived glue it
replaced, array for array."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import compact_pallas
from lightgbm_tpu.ops.compact_pallas import (
    COMPACT_TILE, COMPACT_WORK_FIELDS, SMEM_TABLE_BYTES, _pallas_compact_call,
    build_pair_tables, compact_blocks, compact_rows, exclusive_cumsum,
    max_pairs_bound, pair_tables_packed, pair_work_counts,
    range_partition_dst, run_offsets)


def _np_dst(go_left, ranges, n):
    """Stable 2-way partition forward map, built from the argsort oracle:
    within each range, rows ordered by (right-flag, original position)."""
    dst = np.arange(n)
    for s, c in ranges:
        order = np.argsort(~go_left[s:s + c], kind="stable") + s  # old idx
        dst[order] = np.arange(s, s + c)
    return dst


def _match(ranges, n, valid=None):
    match = np.zeros((len(ranges), n), dtype=bool)  # [K, N]: rows minor
    for k, (s, c) in enumerate(ranges):
        match[k, s:s + c] = valid is None or valid[k]
    return match


def _partition(go_left, ranges, n, tile, valid=None):
    """range_partition_dst on numpy inputs -> (dst, n_left, LeftCounts,
    (starts, counts, valid)): what compact_rows takes after bins and rows."""
    match = _match(ranges, n, valid)
    args = (jnp.asarray([s for s, _ in ranges], jnp.int32),
            jnp.asarray([c for _, c in ranges], jnp.int32),
            jnp.asarray(np.ones(len(ranges), bool) if valid is None
                        else valid))
    dst, n_left, lefts = range_partition_dst(
        jnp.asarray(go_left), jnp.asarray(match),
        jnp.asarray(match.any(axis=0)), *args, tile)
    return np.asarray(dst), np.asarray(n_left), lefts, args


# ---------------------------------------------------------------- the oracle
# The glue as it was until PR 33, kept as the reference: destinations from TWO
# global scans, and the pair tables from a masked per-tile min and max of the
# destinations under each of the 2K class masks (42 passes over [N] a wave).

def _two_scan_partition_dst(go_left, match, starts, counts, valid):
    K, N = match.shape
    pos = jnp.arange(N, dtype=jnp.int32)
    in_any = match.any(axis=0)
    lmask = in_any & go_left
    rmask = in_any & ~go_left
    lcum = exclusive_cumsum(lmask)
    rcum = exclusive_cumsum(rmask)
    lext = jnp.concatenate(
        [lcum, (lcum[-1] + lmask[-1].astype(jnp.int32))[None]])
    rext = jnp.concatenate(
        [rcum, (rcum[-1] + rmask[-1].astype(jnp.int32))[None]])
    ends = starts + counts
    n_left = jnp.take(lext, ends) - jnp.take(lext, starts)
    base_l = starts - jnp.take(lext, starts)
    base_r = starts + n_left - jnp.take(rext, starts)
    bases = jax.lax.dot(jnp.stack([base_l, base_r]).astype(jnp.float32),
                        match.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)  # [2, N]
    dst = jnp.where(
        lmask, bases[0].astype(jnp.int32) + lcum,
        jnp.where(rmask, bases[1].astype(jnp.int32) + rcum, pos))
    return dst, jnp.where(valid, n_left, 0)


def _row_derived_pair_tables(dst, class_masks, moved, tile):
    N = dst.shape[0]
    T = N // tile
    dstT = dst.reshape(T, tile)
    big = jnp.int32(2 ** 30)
    ids = jnp.arange(T, dtype=jnp.int32)
    cands = [ids[:, None]]
    for m in class_masks:
        mT = m.reshape(T, tile)
        any_m = mT.any(axis=1)
        dmin = jnp.min(jnp.where(mT, dstT, big), axis=1) // tile
        dmax = jnp.max(jnp.where(mT, dstT, -1), axis=1) // tile
        c0 = jnp.where(any_m, dmin, T)
        c1 = jnp.where(any_m & (dmax > dmin), dmax, T)
        cands.append(jnp.stack([c0, c1], axis=1))
    cand = jnp.concatenate(cands, axis=1)  # [T, 1 + 2*len(masks)]
    out_flat = cand.reshape(-1)
    in_flat = jnp.repeat(ids, cand.shape[1])
    ok = out_flat < T
    key = jax.lax.sort(jnp.where(ok, out_flat * T + in_flat, big))
    n_pairs = ok.sum().astype(jnp.int32)
    dup = jnp.concatenate([jnp.zeros(1, bool), key[1:] == key[:-1]])
    mp = max_pairs_bound(T, len(class_masks))
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
    touched = moved.reshape(T, tile).any(axis=1)
    is_copy = (pair_in == pair_out) & ~jnp.take(touched, pair_in)
    pcopy = jnp.where(dup & live, 2, is_copy.astype(jnp.int32))
    return pair_in, pair_out, pcopy, n_pairs[None]


CASES = [
    ("multi", [(64, 300), (512, 512), (1100, 180), (1280, 250)]),
    ("adjacent_tiny", [(0, 7), (7, 9), (16, 3), (19, 501)]),
    ("tile_aligned", [(0, 512), (1024, 512)]),
    ("full", [(0, 2048)]),
]

# (name, ranges, valid, share of rows that go left)
GLUE_CASES = [(name, ranges, None, 0.4) for name, ranges in CASES] + [
    ("out_of_position_order",
     [(1100, 180), (64, 300), (1280, 250), (512, 512)], None, 0.5),
    ("invalid_range", [(0, 300), (400, 700), (1200, 500)],
     [True, False, True], 0.5),
    # a shard that holds no row of a selected leaf
    ("empty_valid_range", [(0, 300), (300, 0), (600, 100), (2048, 0)],
     None, 0.5),
    ("all_left", [(64, 300), (512, 900)], None, 1.0),
    ("all_right", [(64, 300), (512, 900)], None, 0.0),
    ("ends_at_n", [(100, 30), (1500, 548)], None, 0.3),
    # the bagged set-up: one range over every row, in-bag rows left
    ("bagged_one_range", [(0, 2048)], None, 0.7),
]


def _assert_glue_equals_oracle(go_left, ranges, n, tile, valid):
    dst, n_left, lefts, args = _partition(go_left, ranges, n, tile, valid)
    match = jnp.asarray(_match(ranges, n, valid))
    gl = jnp.asarray(go_left)
    ref_dst, ref_n_left = _two_scan_partition_dst(gl, match, *args)
    np.testing.assert_array_equal(dst, np.asarray(ref_dst))
    np.testing.assert_array_equal(n_left, np.asarray(ref_n_left))
    masks = ([match[k] & gl for k in range(len(ranges))]
             + [match[k] & ~gl for k in range(len(ranges))])
    ref = _row_derived_pair_tables(ref_dst, masks, match.any(axis=0), tile)
    ours = build_pair_tables(lefts, *args, tile)
    for what, o, r in zip(("pair_in", "pair_out", "pcopy", "n_pairs"),
                          ours, ref):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r), what)


@pytest.mark.parametrize("name,ranges,valid,p_left", GLUE_CASES,
                         ids=[c[0] for c in GLUE_CASES])
@pytest.mark.parametrize("tile", [256, 512])
def test_glue_equals_the_row_derived_oracle(rng, name, ranges, valid, p_left,
                                            tile):
    """dst, n_left and the four pair-table arrays equal, element for
    element, what two scans and 2K masked min/max passes over the rows
    gave: the kernel sees the parent's operands."""
    n = 2048
    _assert_glue_equals_oracle(rng.rand(n) < p_left, ranges, n, tile, valid)


@pytest.mark.parametrize("seed", range(6))
def test_glue_equals_the_oracle_on_random_ranges(seed):
    """Random disjoint ranges in random order, some invalid, some empty,
    one-sided splits among them: the same six arrays as the oracle's."""
    rs = np.random.RandomState(3300 + seed)
    n, tile, k = 4096, 256, 7
    cuts = np.sort(rs.choice(n + 1, 2 * k, replace=False))
    ranges = [(int(cuts[2 * i]), int(cuts[2 * i + 1] - cuts[2 * i]))
              for i in range(k)]
    ranges[rs.randint(k)] = (int(cuts[3]), 0)  # an empty one, inside another
    ranges = [ranges[i] for i in rs.permutation(k)]
    valid = rs.rand(k) < 0.8
    go_left = rs.rand(n) < rs.choice([0.0, 0.1, 0.5, 0.9, 1.0])
    _assert_glue_equals_oracle(go_left, ranges, n, tile, valid)


def test_left_counts_sample_the_one_scan(rng):
    """LeftCounts is lext at the T + 1 tile boundaries and the 2K range
    ends, a range ending at N included."""
    n, tile = 2048, 256
    ranges = [(700, 500), (1500, 548)]
    go_left = rng.rand(n) < 0.5
    _, _, lefts, _ = _partition(go_left, ranges, n, tile)
    lext = np.concatenate([[0], np.cumsum(go_left & _match(ranges, n).any(0))])
    np.testing.assert_array_equal(np.asarray(lefts.tiles), lext[::tile])
    np.testing.assert_array_equal(np.asarray(lefts.starts), lext[[700, 1500]])
    np.testing.assert_array_equal(np.asarray(lefts.ends), lext[[1200, 2048]])


@pytest.mark.parametrize("name,ranges", CASES)
def test_range_partition_dst_matches_oracle(rng, name, ranges):
    n = 2048
    go_left = rng.rand(n) < 0.4
    dst, n_left, _, _ = _partition(go_left, ranges, n, COMPACT_TILE)
    np.testing.assert_array_equal(dst, _np_dst(go_left, ranges, n))
    for k, (s, c) in enumerate(ranges):
        assert n_left[k] == go_left[s:s + c].sum()


def _permuted(bins, row, dst):
    ref_b = np.zeros_like(bins)
    ref_b[:, dst] = bins
    ref_r = np.zeros_like(row)
    ref_r[:, dst] = row
    return ref_b, ref_r


@pytest.mark.parametrize("name,ranges", CASES)
@pytest.mark.parametrize("tile", [256, 512])
def test_compact_pallas_bit_exact(rng, name, ranges, tile):
    n, gp, rc = 2048, 8, 8  # payload [rc, n]: one row a channel
    go_left = rng.rand(n) < 0.5
    dst, _, lefts, args = _partition(go_left, ranges, n, tile)
    bins = rng.randint(0, 60000, size=(gp, n)).astype(np.int32)
    row = rng.randn(rc, n).astype(np.float32)
    row[3] = np.arange(n)  # a perm-style integer row rides along
    # bit patterns a float accumulate would not carry: the kernel ORs bits
    row[0, ::5], row[0, 1::5] = -0.0, 1e-39
    ours_b, ours_r, _ = compact_rows(
        jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst), lefts, *args,
        tile=tile, use_pallas=True, interpret=True)
    ref_b, ref_r = _permuted(bins, row, dst)
    np.testing.assert_array_equal(np.asarray(ours_b), ref_b)
    # bit-exact: limb transport must preserve f32 payloads exactly
    np.testing.assert_array_equal(
        np.asarray(ours_r).view(np.uint32), ref_r.view(np.uint32))


@pytest.mark.parametrize("name,ranges", CASES)
def test_the_packed_pair_word_moves_the_same_rows(rng, name, ranges,
                                                  monkeypatch):
    """Where three pair tables would not fit SMEM the pair's kind rides the
    input tile's word: the same rows land in the same places, uint8 and
    int32 planes, copy, permute and skip pairs."""
    n, gp, rc, tile = 2048, 32, 8, 256
    go_left = rng.rand(n) < 0.5
    dst, _, lefts, args = _partition(go_left, ranges, n, tile)
    row = rng.randn(rc, n).astype(np.float32)
    row[0, ::5] = -0.0
    monkeypatch.setattr(compact_pallas, "SMEM_TABLE_BYTES", 0)
    jax.clear_caches()
    try:
        for bins in (rng.randint(0, 256, size=(gp, n)).astype(np.uint8),
                     rng.randint(0, 60000, size=(gp, n)).astype(np.int32)):
            ours_b, ours_r, _ = compact_rows(
                jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst), lefts,
                *args, tile=tile, use_pallas=True, interpret=True)
            ref_b, ref_r = _permuted(bins, row, dst)
            np.testing.assert_array_equal(np.asarray(ours_b), ref_b)
            np.testing.assert_array_equal(
                np.asarray(ours_r).view(np.uint32), ref_r.view(np.uint32))
    finally:
        monkeypatch.undo()
        jax.clear_caches()


# Each training cell's rows a chip and plane (groups padded, bytes a value),
# and whether its tables pack once the run offsets sit beside them.
CELL_TABLES = [
    ("higgs_binary", 4_194_304, 32, 1, False),
    ("higgs_full_dp4_a_chip", 2_625_536, 32, 1, False),
    ("higgs_binary_quant", 10_500_000, 32, 1, False),
    ("mslr_lambdarank", 2_270_296, 160, 1, False),
    ("expo_airline_cat", 11_000_000, 8, 4, True),
    ("allstate_efb", 13_184_290, 96, 1, True),
]


@pytest.mark.parametrize("name,rows,gp,plane_bytes,packed", CELL_TABLES,
                         ids=[c[0] for c in CELL_TABLES])
def test_three_pair_tables_wherever_they_fit(name, rows, gp, plane_bytes,
                                             packed):
    """The benchmark's row counts a chip at a wave of 21 slots, with the
    run offsets counted: the tables stay three up to the quantized cell's
    10,500,000 rows and pack at the Expo cell's 11,000,000, where three
    alone fit but not with the offsets' 2,687 words, and at the Allstate
    cell's 13,184,290, where three alone take 1.18 MiB of the v5e's
    1 MiB SMEM. Every cell's plane takes runs of eight output tiles."""
    tiles = -(-rows // 1024) * 1024 // COMPACT_TILE
    run_tiles, _ = compact_blocks(gp, plane_bytes, 8, COMPACT_TILE)
    assert run_tiles == 8
    mp = max_pairs_bound(tiles, 2 * 21)
    assert pair_tables_packed(mp, -(-tiles // run_tiles)) == packed
    assert (3 * 4 * mp > SMEM_TABLE_BYTES) == (name == "allstate_efb")


def test_pair_list_holds_a_range_spanning_many_tiles(rng):
    """A tree's root split is ONE range over every tile, its left and right
    rows interleaved in each: close to 4 pairs per tile. A pair list sized
    3 per tile truncated there, dropping the last output tiles' rows — on
    every tree over ~90k rows, and in no test, since none spanned more
    than 8 tiles."""
    n, gp, rc, tile = 16384, 32, 8, 256
    go_left = rng.rand(n) < 0.5
    dst, _, lefts, args = _partition(go_left, [(0, n)], n, tile)
    *_, n_pairs = build_pair_tables(lefts, *args, tile)
    assert 3 * (n // tile) < int(n_pairs[0]) <= max_pairs_bound(n // tile, 2)
    bins = rng.randint(0, 256, size=(gp, n)).astype(np.uint8)
    row = rng.randn(rc, n).astype(np.float32)
    row[3] = np.arange(n)
    ours_b, ours_r, _ = compact_rows(
        jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst), lefts, *args,
        tile=tile, use_pallas=True, interpret=True)
    ref_b, ref_r = _permuted(bins, row, dst)
    np.testing.assert_array_equal(np.asarray(ours_b), ref_b)
    np.testing.assert_array_equal(
        np.asarray(ours_r).view(np.uint32), ref_r.view(np.uint32))


@pytest.mark.parametrize("name,ranges", CASES)
def test_compact_pallas_uint8_plane(rng, name, ranges):
    """8-bit bin plane rides the single-limb path, output stays uint8 and
    matches both the permutation oracle and the int32 2-limb result."""
    n, gp, rc, tile = 2048, 32, 8, 256  # gp % 32 == 0 for the 8-bit tile
    go_left = rng.rand(n) < 0.5
    dst, _, lefts, args = _partition(go_left, ranges, n, tile)
    bins8 = rng.randint(0, 256, size=(gp, n)).astype(np.uint8)
    row = rng.randn(rc, n).astype(np.float32)
    b8, r8, _ = compact_rows(
        jnp.asarray(bins8), jnp.asarray(row), jnp.asarray(dst), lefts, *args,
        tile=tile, use_pallas=True, interpret=True)
    assert np.asarray(b8).dtype == np.uint8
    ref_b, _ = _permuted(bins8, row, dst)
    np.testing.assert_array_equal(np.asarray(b8), ref_b)
    b32, r32, _ = compact_rows(
        jnp.asarray(bins8.astype(np.int32)), jnp.asarray(row),
        jnp.asarray(dst), lefts, *args, tile=tile, use_pallas=True,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(b8).astype(np.int32),
                                  np.asarray(b32))
    np.testing.assert_array_equal(
        np.asarray(r8).view(np.uint32), np.asarray(r32).view(np.uint32))


# ------------------------------------------------------ the runs and the ring
# The kernel's grid walks runs of output tiles; a loop inside each step walks
# the run's pairs, its bounds the run offsets, which end at n_pairs. A step
# zeroes its output blocks first, so a pair the loop does not reach leaves
# its rows zero and a bound that stops short shows below as a mismatch.

def _kernel_and_xla(rng, ranges, valid, n=2048, tile=256):
    """compact_rows' operands for `ranges` over an int32 plane and a
    payload with -0.0 among its values, and what the XLA body makes of
    them."""
    go_left = rng.rand(n) < 0.5
    dst, _, lefts, args = _partition(go_left, ranges, n, tile, valid)
    bins = jnp.asarray(rng.randint(0, 60000, size=(8, n)).astype(np.int32))
    row = rng.randn(8, n).astype(np.float32)
    row[0, ::5] = -0.0
    ops = (bins, jnp.asarray(row), jnp.asarray(dst), lefts, args)
    xla = compact_rows(*ops[:4], *args, tile=tile, use_pallas=False)[:2]
    return ops, xla


def _bit_equal(got, want) -> bool:
    return (np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
            and np.array_equal(np.asarray(got[1]).view(np.uint32),
                               np.asarray(want[1]).view(np.uint32)))


# (ranges, valid): a wave that permutes and leaves duplicates, the root
# split (one range over every row: the most pairs a run), and a wave of
# invalid ranges, whose runs hold raw copies alone
RUN_CASES = {
    "multi": ([(64, 300), (512, 512), (1100, 180), (1280, 250)], None),
    "root": ([(0, 2048)], None),
    "only_copies": ([(0, 700), (900, 400)], [False, False]),
}
# (run_tiles, ring) over T = 8 tiles: a tile a run; runs of three, the last
# of two, each holding more pairs than the two-deep ring; one run of every
# pair; runs of four with a ring deeper than some of them
RUN_BLOCKS = [(1, 2), (3, 2), (8, 3), (4, 8)]
# plane dtype and groups, and whether the kind rides the input tile's word
RUN_LAYOUTS = [(np.uint8, 32, False), (np.int32, 8, False),
               (np.uint8, 32, True), (np.int32, 8, True)]


def _run_case(case, plane, gp, n=2048, tile=256):
    """compact_rows' operands for RUN_CASES[case] over a `plane` of gp rows
    and a payload with -0.0 among its values, the pair tables, and what the
    XLA body makes of the operands."""
    ranges, valid = RUN_CASES[case]
    rng = np.random.RandomState(4200)
    go_left = rng.rand(n) < 0.5
    dst, _, lefts, args = _partition(go_left, ranges, n, tile, valid)
    hi = 256 if plane == np.uint8 else 60000
    bins = jnp.asarray(rng.randint(0, hi, size=(gp, n)).astype(plane))
    row = rng.randn(8, n).astype(np.float32)
    row[0, ::5] = -0.0
    row = jnp.asarray(row)
    xla = compact_rows(bins, row, jnp.asarray(dst), lefts, *args, tile=tile,
                       use_pallas=False)[:2]
    return ((bins, row, jnp.asarray(dst)),
            build_pair_tables(lefts, *args, tile), xla)


@pytest.mark.parametrize("plane,gp,packed", RUN_LAYOUTS,
                         ids=["uint8", "int32", "uint8_packed",
                              "int32_packed"])
@pytest.mark.parametrize("run_tiles,ring", RUN_BLOCKS,
                         ids=[f"R{r}_ring{g}" for r, g in RUN_BLOCKS])
@pytest.mark.parametrize("case", sorted(RUN_CASES))
def test_runs_and_ring_move_what_the_xla_body_moves(case, run_tiles, ring,
                                                    plane, gp, packed,
                                                    monkeypatch):
    """Whatever the run length and the ring's depth, over a uint8 or an
    int32 plane, with the kind in a table of its own or in the input
    tile's word: the rows land where the XLA scatter puts them, bit for
    bit."""
    if packed:
        monkeypatch.setattr(compact_pallas, "SMEM_TABLE_BYTES", 0)
    (bins, row, dst), tables, xla = _run_case(case, plane, gp)
    got = _pallas_compact_call(bins, row, dst, *tables, 256, True,
                               run_tiles=run_tiles, ring=ring)
    assert _bit_equal(got, xla)


def test_the_run_cases_hold_every_kind_of_run():
    """RUN_CASES x RUN_BLOCKS hold runs of raw copies alone, runs of more
    pairs than the ring is deep, duplicates inside a run, and a last run
    shorter than the others (8 tiles in runs of 3)."""
    seen = set()
    for case in RUN_CASES:
        _, (pair_in, pair_out, pcopy, n_pairs), _ = _run_case(case, np.uint8,
                                                              32)
        live = int(n_pairs[0])
        kinds = np.asarray(pcopy)[:live]
        for run_tiles, ring in RUN_BLOCKS:
            n_runs = -(-8 // run_tiles)
            off = np.asarray(run_offsets(pair_out, n_pairs, run_tiles,
                                         n_runs))
            assert off[0] == 0 and off[-1] == live
            assert (np.diff(off) > 0).all()  # no run without its pairs
            for a, b in zip(off[:-1], off[1:]):
                outs = np.asarray(pair_out)[a:b] // run_tiles
                assert (outs == outs[0]).all()  # the run's pairs alone
                if (kinds[a:b] == 1).all():
                    seen.add("only copies")
                if b - a > ring:
                    seen.add("more pairs than the ring")
                if (kinds[a:b] == 2).any():
                    seen.add("duplicates")
            if 8 % run_tiles:
                seen.add("short last run")
    assert seen == {"only copies", "more pairs than the ring", "duplicates",
                    "short last run"}


@pytest.mark.parametrize("n,tile", [(2048, 256), (5120, 512), (16384, 256)])
def test_the_grid_walks_one_step_a_run_of_output_tiles(rng, n, tile):
    """compact_grid_steps is ceil(T / R), R the run compact_blocks takes
    for the plane (8 tiles; the 10 tiles of 5,120 rows end in a run of
    two), and compact_pairs the tables' live pairs, as before the loop."""
    go_left = rng.rand(n) < 0.5
    ranges = [(0, n // 3), (n // 2, n // 4)]
    dst, _, lefts, args = _partition(go_left, ranges, n, tile)
    bins = jnp.asarray(rng.randint(0, 256, size=(32, n)).astype(np.uint8))
    row = jnp.asarray(rng.randn(8, n).astype(np.float32))
    *got, work = compact_rows(bins, row, jnp.asarray(dst), lefts, *args,
                              tile=tile, use_pallas=True, interpret=True)
    assert _bit_equal(got, _permuted(np.asarray(bins), np.asarray(row), dst))
    run_tiles, _ = compact_blocks(32, 1, 8, tile)
    counts = dict(zip(COMPACT_WORK_FIELDS, np.asarray(work).tolist()))
    *_, n_pairs = build_pair_tables(lefts, *args, tile)
    assert counts["compact_grid_steps"] == -(-(n // tile) // run_tiles)
    assert counts["compact_pairs"] == int(n_pairs[0])


@pytest.mark.parametrize("ranges,valid", [
    ([(0, 700), (900, 400)], [False, False]),
    ([(300, 0), (2048, 0)], None),
], ids=["every_range_invalid", "every_range_empty"])
def test_a_compaction_that_moves_no_row_walks_one_copy_a_tile(rng, ranges,
                                                              valid):
    """No valid range: n_pairs == T, every pair a tile's raw copy of
    itself, and the grid walks one step a run of output tiles."""
    n, tile = 2048, 256
    (bins, row, dst, lefts, args), xla = _kernel_and_xla(rng, ranges, valid)
    _, _, pcopy, n_pairs = build_pair_tables(lefts, *args, tile)
    assert int(n_pairs[0]) == n // tile
    assert (np.asarray(pcopy)[:n // tile] == 1).all()
    *got, work = compact_rows(bins, row, dst, lefts, *args, tile=tile,
                              use_pallas=True, interpret=True)
    assert _bit_equal(got, xla) and _bit_equal(got, (bins, row))
    counts = dict(zip(COMPACT_WORK_FIELDS, np.asarray(work).tolist()))
    assert counts["compact_pairs"] == counts["compact_copy_pairs"] \
        == n // tile
    run_tiles, _ = compact_blocks(8, 4, 8, tile)
    assert counts["compact_grid_steps"] == -(-(n // tile) // run_tiles)


@pytest.mark.parametrize("name,ranges,valid,p_left", GLUE_CASES,
                         ids=[c[0] for c in GLUE_CASES])
def test_the_grid_bound_is_the_live_pair_count(rng, name, ranges, valid,
                                               p_left):
    """The kernel called with its tables cut to the live pairs (n_pairs
    then IS the tables' length) moves what the XLA body moves, bit for
    bit; its pair loops stopped before the last pair that moves rows, it
    does not."""
    n, tile = 2048, 256
    (bins, row, dst, lefts, args), xla = _kernel_and_xla(rng, ranges, valid)
    pair_in, pair_out, pcopy, n_pairs = build_pair_tables(lefts, *args, tile)
    live = int(n_pairs[0])

    def kernel(tables, bound):
        return _pallas_compact_call(bins, row, dst, *tables,
                                    jnp.full(1, bound, jnp.int32), tile, True)

    tables = (pair_in, pair_out, pcopy)
    assert _bit_equal(kernel(tables, live), xla)
    assert _bit_equal(kernel([t[:live] for t in tables], live), xla)
    last_mover = int(np.flatnonzero(np.asarray(pcopy)[:live] < 2)[-1])
    assert not _bit_equal(kernel(tables, last_mover), xla)


def test_compact_xla_fallback_uint8(rng):
    n, gp = 1024, 4
    ranges = [(100, 500)]
    go_left = rng.rand(n) < 0.3
    dst, _, lefts, args = _partition(go_left, ranges, n, COMPACT_TILE)
    bins = rng.randint(0, 256, size=(gp, n)).astype(np.uint8)
    row = rng.randn(3, n).astype(np.float32)
    ours_b, *_ = compact_rows(
        jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst), lefts, *args,
        use_pallas=False)
    assert np.asarray(ours_b).dtype == np.uint8
    ref_b, _ = _permuted(bins, row, dst)
    np.testing.assert_array_equal(np.asarray(ours_b), ref_b)


def test_compact_xla_fallback_exact(rng):
    n, gp, rc = 1024, 3, 5
    ranges = [(100, 500), (700, 300)]
    go_left = rng.rand(n) < 0.3
    dst, _, lefts, args = _partition(go_left, ranges, n, COMPACT_TILE)
    bins = rng.randint(0, 256, size=(gp, n)).astype(np.int32)
    row = rng.randn(rc, n).astype(np.float32)
    ours_b, ours_r, _ = compact_rows(
        jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst), lefts, *args,
        use_pallas=False)
    ref_b, ref_r = _permuted(bins, row, dst)
    np.testing.assert_array_equal(np.asarray(ours_b), ref_b)
    np.testing.assert_array_equal(np.asarray(ours_r), ref_r)


def test_compact_one_sided(rng):
    """Empty-left and empty-right partitions stay identity permutations."""
    n, tile = 1024, 256
    for flag in (True, False):
        go_left = np.full(n, flag)
        ranges = [(0, 600)]
        dst, n_left, lefts, args = _partition(go_left, ranges, n, tile)
        np.testing.assert_array_equal(dst, np.arange(n))
        assert n_left[0] == (600 if flag else 0)
        bins = np.arange(2 * n, dtype=np.int32).reshape(2, n) % 256
        bins = np.vstack([bins] * 4)  # gp=8
        row = np.arange(n * 8, dtype=np.float32).reshape(8, n)
        ob, orr, _ = compact_rows(
            jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst), lefts,
            *args, tile=tile, use_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(ob), bins)
        np.testing.assert_array_equal(np.asarray(orr), row)


def test_compact_rows_refuses_samples_of_another_tile(rng):
    """dst's LeftCounts are taken at one tile size; the kernel's grid at
    another would read the wrong boundaries, so it is refused outright."""
    n = 2048
    go_left = rng.rand(n) < 0.5
    dst, _, lefts, args = _partition(go_left, [(0, n)], n, 512)
    with pytest.raises(ValueError, match="LeftCounts holds 4 tiles"):
        compact_rows(jnp.zeros((8, n), jnp.int32), jnp.zeros((8, n)),
                     jnp.asarray(dst), lefts, *args, tile=256,
                     use_pallas=True, interpret=True)


def test_pair_table_bound_and_coverage(rng):
    """n_pairs respects the static bound; every output tile is produced."""
    n, tile = 4096, 256
    ranges = [(0, 900), (1000, 200), (1200, 64), (1500, 2000)]
    go_left = rng.rand(n) < 0.5
    _, _, lefts, args = _partition(go_left, ranges, n, tile)
    pi, po, copy, npairs = build_pair_tables(lefts, *args, tile)
    t = n // tile
    mp = max_pairs_bound(t, 2 * len(ranges))
    assert pi.shape == (mp,)
    assert int(npairs[0]) <= mp
    # all T output tiles covered, pairs sorted by out tile
    live = np.asarray(po)[:int(npairs[0])]
    assert set(live.tolist()) == set(range(t))
    assert (np.diff(live) >= 0).all()
    # pcopy semantics: 1 = raw copy of an untouched identity tile,
    # 2 = duplicate pair demoted to a skip (must repeat its predecessor's
    # blocks and never open an output block), 0 = one-hot permute.
    touched = _match(ranges, n).any(axis=0).reshape(t, tile).any(axis=1)
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
    """The static bound is derived for DISJOINT ranges. Ranges that overlap
    (three over every row: each lists every tile's rows again, ~11 pairs a
    tile) are the one way left to outgrow it. The list is truncated either
    way; LGBM_TPU_SANITIZE=1 says so instead of dropping the rows in
    silence."""
    n, tile = 8192, 256
    ranges = [(0, n)] * 3
    go_left = rng.rand(n) < 0.5
    _, _, lefts, args = _partition(go_left, ranges, n, tile)
    *_, n_pairs = build_pair_tables(lefts, *args, tile)
    assert int(n_pairs[0]) > max_pairs_bound(n // tile, 2 * len(ranges))
    monkeypatch.setenv("LGBM_TPU_SANITIZE", "1")
    with pytest.raises(Exception, match="the truncated list drops rows"):
        jax.block_until_ready(build_pair_tables(lefts, *args, tile))


# ------------------------------------------------------------- the pair counts
# What compact_rows hands back beside the arrays (COMPACT_WORK_FIELDS): the
# tree program sums it into the `tree_wave` note, and the chip benchmark reads
# the kernel's time per pair from it.

@pytest.mark.parametrize("name,ranges,valid,p_left", GLUE_CASES,
                         ids=[c[0] for c in GLUE_CASES])
def test_pair_counts_are_a_recount_of_the_pair_tables(rng, name, ranges,
                                                      valid, p_left):
    n, tile = 2048, 256
    go_left = rng.rand(n) < p_left
    dst, _, lefts, args = _partition(go_left, ranges, n, tile, valid)
    _, _, pcopy, n_pairs = build_pair_tables(lefts, *args, tile)
    bins = rng.randint(0, 256, size=(32, n)).astype(np.uint8)
    row = rng.randn(8, n).astype(np.float32)
    *_, work = compact_rows(
        jnp.asarray(bins), jnp.asarray(row), jnp.asarray(dst), lefts, *args,
        tile=tile, use_pallas=True, interpret=True)
    assert work.shape == (len(COMPACT_WORK_FIELDS),) and work.dtype == jnp.int32
    got = dict(zip(COMPACT_WORK_FIELDS, np.asarray(work).tolist()))
    steps = -(-(n // tile) // compact_blocks(32, 1, 8, tile)[0])
    np.testing.assert_array_equal(
        np.asarray(work), np.asarray(pair_work_counts(pcopy, n_pairs, steps)))
    live = np.asarray(pcopy)[:int(n_pairs[0])]
    assert got["compact_pairs"] == live.size
    assert got["compact_copy_pairs"] == int((live == 1).sum())
    assert got["compact_permute_pairs"] == int((live == 0).sum())
    duplicates = int((live == 2).sum())
    assert got["compact_pairs"] == (got["compact_copy_pairs"]
                                    + got["compact_permute_pairs"]
                                    + duplicates)
    # the grid walks one step a run of output tiles, the pairs inside it
    assert got["compact_grid_steps"] == steps
    assert got["compact_pairs"] > 0
    assert got["compact_pairs"] <= max_pairs_bound(n // tile,
                                                   2 * len(ranges))
    # every tile is written once at least: a raw copy where no range
    # touches it, a permute where one does
    touched = _match(ranges, n, valid).any(axis=0).reshape(-1, tile).any(
        axis=1)
    assert got["compact_copy_pairs"] == int((~touched).sum())
    assert got["compact_permute_pairs"] >= int(touched.sum())


def test_the_xla_fallback_counts_no_pair(rng):
    n = 1024
    go_left = rng.rand(n) < 0.3
    dst, _, lefts, args = _partition(go_left, [(100, 500)], n, COMPACT_TILE)
    *_, work = compact_rows(
        jnp.zeros((4, n), jnp.uint8), jnp.zeros((3, n), jnp.float32),
        jnp.asarray(dst), lefts, *args, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(work),
                                  np.zeros(len(COMPACT_WORK_FIELDS)))
