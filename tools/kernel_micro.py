#!/usr/bin/env python3
"""kernel_micro: one wave of each Mosaic kernel of the tree program, timed
on the chip at a benchmark cell's shape.

    python3 tools/kernel_micro.py --cell higgs_binary --cell allstate_efb
    python3 tools/kernel_micro.py --cell higgs_binary --baseline _scratch/parent
    python3 tools/kernel_micro.py --cpu     # tiny shapes, interpreted kernels

For each cell it builds one synthetic wave of WAVE_K leaf ranges at the
cell's rows and plane width (the ranges split `cover` of the rows, each
range's rows going left with its own probability) and prints one JSON line
a measurement:

- `compact`: the compaction kernel over the wave's pair tables, with the
  run of output tiles a grid step writes and the ring of input tiles its
  DMAs keep (`compact_blocks`' choice at the cell's plane);
- `compact_blocks_sweep`: the same call at each run length of
  SWEEP_RUN_TILES and each ring depth of SWEEP_RINGS, each output checked
  against the chosen one's;
- `compact_pair_kinds`: T identity pairs each a raw block copy, each a
  one-hot permute, and each a permute followed by its duplicate, so the
  price of a copy pair, a permute pair and a duplicate (live, skipped) pair
  (and the baseline's, where one is given);
- `hist_wave`: the histogram kernel over the wave's smaller children;
- `hist_root`: the histogram kernel's one-slot root pass over every tile.

`--baseline DIR` loads another checkout's two kernel modules
(`DIR/lightgbm_tpu/ops/{compact,hist}_pallas.py`, their package imports
resolved against this checkout) and times them beside this checkout's on
the same operands, interleaved (A B B A), checking the outputs bit for bit;
both take the same pair tables, and each builds its own kernel operands
from them. Times are host-clock milliseconds a call, the median of
back-to-back calls ended by `block_until_ready`: a chip's numbers only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The plane a cell's tree program histograms: its groups (Higgs's 28
# features; Allstate's 69 EFB groups from the binning sample's layout,
# PERF.md section 4) and the widest group's bins.
CELLS = {"higgs_binary": (28, 255, 0.64), "allstate_efb": (69, 256, 0.5)}
TINY_ROWS = 16384
# A measurement's rounds of A B B A (one with --cpu), and the first cell's
# seed of its synthetic wave (the next cell's is SEED + 1).
ROUNDS = 4
SEED = 41
# The compaction kernel's run lengths and ring depths timed beside the ones
# compact_blocks chooses.
SWEEP_RUN_TILES = (1, 4, 8)
SWEEP_RINGS = (4, 8, 16)


def _rows(cell: str) -> int:
    """The cell's rows padded as the learner pads them, to whole 1,024-row
    histogram tiles."""
    with open(os.path.join(REPO, "benchmark", "configs", cell + ".json"),
              encoding="utf-8") as fh:
        return -(-int(json.load(fh)["rows"]) // 1024) * 1024


def _load_baseline(root: str, name: str) -> types.ModuleType:
    path = os.path.join(root, "lightgbm_tpu", "ops", name + ".py")
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    src = src.replace("from ..utils import", "from lightgbm_tpu.utils import")
    src = src.replace("from .. import", "from lightgbm_tpu import")
    mod = types.ModuleType("baseline_" + name)
    mod.__file__ = path
    exec(compile(src, path, "exec"), mod.__dict__)  # noqa: S102
    return mod


def _emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


class Bench:
    def __init__(self, rounds: int, interpret: bool):
        import jax

        self.jax = jax
        self.rounds = rounds
        self.interpret = interpret

    def timed(self, f, reps: int) -> float:
        r = f()
        self.jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(reps):
            r = f()
        self.jax.block_until_ready(r)
        return (time.perf_counter() - t0) / reps * 1e3

    def interleave(self, fns: dict, reps: int) -> dict:
        """name -> (median ms a call, every round's reading); two names
        alternate A B B A so that a drift of the host clock hits both."""
        names = list(fns)
        got = {n: [] for n in names}
        for r in range(self.rounds):
            order = names if r % 2 == 0 else names[::-1]
            for n in order + order[::-1]:
                got[n].append(self.timed(fns[n], reps))
        return {n: (float(np.median(v)), [round(x, 4) for x in v])
                for n, v in got.items()}

    def bit_equal(self, a, b) -> bool:
        jnp = self.jax.numpy
        ok = True
        for x, y in zip(self.jax.tree_util.tree_leaves(a),
                        self.jax.tree_util.tree_leaves(b)):
            if x.dtype == jnp.float32:
                x = self.jax.lax.bitcast_convert_type(x, jnp.int32)
                y = self.jax.lax.bitcast_convert_type(y, jnp.int32)
            ok &= bool(jnp.array_equal(x, y))
        return ok


def _wave(rng, n: int, k: int, cover: float):
    """2k leaves in the leaf-contiguous layout; the wave splits every other
    one (together `cover` of the rows)."""
    sel = rng.dirichlet(np.ones(k) * 2) * cover * n
    rest = rng.dirichlet(np.ones(k) * 2) * (1 - cover) * n
    sizes = np.floor(np.stack([sel, rest], axis=1).reshape(-1)).astype(
        np.int64)
    sizes[-1] += n - sizes.sum()
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])[0::2]
    counts = sizes[0::2]
    go_left = np.zeros(n, bool)
    for s, c, p in zip(starts, counts, rng.uniform(0.15, 0.85, k)):
        go_left[s:s + c] = rng.random(c) < p
    return starts.astype(np.int32), counts.astype(np.int32), go_left


def run_cell(b: Bench, cell: str, n: int, seed: int, base) -> None:
    jax, jnp = b.jax, b.jax.numpy
    from lightgbm_tpu.ops import compact_pallas as C
    from lightgbm_tpu.ops import hist_pallas as H
    from lightgbm_tpu.treelearner.device import WAVE_K as K

    groups, num_bins, cover = CELLS[cell]
    interp = b.interpret
    reps_c, reps_h = (1, 1) if interp else (max(1, 32_000_000 // n), 20)
    rng = np.random.default_rng(seed)
    gp = -(-groups // 32) * 32
    tile = C.COMPACT_TILE
    T = n // tile
    bins = jnp.asarray(rng.integers(0, num_bins, (gp, n), dtype=np.uint8))
    row = jnp.asarray(rng.standard_normal((8, n), dtype=np.float32))
    starts, counts, go_left = _wave(rng, n, K, cover)
    match = np.zeros((K, n), bool)
    for k in range(K):
        match[k, starts[k]:starts[k] + counts[k]] = True
    valid = jnp.ones(K, bool)
    st, ct = jnp.asarray(starts), jnp.asarray(counts)
    dst, n_left, lefts = jax.jit(C.range_partition_dst, static_argnums=6)(
        jnp.asarray(go_left), jnp.asarray(match),
        jnp.asarray(match.any(axis=0)), st, ct, valid, tile)
    del match
    tables = jax.jit(C.build_pair_tables, static_argnums=4)(
        lefts, st, ct, valid, tile)
    live = int(tables[3][0])
    kinds = np.asarray(tables[2])[:live]
    mp = tables[0].shape[0]
    run_tiles, ring = C.compact_blocks(gp, 1, 8, tile)
    _emit(cell=cell, rows=n, plane=[gp, n], tiles=T, table_steps=mp,
          pairs=live, copy=int((kinds == 1).sum()),
          permute=int((kinds == 0).sum()), duplicate=int((kinds == 2).sum()),
          packed=C.pair_tables_packed(mp, -(-T // run_tiles)))

    def compact(mod, d, *t, **blocks):
        return lambda: mod._pallas_compact_call(bins, row, d, *t, tile,
                                                interp, **blocks)

    fns = {"this": compact(C, dst, *tables)}
    if base:
        fns["baseline"] = compact(base[0], dst, *tables)
    res = b.interleave(fns, reps_c)
    line = dict(kernel="compact", cell=cell, run_tiles=run_tiles, ring=ring,
                grid_steps=-(-T // run_tiles), ms=res["this"][0],
                us_per_live_pair=res["this"][0] * 1e3 / live,
                runs={k: v[1] for k, v in res.items()})
    if base:
        line.update(bit_equal=b.bit_equal(fns["this"](), fns["baseline"]()),
                    ms_baseline=res["baseline"][0],
                    us_per_live_pair_baseline=res["baseline"][0] * 1e3 / live)
    _emit(**line)
    # the run lengths and ring depths tried beside compact_blocks' choice
    sweep = {f"R{r}_ring{g}": compact(C, dst, *tables, run_tiles=r, ring=g)
             for r, g in sorted({(r, ring) for r in SWEEP_RUN_TILES}
                                | {(run_tiles, g) for g in SWEEP_RINGS})}
    want = fns["this"]()
    res = b.interleave(sweep, reps_c)
    _emit(kernel="compact_blocks_sweep", cell=cell,
          **{k: dict(ms=v[0], us_per_live_pair=v[0] * 1e3 / live,
                     bit_equal=b.bit_equal(sweep[k](), want))
             for k, v in res.items()})

    ident = jnp.arange(n, dtype=jnp.int32)
    ids = jnp.arange(T, dtype=jnp.int32)

    def padded(x):
        return jnp.concatenate([x, jnp.full(mp - x.shape[0], x[-1],
                                            jnp.int32)])

    n_t, n_2t = jnp.full(1, T, jnp.int32), jnp.full(1, 2 * T, jnp.int32)
    two = jnp.repeat(ids, 2)
    kind_tables = {
        "copy": (padded(ids), padded(ids), padded(jnp.ones(T, jnp.int32)),
                 n_t),
        "permute": (padded(ids), padded(ids),
                    padded(jnp.zeros(T, jnp.int32)), n_t),
        "permute_dup": (padded(two), padded(two),
                        padded(jnp.tile(jnp.asarray([0, 2], jnp.int32), T)),
                        n_2t)}
    identity = all(b.bit_equal(compact(C, ident, *t)(), (bins, row))
                   for t in kind_tables.values())
    sides = {"": C, "_baseline": base[0]} if base else {"": C}
    res = {}
    for name, t in kind_tables.items():
        res.update(b.interleave({name + side: compact(mod, ident, *t)
                                 for side, mod in sides.items()}, reps_c))
    line = dict(kernel="compact_pair_kinds", cell=cell,
                outputs_identity=identity,
                runs={k: v[1] for k, v in res.items()})
    for side in sides:
        line.update({
            "us_per_copy_pair" + side: res["copy" + side][0] * 1e3 / T,
            "us_per_permute_pair" + side: res["permute" + side][0] * 1e3 / T,
            "us_per_duplicate_pair" + side: (res["permute_dup" + side][0]
                                             - res["permute" + side][0])
            * 1e3 / T})
    _emit(**line)
    del row

    # the histogram wave over the K splits' smaller children
    tr = H.DEFAULT_TILE_ROWS
    nl = np.asarray(n_left)
    small_left = nl <= counts - nl
    hs = np.where(small_left, starts, starts + nl).astype(np.int32)
    he = np.where(small_left, starts + nl, starts + counts).astype(np.int32)
    slot_np = np.full(n, K, np.int32)
    for k in range(K):
        slot_np[hs[k]:he[k]] = k
    gh = jnp.asarray(rng.standard_normal((3, n), dtype=np.float32))
    g_blocks = -(-groups // 32)
    for name, slot, (tiles, slots, n_pairs, _), n_slots in (
            ("hist_wave", jnp.asarray(slot_np),
             H.tile_slot_pairs(jnp.asarray(hs), jnp.asarray(he), valid,
                               n // tr, tr), K),
            ("hist_root", jnp.zeros(n, jnp.int32),
             H.tile_slot_pairs(jnp.zeros(1, jnp.int32),
                               jnp.full(1, n, jnp.int32), jnp.ones(1, bool),
                               n // tr, tr), 1)):
        def hist(mod, slot=slot, tiles=tiles, slots=slots, n_pairs=n_pairs,
                 n_slots=n_slots):
            return lambda: mod.pallas_histogram_slots_ragged(
                bins, gh, slot, tiles, slots, n_pairs, num_bins, n_slots,
                f32=True, n_groups=groups, interpret=interp)

        visits, steps = int(n_pairs[0]), tiles.shape[0]
        fns = {"this": hist(H)}
        if base:
            fns["baseline"] = hist(base[1])
        res = b.interleave(fns, reps_h)
        line = dict(kernel=name, cell=cell, visits=visits,
                    table_steps=steps, group_blocks=g_blocks,
                    ms=res["this"][0],
                    us_per_visit=res["this"][0] * 1e3 / visits,
                    runs={k: v[1] for k, v in res.items()})
        if base:
            line.update(
                bit_equal=b.bit_equal(fns["this"](), fns["baseline"]()),
                ms_baseline=res["baseline"][0])
        _emit(**line)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", action="append", choices=sorted(CELLS))
    ap.add_argument("--baseline", help="another checkout's root")
    ap.add_argument("--cpu", action="store_true",
                    help=f"{TINY_ROWS} rows, interpreted kernels")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    import jax

    import lightgbm_tpu  # noqa: F401  (the package's JAX settings)

    base = None
    if args.baseline:
        base = (_load_baseline(args.baseline, "compact_pallas"),
                _load_baseline(args.baseline, "hist_pallas"))
    b = Bench(1 if args.cpu else ROUNDS, args.cpu)
    _emit(device=str(jax.devices()[0].device_kind), jax=jax.__version__,
          baseline=args.baseline)
    for i, cell in enumerate(args.cell or sorted(CELLS)):
        run_cell(b, cell, TINY_ROWS if args.cpu else _rows(cell),
                 SEED + i, base)
    return 0


if __name__ == "__main__":
    sys.exit(main())
