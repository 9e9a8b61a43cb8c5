"""Cross-device traffic formulas and the Pallas VMEM lint tables.

Two kinds of reader remain:

  * **The sharded learners** (parallel/learners.py) publish each wave's
    cross-device bytes through `ici_bytes_per_wave`,
    `voting_ici_bytes_per_wave` and `feature_ici_bytes_per_wave` (the
    docs/PERF_NOTES.md models as code), and the share of it dispatched
    behind local compute through `ici_overlap_pct`. The `tree_wave` flight
    note's `ici_bytes` carries the first three to the benchmark.
  * **graftlint R14** (tools/graftlint/rules/pallas_vmem.py) reads
    `PALLAS_VMEM_DEFAULT_BYTES` and `PALLAS_DIM_BOUNDS` from this file's
    AST, found by its name; nothing imports them at run time.
"""
from __future__ import annotations

from typing import Tuple

# packed best-split record length ([2K, F_pad, REC] all_gather, f32)
REC_FIELDS = 14


def ici_bytes_per_wave(wave_width: int, f_pad: int, max_bins: int,
                       ch: int = 3, pool_bytes: int = 4) -> int:
    """Cross-device bytes per wave for the data-parallel learner
    (PERF_NOTES round-6): one psum_scatter of the raw [K, F_pad, Bmax, CH]
    histograms plus the [2K, F_pad, REC] best-record all_gather."""
    k = int(wave_width)
    return (k * int(f_pad) * int(max_bins) * int(ch) * int(pool_bytes)
            + 2 * k * int(f_pad) * REC_FIELDS * 4)


def voting_ici_bytes_per_wave(wave_width: int, k_local: int, k_global: int,
                              max_bins: int, n_shards: int, ch: int = 3,
                              pool_bytes: int = 4) -> int:
    """Cross-device bytes per wave for the voting-parallel learner
    (PERF_NOTES round-9, PV-Tree): the [2K, D*k_local] nomination
    all_gather plus the psum of the [2K, k_global, Bmax, CH] ELECTED
    histogram slices. No term scales with the feature count — that is the
    whole point of the vote."""
    k = int(wave_width)
    return (2 * k * int(n_shards) * int(k_local) * 4
            + 2 * k * int(k_global) * int(max_bins) * int(ch)
            * int(pool_bytes))


def feature_ici_bytes_per_wave(wave_width: int, n_shards: int) -> int:
    """Cross-device bytes per wave for the feature-parallel learner
    (PERF_NOTES round-9): rows are replicated and every histogram stays
    local, so the only traffic is the [2K, D, REC] best-record all_gather
    — independent of the row count AND the feature count."""
    return 2 * int(wave_width) * int(n_shards) * REC_FIELDS * 4


def ici_overlap_pct(overlapped_bytes: int, total_bytes: int) -> float:
    """Share of a wave's ICI traffic dispatched while independent local
    compute is still pending (double-buffered dispatch, PERF_NOTES
    round-9) — the fraction of the transfer XLA's async collectives can
    hide behind the Pallas kernels. Byte accounting, so the gauge is
    deterministic."""
    if int(total_bytes) <= 0:
        return 0.0
    return round(100.0 * int(overlapped_bytes) / int(total_bytes), 2)


# graftlint R14 bounds every pallas_call's worst-case footprint by the
# DEFAULT: the smallest VMEM per core of any device the kernels are
# expected to run on (see the guide numbers cited in docs/PERF_NOTES.md).
PALLAS_VMEM_DEFAULT_BYTES: int = 16777216          # 16 MiB conservative floor

# Lint-time worst-case caps for BlockSpec dimensions that are runtime
# values (static argnums, shape components). R14 substitutes these when a
# dimension does not resolve to a literal/module constant; raising a cap
# here widens the envelope the kernels are certified for, so keep each in
# sync with the call sites' actual maxima.
PALLAS_DIM_BOUNDS: Tuple[Tuple[str, int], ...] = (
    ("num_bins", 256), ("n_bins", 256),   # histogram bins cap (uint8 codes)
    ("tile_rows", 2048),                  # row tiles (hist/predict)
    ("GB", 64), ("CH", 8), ("SC", 64),    # hist group block / channels / slots
    ("Gp", 512), ("tile", 1024), ("rc", 16),  # compact planes / row tile / cols
    ("F", 1024), ("C", 32),               # predict feature row / tree outputs
)
