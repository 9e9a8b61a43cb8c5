"""Pallas TPU fused split-scan kernel: the per-feature gain search in one pass.

The XLA formulation in ops/split.py (per_feature_best) materializes every
stage of the search — cumsum over bins, two left/right aggregate lanes, the
masked gain surfaces, the lane-major argmax operand — as separate [K, F_pad,
Bmax{,3}] tensors through HBM. Per wave that is ~10 round trips of the
histogram working set for a computation whose arithmetic intensity is tiny;
docs/PERF_NOTES.md (round 8) gives the byte model that motivated fusing
them. This kernel fuses the whole pipeline over a feature tile so every
intermediate lives in VMEM:

    grid (F_pad / FT,); per step, for an [FT, B] feature slab:
        pull the missing bin out of the ordered scan        # VPU
        cumsum over bins -> left aggregates (both lanes)    # VPU scan
        right aggregates, validity masks, regularized gains # VPU
        lane-major argmax + masked-max stat extraction      # VPU reduce
        packed [FT, REC_PAD] split records                  # one HBM write

The per-tile scan + carry decomposition of arxiv 2505.15112 degenerates to
its single-tile case here on purpose: Bmax <= 256 always (max_bin caps at
255), so the whole bin axis rides the lane dimension of one block and the
tile-parallel axis is features. Keeping the bin axis unsplit is also what
makes bit-identity cheap: the in-kernel jnp.cumsum sees exactly the same
length-Bmax scan the XLA path runs, so interpret mode reproduces the XLA
records bit-for-bit (pinned by tests/test_scan_pallas.py). The two exact-
value extractions (missing bin, picked threshold stats) use masked-max
instead of gather — a max over {v, -inf, ...} returns v's bits unchanged,
while a masked sum would lose the sign of a -0.0 aggregate.

The identity contract is jit-vs-jit AT THE DISPATCH BOUNDARY. Embedded in
a larger jit (the device learner's fused tree growth), the XLA body is not
even stable against ITSELF: XLA fuses the gain arithmetic differently in
the big-jit context and drifts 1 ULP from its standalone compilation —
the standalone value being the one this kernel reproduces (the
`best_gain - gain_shift` cancellation then amplifies that one rounding to
a few ULP of the result). In practice that surfaces as a tiny wobble in
the stored split_gain metadata between LGBM_TPU_SCAN_PALLAS on/off
end-to-end runs and, with use_quantized_grad, as one f32 ULP in the stored
hessian sums (internal_weight, leaf_weight): there the totals reach the
scan as `int * scale`, XLA:CPU duplicates that multiply into the XLA
body's fusions and LLVM contracts it with `total - left` into an fma, so
the body subtracts from the unrounded product while this kernel reads the
rounded totals through its operand boundary. Decisions, thresholds,
counts, left sums and leaf outputs stay byte-equal on every plane, and the
hessian sums do on the float planes (all of it pinned by
test_train_bit_identical_fused_vs_xla).

Scope: numeric/default-direction lanes only. Categorical and CTR lanes stay
on the XLA path behind the same find_best_split dispatch, as does any scan
with monotone constraints (the clamped-output gain variant).

Status: explicit opt-in (LGBM_TPU_SCAN_PALLAS=1), never the default. The
v5e compiler refuses the body — "Unimplemented primitive in Pallas TPU
lowering for KernelType.TC: cumsum", with jnp.argmax next in line — and a
scan that Mosaic does lower (log-step rolls, a triangular matmul) would
have to be shown to add in the XLA cumsum's order before the identity
contract above could stand. So the XLA body runs on every backend, the
tests run the kernel interpreted (LGBM_TPU_PALLAS_INTERPRET=1), and opting
in on a TPU raises that NotImplementedError instead of falling back.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import telemetry
from ..common import MISSING_NAN, MISSING_NONE

# classify this entry's jit cache misses as kernel compiles (telemetry's
# recompile watcher keeps them separate from XLA churn)
for _fn in ("fused_split_scan",):
    telemetry.register_kernel_fn(_fn)

SCAN_TILE_FEATURES = 8  # feature-tile sublane dim (Mosaic f32 tile is (8, 128))
REC_PAD = 128  # record columns padded to one lane tile; cols 14+ are zero
N_REC = 14  # == len(ops.split.SPLIT_FIELDS); pinned by test_scan_pallas
K_EPSILON = 1e-15  # == ops.split.K_EPSILON; pinned by test_scan_pallas

# meta_cols column layout (one row per feature, padded to REC_PAD lanes)
_MC_MISSING_POS = 0
_MC_HAS_MISSING = 1
_MC_NBINS = 2
_MC_GATE = 3  # numeric-lane feature gate: ~is_categorical & feature_mask
_MC_PENALTY = 4
_MC_PARAMS = 5  # l1, l2, min_data, min_hess, min_gain, max_delta
_MC_TOTALS = 11  # total_g, total_h, total_count
_MC_COLS = 14


def use_scan_pallas() -> bool:
    """LGBM_TPU_SCAN_PALLAS=1|pallas opts in to the fused kernel; the XLA
    body is the default on every backend (see the module docstring: Mosaic
    refuses the kernel today). Resolved at trace time of the enclosing jit
    (find_best_split / grow_tree_on_device), so flip it before the first
    training call — tests that toggle mid-process clear the jit caches."""
    return os.environ.get("LGBM_TPU_SCAN_PALLAS", "").lower() in (
        "1", "true", "on", "pallas")


# graftlint: disable=untimed-hot-func -- traced kernel body; the jitted call site owns the timer scope
def _make_scan_kernel(n_bins: int, feat_tile: int, barrier: bool):
    neg_inf = float("-inf")  # python float: weak-typed, not a captured array

    def fused_scan_kernel(hist_ref, meta_ref, valid_ref, out_ref):
        g = hist_ref[0]  # [FT, B] f32 grad sums
        h = hist_ref[1]
        c = hist_ref[2]
        valid = valid_ref[...] > 0.0  # [FT, B]

        mpos = meta_ref[:, _MC_MISSING_POS:_MC_MISSING_POS + 1]
        mpos = mpos.astype(jnp.int32)  # [FT, 1]
        has_missing = meta_ref[:, _MC_HAS_MISSING:_MC_HAS_MISSING + 1] > 0.0
        nbins = meta_ref[:, _MC_NBINS:_MC_NBINS + 1].astype(jnp.int32)
        gate = meta_ref[:, _MC_GATE:_MC_GATE + 1] > 0.0
        penalty = meta_ref[:, _MC_PENALTY:_MC_PENALTY + 1]
        l1 = meta_ref[:, _MC_PARAMS:_MC_PARAMS + 1]
        l2 = meta_ref[:, _MC_PARAMS + 1:_MC_PARAMS + 2]
        min_data = meta_ref[:, _MC_PARAMS + 2:_MC_PARAMS + 3]
        min_hess = meta_ref[:, _MC_PARAMS + 3:_MC_PARAMS + 4]
        min_gain = meta_ref[:, _MC_PARAMS + 4:_MC_PARAMS + 5]
        max_delta = meta_ref[:, _MC_PARAMS + 5:_MC_PARAMS + 6]
        total_g = meta_ref[:, _MC_TOTALS:_MC_TOTALS + 1]
        total_h = meta_ref[:, _MC_TOTALS + 1:_MC_TOTALS + 2]
        total_c = meta_ref[:, _MC_TOTALS + 2:_MC_TOTALS + 3]

        def soft_l1(s):
            # threshold_l1: in interpret mode the barrier is required for
            # bit-identity (it stops XLA reassociating the sign/abs/divide
            # chain, exactly as in the XLA scan); Mosaic has no lowering for
            # optimization_barrier, so the hardware kernel runs the plain
            # arithmetic and owns its own instruction schedule.
            t = jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)
            return jax.lax.optimization_barrier(t) if barrier else t

        def out_of(sg, sh):
            out = -soft_l1(sg) / jnp.maximum(sh + l2, K_EPSILON)
            return jnp.where(max_delta > 0,
                             jnp.clip(out, -max_delta, max_delta), out)

        def gain_given(sg, sh, out):
            gg = soft_l1(sg)
            return -(2.0 * gg * out + (sh + l2) * out * out)

        def gain_of(sg, sh):
            return gain_given(sg, sh, out_of(sg, sh))

        tpos = jax.lax.broadcasted_iota(jnp.int32, (feat_tile, n_bins), 1)
        slot = tpos == mpos  # the missing bin's scan slot
        at_missing = slot & has_missing

        def extract(x):  # exact-value gather of the missing bin (keeps -0.0)
            return jnp.max(jnp.where(slot, x, neg_inf), axis=1,
                           keepdims=True)

        miss_g = jnp.where(has_missing, extract(g), 0.0)
        miss_h = jnp.where(has_missing, extract(h), 0.0)
        miss_c = jnp.where(has_missing, extract(c), 0.0)

        cum_g = jnp.cumsum(jnp.where(at_missing, 0.0, g), axis=1)
        cum_h = jnp.cumsum(jnp.where(at_missing, 0.0, h), axis=1)
        cum_c = jnp.cumsum(jnp.where(at_missing, 0.0, c), axis=1)

        # lane 0: missing goes right (natural); lane 1: missing goes left
        lg0, lh0, lc0 = cum_g, cum_h, cum_c
        lg1, lh1, lc1 = cum_g + miss_g, cum_h + miss_h, cum_c + miss_c

        def lane(lg, lh, lc, lane1):
            rg, rh, rc = total_g - lg, total_h - lh, total_c - lc
            ok = (lc >= min_data) & (rc >= min_data) & \
                 (lh >= min_hess) & (rh >= min_hess)
            ok &= tpos < (nbins - 1)
            ok &= valid
            ok &= gate
            if lane1:
                ok &= has_missing
            gain = gain_of(lg, lh) + gain_of(rg, rh)
            return jnp.where(ok, gain, neg_inf), rg, rh, rc

        gain0, rg0, rh0, rc0 = lane(lg0, lh0, lc0, False)
        gain1, rg1, rh1, rc1 = lane(lg1, lh1, lc1, True)

        gain_shift = gain_of(total_g, total_h) + min_gain

        per_f = jnp.concatenate([gain0, gain1], axis=1)  # [FT, 2B] lane-major
        bf = jnp.argmax(per_f, axis=1, keepdims=True).astype(jnp.int32)
        lane_b = bf // n_bins
        t_b = bf - lane_b * n_bins
        best_gain = jnp.max(per_f, axis=1, keepdims=True)

        sel = tpos == t_b  # the winning threshold's bin column

        def pick(a0, a1):  # exact-value stat extraction at (lane_b, t_b)
            v0 = jnp.max(jnp.where(sel, a0, neg_inf), axis=1, keepdims=True)
            v1 = jnp.max(jnp.where(sel, a1, neg_inf), axis=1, keepdims=True)
            return jnp.where(lane_b == 0, v0, v1)

        lg = pick(lg0, lg1)
        lh = pick(lh0, lh1)
        lc = pick(lc0, lc1)
        rg = pick(rg0, rg1)
        rh = pick(rh0, rh1)
        rc = pick(rc0, rc1)

        is_valid = jnp.isfinite(best_gain) & (best_gain > gain_shift)
        out_gain = jnp.where(is_valid, best_gain - gain_shift, neg_inf)
        out_gain = jnp.where(is_valid, out_gain - penalty, neg_inf)
        lout = out_of(lg, lh)
        rout = out_of(rg, rh)
        rows = (pl.program_id(0) * feat_tile
                + jax.lax.broadcasted_iota(jnp.int32, (feat_tile, 1), 0))
        feat = jnp.where(is_valid, rows.astype(jnp.float32), -1.0)
        zero = jnp.zeros_like(out_gain)
        rec = jnp.concatenate(
            [out_gain, feat, t_b.astype(jnp.float32),
             lane_b.astype(jnp.float32), lg, lh, lc, rg, rh, rc,
             lout, rout, zero, zero], axis=1)  # [FT, N_REC]
        out_ref[...] = jnp.concatenate(
            [rec, jnp.zeros((feat_tile, REC_PAD - N_REC), jnp.float32)],
            axis=1)

    return fused_scan_kernel


@partial(jax.jit, static_argnames=("interpret",))
def fused_split_scan(hist3: jax.Array, meta_cols: jax.Array,
                     valid: jax.Array, interpret: bool = False) -> jax.Array:
    """[3, F_pad, B] channel-major feature hists + [F_pad, REC_PAD] packed
    per-feature meta columns + [F_pad, B] valid-slot mask -> [F_pad, REC_PAD]
    split records (cols N_REC+ zero). F_pad must be a multiple of
    SCAN_TILE_FEATURES; the bin axis is never split (see module docstring)."""
    _, f_pad, n_bins = hist3.shape
    grid = (f_pad // SCAN_TILE_FEATURES,)
    return pl.pallas_call(
        _make_scan_kernel(n_bins, SCAN_TILE_FEATURES, barrier=interpret),
        grid=grid,
        in_specs=[
            pl.BlockSpec((3, SCAN_TILE_FEATURES, n_bins),
                         lambda i: (0, i, 0)),
            pl.BlockSpec((SCAN_TILE_FEATURES, REC_PAD), lambda i: (i, 0)),
            pl.BlockSpec((SCAN_TILE_FEATURES, n_bins), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((SCAN_TILE_FEATURES, REC_PAD),
                               lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((f_pad, REC_PAD), jnp.float32),
        interpret=interpret,
        name="fused_split_scan",
    )(hist3, meta_cols, valid)


def per_feature_best_fused(fh, totals, meta, params, feature_mask=None,
                           penalty=None, interpret=False):
    """Drop-in for ops.split.per_feature_best (numeric lanes, no monotone
    constraint): [F, Bmax, 3] feature hists -> [F, N_REC] records. Builds the
    kernel operands (channel-major hist, packed meta columns) and slices the
    padded record block back to the caller's shape."""
    F, _, _ = fh.shape
    f_pad = -(-F // SCAN_TILE_FEATURES) * SCAN_TILE_FEATURES
    missing_pos = jnp.where(meta.missing_type == MISSING_NAN,
                            meta.nbins - 1, meta.default_bin)
    has_missing = meta.missing_type != MISSING_NONE
    gate = ~meta.is_categorical
    if feature_mask is not None:
        gate = gate & feature_mask
    pen = penalty if penalty is not None \
        else jnp.zeros((F,), jnp.float32)
    cols = [missing_pos.astype(jnp.float32),
            has_missing.astype(jnp.float32),
            meta.nbins.astype(jnp.float32),
            gate.astype(jnp.float32),
            pen.astype(jnp.float32)]
    cols += [jnp.broadcast_to(params[i].astype(jnp.float32), (F,))
             for i in range(6)]
    cols += [jnp.broadcast_to(totals[i].astype(jnp.float32), (F,))
             for i in range(3)]
    meta_cols = jnp.stack(cols, axis=1)  # [F, _MC_COLS]
    meta_cols = jnp.pad(meta_cols,
                        ((0, f_pad - F), (0, REC_PAD - _MC_COLS)))
    hist3 = jnp.pad(jnp.moveaxis(fh, -1, 0), ((0, 0), (0, f_pad - F), (0, 0)))
    valid = jnp.pad(meta.valid_slot.astype(jnp.float32),
                    ((0, f_pad - F), (0, 0)))
    rec = fused_split_scan(hist3, meta_cols, valid, interpret=interpret)
    return rec[:F, :N_REC]
