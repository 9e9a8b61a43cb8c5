"""Gradient discretization for quantized-histogram training.

Counterpart of GradientDiscretizer
(src/treelearner/gradient_discretizer.{hpp,cpp}): gradients/hessians are
linearly quantized to small signed integers,

    grad_scale = max|g| / (num_grad_quant_bins / 2)
    hess_scale = max|h| / num_grad_quant_bins      (max|h| if constant hess)
    g_int = trunc(g / grad_scale +- r)   (r ~ U[0,1) stochastic rounding,
                                          0.5 for nearest rounding)

and histograms accumulate the integers exactly in int32. On the device
learner the ragged wave kernel (ops/hist_pallas.py, `hist_operand` "int")
carries them as ONE bfloat16 limb (integers up to 255 are exact in
bfloat16) against the bfloat16 one-hot of the bin row, one MXU pass a group
with float32 partial sums per tile of 1,024 rows (exact: below 2**24) and
int32 accumulation across tiles; the host-driven learners' dense kernel and
the XLA body contract int8 against an int8 one-hot into int32. The split
scan rescales integer sums back to float (`hist.astype(float32) * scales`).

`quantize_pack` is the per-tree step as ONE jitted program under the device
scope `lgbm.quantize`: the key's split, the discretizer, the scales and the
[N+1, 3] int8 pack (g_int, h_int, 1; a zero sentinel row) that every
learner's histograms take.

TPU-first notes vs the reference: the int8/int16/int32 per-leaf histogram
bit-width machinery (gradient_discretizer.hpp:60-90, bin.h:63-81) exists to
save CPU cache; the TPU formulation always accumulates int32 (exact, no
overflow for any leaf below 2^23 rows per bin at 4-bit quantization) and
instead narrows the DISTRIBUTED reduction to int16 when the per-device shard
provably fits (parallel/learners.py), halving psum_scatter bytes — the
analog of the reference's int16 histogram reduction
(data_parallel_tree_learner.cpp:285-297). Row counts come from the pack's
third channel, exactly, where the reference estimates them from the hessian
sum.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..utils.timer import SCOPE_QUANTIZE


def int16_reduction_safe(row_count: int, num_grad_quant_bins: int) -> bool:
    """True when a quantized histogram bin over `row_count` rows provably
    fits int16, so the cross-device reduction can ship int16 instead of
    int32 (the reference's int16 histogram reduction,
    data_parallel_tree_learner.cpp:285-297). Conservative: assumes every
    row lands in one bin at the max quantized magnitude, with headroom
    under 2^15."""
    return row_count * num_grad_quant_bins < 32000


@partial(jax.jit, static_argnames=("num_bins", "stochastic"))
def discretize_gradients(grad: jax.Array, hess: jax.Array, key: jax.Array,
                         num_bins: int = 4, stochastic: bool = True
                         ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """[N] float grad/hess -> ([N] int8 g_int, [N] int8 h_int, g_scale, h_scale).

    GradientDiscretizer::DiscretizeGradients (gradient_discretizer.cpp:70-160).
    The hessian is quantized over [0, num_bins]; a constant-hessian objective
    (max == min) degenerates to h_int == 1 with hess_scale = max|h|, matching
    the reference's is_constant_hessian branch.
    """
    eps = jnp.float32(1e-35)
    max_g = jnp.maximum(jnp.max(jnp.abs(grad)), eps)
    max_h = jnp.maximum(jnp.max(jnp.abs(hess)), eps)
    min_h = jnp.min(hess)
    const_hess = (max_h - min_h) <= 1e-12 * max_h
    g_scale = max_g / (num_bins // 2)
    h_scale = jnp.where(const_hess, max_h, max_h / num_bins)
    inv_g = 1.0 / g_scale
    inv_h = 1.0 / h_scale
    if stochastic:
        kg, kh = jax.random.split(key)
        rg = jax.random.uniform(kg, grad.shape, dtype=jnp.float32)
        rh = jax.random.uniform(kh, hess.shape, dtype=jnp.float32)
    else:
        rg = rh = jnp.float32(0.5)
    g_int = jnp.trunc(
        jnp.where(grad >= 0, grad * inv_g + rg, grad * inv_g - rg)
    ).astype(jnp.int8)
    h_int = jnp.where(const_hess, jnp.int8(1),
                      jnp.trunc(hess * inv_h + rh).astype(jnp.int8))
    return g_int, h_int, g_scale, h_scale


@partial(jax.jit, static_argnames=("num_bins", "stochastic"))
def quantize_pack(gh_ext: jax.Array, key: jax.Array, num_bins: int = 4,
                  stochastic: bool = True
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One tree's quantization in one dispatch: [N+1, 3] float gradient pack
    (zero sentinel row last) + the learner's PRNG key -> (the next key, the
    [N+1, 3] int8 pack (g_int, h_int, 1) with its zero sentinel, the
    float32 scales [grad_scale, hess_scale, 1])."""
    with jax.named_scope(SCOPE_QUANTIZE):
        next_key, sub = jax.random.split(key)
        g_int, h_int, gs, hs = discretize_gradients(
            gh_ext[:-1, 0], gh_ext[:-1, 1], sub, num_bins, stochastic)
        scale_vec = jnp.stack([gs, hs, jnp.float32(1.0)])
        ghq = jnp.stack([g_int, h_int, jnp.ones_like(g_int)], axis=1)
        ghq_ext = jnp.concatenate([ghq, jnp.zeros((1, 3), jnp.int8)], axis=0)
    return next_key, ghq_ext, scale_vec
