"""Histogram construction as batched one-hot MXU contractions.

TPU-native replacement for the reference's histogram kernels:
  * CPU: DenseBin::ConstructHistogram gather-add loops (src/io/dense_bin.hpp)
  * CUDA: CUDAHistogramConstructor shared-memory scatter kernels
    (src/treelearner/cuda/cuda_histogram_constructor.cu:20-513)

TPUs have no fast arbitrary scatter; the idiomatic formulation is a one-hot
contraction that runs on the MXU: for each feature group g,

    hist[g, b, c] = sum_p [bins[g, p] == b] * gh[p, c]

i.e. an einsum('gpb,pc->gbc') where the one-hot tensor is generated on the
fly from an iota comparison. XLA tiles this onto the systolic array; rows are
processed in chunks via lax.scan so the transient one-hot stays small (VMEM-
friendly) and the accumulator lives in f32.

Leaf-restricted histograms use gather-by-index: the trainer keeps per-leaf
padded row-index arrays (ops/partition.py); `gh` is stored with a zero
sentinel row at index N so padded indices contribute nothing.

The channel layout is [grad, hess, count].
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .. import perfmodel, telemetry
from ..utils.backend import on_tpu

DEFAULT_ROW_CHUNK = 16384


def _acc_dtype(compute_dtype):
    """Accumulator dtype: int32 for integer (quantized-gradient) histograms
    — exact, and int8 x int8 -> int32 contractions are MXU-native — f32
    otherwise."""
    return (jnp.int32 if jnp.issubdtype(jnp.dtype(compute_dtype), jnp.integer)
            else jnp.float32)


def _hist_chunk(bins_c: jax.Array, gh_c: jax.Array, num_bins: int,
                compute_dtype) -> jax.Array:
    """One chunk: bins_c [G, C] int32, gh_c [C, 3] -> [G, num_bins, 3]."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, num_bins), 2)
    onehot = (bins_c[:, :, None] == iota).astype(compute_dtype)  # [G, C, B]
    return jax.lax.dot_general(
        onehot, gh_c.astype(compute_dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=_acc_dtype(compute_dtype),
    )  # [G, B, 3]


def build_histogram(bins: jax.Array, gh: jax.Array, num_bins: int,
                    row_chunk: int = DEFAULT_ROW_CHUNK,
                    compute_dtype=jnp.float32,
                    use_pallas: bool = None) -> jax.Array:
    """Full-data histogram.

    bins: [G, N] integer bin matrix (any int dtype)
    gh:   [N, 3] float (grad, hess, 1.0)
    Returns [G, num_bins, 3] float32.

    Unjitted dispatch wrapper: the backend choice (the Pallas kernel on a
    TPU, whose sequential grid its revisited-output accumulation relies on;
    the XLA contraction elsewhere) resolves per call, then routes to a
    jitted implementation. Inside an outer jit the choice is baked at that
    trace's creation, as any Python-level branch must be.
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        from .hist_pallas import hist_force_f32, pallas_histogram

        # native dtype pass-through: 8-bit planes stay narrow on the wire
        # (the kernel wrapper widens only when its VMEM policy demands it)
        return pallas_histogram(
            bins, gh, num_bins,
            quantized=jnp.issubdtype(jnp.dtype(compute_dtype), jnp.integer),
            f32=hist_force_f32())
    return _build_histogram_xla(bins, gh, num_bins, row_chunk, compute_dtype)


@partial(jax.jit, static_argnames=("num_bins", "row_chunk", "compute_dtype"))
def _build_histogram_xla(bins: jax.Array, gh: jax.Array, num_bins: int,
                         row_chunk: int = DEFAULT_ROW_CHUNK,
                         compute_dtype=jnp.float32) -> jax.Array:
    G, N = bins.shape
    bins = bins.astype(jnp.int32)
    if N <= row_chunk:
        return _hist_chunk(bins, gh, num_bins, compute_dtype)
    n_chunks = (N + row_chunk - 1) // row_chunk
    pad = n_chunks * row_chunk - N
    if pad:
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        gh = jnp.pad(gh, ((0, pad), (0, 0)))  # zero gh => no contribution
    bins_s = bins.reshape(G, n_chunks, row_chunk).transpose(1, 0, 2)
    gh_s = gh.reshape(n_chunks, row_chunk, gh.shape[1])

    def step(acc, xs):
        b_c, g_c = xs
        return acc + _hist_chunk(b_c, g_c, num_bins, compute_dtype), None

    init = jnp.zeros((G, num_bins, gh.shape[1]), dtype=_acc_dtype(compute_dtype))
    hist, _ = jax.lax.scan(step, init, (bins_s, gh_s))
    return hist


def build_histogram_rows(bins: jax.Array, gh_ext: jax.Array, row_idx: jax.Array,
                         num_bins: int, row_chunk: int = DEFAULT_ROW_CHUNK,
                         compute_dtype=jnp.float32,
                         use_pallas: bool = None) -> jax.Array:
    """Leaf histogram over a padded row-index set (unjitted dispatch wrapper
    like build_histogram).

    bins:    [G, N] full bin matrix
    gh_ext:  [N+1, 3] gradients with a ZERO sentinel row at index N
    row_idx: [P] row indices, padded with N (the sentinel)
    Returns [G, num_bins, 3] float32.

    Padded entries gather gh == 0 so they contribute nothing; their bins
    gather is clamped (any bin works since the weight is zero).
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        from .hist_pallas import hist_force_f32, pallas_histogram

        G, N = bins.shape
        bins_leaf = jnp.take(bins, jnp.minimum(row_idx, N - 1), axis=1)
        gh_leaf = jnp.take(gh_ext, row_idx, axis=0)
        quantized = jnp.issubdtype(jnp.dtype(compute_dtype), jnp.integer)
        f32 = hist_force_f32()
        if telemetry.enabled():
            # one-time capture for perfmodel's AOT cost_analysis; the dict
            # check keeps the per-leaf hot path O(1) afterwards
            perfmodel.note_dispatch("histogram", pallas_histogram,
                                    bins_leaf, gh_leaf, num_bins,
                                    quantized=quantized, f32=f32)
        return pallas_histogram(bins_leaf, gh_leaf, num_bins,
                                quantized=quantized, f32=f32)
    if telemetry.enabled():
        perfmodel.note_dispatch("histogram", _build_histogram_rows_xla,
                                bins, gh_ext, row_idx, num_bins,
                                row_chunk, compute_dtype)
    return _build_histogram_rows_xla(bins, gh_ext, row_idx, num_bins,
                                     row_chunk, compute_dtype)


@partial(jax.jit, static_argnames=("num_bins", "row_chunk", "compute_dtype"))
def _build_histogram_rows_xla(bins: jax.Array, gh_ext: jax.Array,
                              row_idx: jax.Array, num_bins: int,
                              row_chunk: int = DEFAULT_ROW_CHUNK,
                              compute_dtype=jnp.float32) -> jax.Array:
    G, N = bins.shape
    bins_leaf = jnp.take(bins, jnp.minimum(row_idx, N - 1), axis=1).astype(jnp.int32)
    gh_leaf = jnp.take(gh_ext, row_idx, axis=0)  # idx==N hits the zero row
    P = row_idx.shape[0]
    if P <= row_chunk:
        return _hist_chunk(bins_leaf, gh_leaf, num_bins, compute_dtype)
    n_chunks = (P + row_chunk - 1) // row_chunk
    pad = n_chunks * row_chunk - P
    if pad:
        bins_leaf = jnp.pad(bins_leaf, ((0, 0), (0, pad)))
        gh_leaf = jnp.pad(gh_leaf, ((0, pad), (0, 0)))
    bins_s = bins_leaf.reshape(G, n_chunks, row_chunk).transpose(1, 0, 2)
    gh_s = gh_leaf.reshape(n_chunks, row_chunk, gh_leaf.shape[1])

    def step(acc, xs):
        b_c, g_c = xs
        return acc + _hist_chunk(b_c, g_c, num_bins, compute_dtype), None

    init = jnp.zeros((G, num_bins, gh_leaf.shape[1]),
                     dtype=_acc_dtype(compute_dtype))
    hist, _ = jax.lax.scan(step, init, (bins_s, gh_s))
    return hist


@jax.jit
def subtract_histogram(parent: jax.Array, sibling: jax.Array) -> jax.Array:
    """The histogram-subtraction trick (FeatureHistogram::Subtract,
    src/treelearner/feature_histogram.hpp:99; CUDA SubtractHistogramForLeaf):
    larger child = parent - smaller child, skipping a full construction pass.
    """
    return parent - sibling
