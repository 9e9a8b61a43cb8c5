"""Pallas TPU row-tile kernel for ensemble inference.

The fused XLA traversal (ops/predict.py:_predict_raw_fused) already does
one X-gather per level, but XLA stages each level's [N, T] gather results
through HBM. This kernel pins a row tile of X plus the whole packed node
table in VMEM and runs ALL max_depth levels and the leaf-value gather for
that tile before touching HBM again — HBM traffic becomes the irreducible
read of X and node tables plus the [N, C] output write.

    grid (N / tile_rows,); per step:
        node[tile, T] level loop (forest_level_step, shared verbatim with
        the XLA path — bit-identical decisions by construction)
        out[tile, C] = leaf_value gather, per-class sum

Tables replicate into every grid step via constant index maps; the node
table for serving-size ensembles (T*I ints) is a few MB — comfortably
VMEM-resident next to a 512-row X tile. Linear-tree ensembles keep the
XLA path (the [N, T, K] coefficient gather does not tile this way).

Enabled by LGBM_TPU_PREDICT_PALLAS=1 (ops/predict.py:predict_raw), an
explicit opt-in; correctness pinned by interpret-mode tests
(LGBM_TPU_PALLAS_INTERPRET=1) against the XLA path. The v5e compiler
refuses the body today ("Only 2D gather is supported": the node-table
gathers), so opting in on a TPU raises that error — it does not fall back
and is never interpreted unasked (tests/test_chip_compile.py holds it to
that).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import telemetry
from .predict import PackedEnsemble, forest_level_step

# kernel-compile classification for the recompile watcher's split counter
telemetry.register_kernel_fn("pallas_predict_raw")

PREDICT_TILE_ROWS = 512


def _make_kernel(num_tree_per_iteration: int, max_depth: int):
    def kernel(sf_ref, th_ref, dt_ref, lc_ref, rc_ref, co_ref, cn_ref,
               cw_ref, nl_ref, lv_ref, x_ref, out_ref):
        X = x_ref[...]
        sf = sf_ref[...]
        th = th_ref[...]
        dt = dt_ref[...]
        lc = lc_ref[...]
        rc = rc_ref[...]
        co = co_ref[...]
        cn = cn_ref[...]
        cw = cw_ref[...]
        nl = nl_ref[...]
        lv = lv_ref[...]
        rows = X.shape[0]
        T, L = lv.shape
        node0 = jnp.zeros((rows, T), dtype=jnp.int32)

        def body(_, node):
            return forest_level_step(X, node, sf, th, dt, lc, rc, co, cn, cw)

        node = jax.lax.fori_loop(0, max_depth, body, node0)
        leaf = jnp.where(nl[None, :] <= 1, 0, ~node)
        flat = jnp.arange(T, dtype=jnp.int32)[None, :] * L + leaf
        vals = lv.reshape(-1)[flat]
        out_ref[...] = vals.reshape(
            rows, T // num_tree_per_iteration, num_tree_per_iteration
        ).sum(axis=1)

    return kernel


def _replicated_spec(shape):
    """Full-array block replicated into every grid step."""
    return pl.BlockSpec(shape, lambda t: (0,) * len(shape))


@partial(jax.jit, static_argnames=("num_tree_per_iteration", "tile_rows",
                                   "interpret"))
def pallas_predict_raw(packed: PackedEnsemble, X: jax.Array,
                       num_tree_per_iteration: int,
                       tile_rows: int = PREDICT_TILE_ROWS,
                       interpret: bool = False) -> jax.Array:
    """Raw scores [N, num_tree_per_iteration] via the row-tile kernel."""
    n, F = X.shape
    C = num_tree_per_iteration
    n_tiles = max(-(-n // tile_rows), 1)
    n_pad = n_tiles * tile_rows
    if n_pad > n:
        X = jnp.concatenate(
            [X, jnp.zeros((n_pad - n, F), dtype=X.dtype)], axis=0)
    kernel = _make_kernel(C, packed.max_depth)
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            _replicated_spec(packed.split_feature.shape),
            _replicated_spec(packed.threshold.shape),
            _replicated_spec(packed.decision_type.shape),
            _replicated_spec(packed.left_child.shape),
            _replicated_spec(packed.right_child.shape),
            _replicated_spec(packed.cat_offset.shape),
            _replicated_spec(packed.cat_n_words.shape),
            _replicated_spec(packed.cat_words.shape),
            _replicated_spec(packed.num_leaves.shape),
            _replicated_spec(packed.leaf_value.shape),
            pl.BlockSpec((tile_rows, F), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, C), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, C), packed.leaf_value.dtype),
        interpret=interpret,
        name="pallas_predict_raw",
    )(packed.split_feature, packed.threshold, packed.decision_type,
      packed.left_child, packed.right_child, packed.cat_offset,
      packed.cat_n_words, packed.cat_words, packed.num_leaves,
      packed.leaf_value, X)
    return out[:n]
