"""Row-sharded multi-chip inference over the ``data`` mesh axis.

Prediction is embarrassingly parallel over rows, so the Meng et al.
communication model that PR 4 applied to training degenerates to its best
case for serving: the packed ensemble (O(T*I) node words) replicates onto
every device ONCE per PredictorCache entry, X scatters as [N/n_dev, F]
row shards, every device traverses its shard with zero cross-device
traffic, and the only collective output is the [N/n_dev, C] per-shard
score gather — per-row ICI is O(C) out, 0 in. Contrast training
(PERF_NOTES Round-6), which pays a K*F_pad*Bmax*CH histogram scatter per
wave; serving pays nothing per tree.

Gated by LGBM_TPU_PREDICT_SHARD (1/0 force on/off); by default engages
only for batches large enough that per-device dispatch overhead amortizes.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from ..ops.predict import PackedEnsemble, fused_program, validate_tree_count
from ..utils.timer import global_timer
from .dist import put_global, put_replicated
from .mesh import data_mesh, padded_row_count

_SHARD_ENV = "LGBM_TPU_PREDICT_SHARD"
_AUTO_MIN_ROWS = 1 << 16  # below this, single-chip dispatch is cheaper

_fn_cache: dict = {}


def sharded_predict_enabled(n_rows: int,
                            min_rows: Optional[int] = None) -> bool:
    """Row-sharding policy: env force-off/on, else auto for large batches
    on multi-device platforms. `min_rows` (the pred_shard_rows param —
    the serving fleet sets it per model entry) replaces the auto
    threshold: batches at or above it shard, smaller ones stay
    single-chip."""
    env = os.environ.get(_SHARD_ENV, "").lower()
    if env in ("0", "false", "off"):
        return False
    if jax.device_count() <= 1:
        return False
    if env in ("1", "true", "on"):
        return True
    return n_rows >= (_AUTO_MIN_ROWS if min_rows is None else max(1, min_rows))


def _sharded_predict_fn(mesh: jax.sharding.Mesh, num_tree_per_iteration: int):
    """jit(shard_map) closure per (device set, C): packed replicates,
    X and the output shard over ``data``."""
    key = (tuple(int(d.id) for d in mesh.devices.flat), num_tree_per_iteration)
    fn = _fn_cache.get(key)
    if fn is not None:
        return fn
    P = jax.sharding.PartitionSpec

    def body(packed, x):
        return fused_program(packed)(packed, x, num_tree_per_iteration)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P("data")),
                           out_specs=P("data"), check_vma=False))
    _fn_cache[key] = fn
    return fn


def predict_raw_sharded(packed: PackedEnsemble, X: np.ndarray,
                        num_tree_per_iteration: int,
                        mesh: Optional[jax.sharding.Mesh] = None) -> np.ndarray:
    """Raw scores [N, C] with rows sharded across the mesh."""
    validate_tree_count(packed, num_tree_per_iteration)
    if mesh is None:
        mesh = data_mesh()
    n_dev = mesh.devices.size
    n = X.shape[0]
    with global_timer.scope("predict_shard"):
        n_pad = padded_row_count(n, n_dev)
        if n_pad > n:
            X = np.concatenate(
                [X, np.zeros((n_pad - n, X.shape[1]), dtype=X.dtype)])
        P = jax.sharding.PartitionSpec
        x_dev = put_global(X, mesh, P("data"))
        packed_rep = put_replicated(packed, mesh)
        out = _sharded_predict_fn(mesh, num_tree_per_iteration)(
            packed_rep, x_dev)
        global_timer.add_count("predict_sharded_rows", n)
        return np.asarray(out)[:n]
