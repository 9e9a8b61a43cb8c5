"""A run's per-row training state kept in the row-sharded learner's layout.

The sharded device learner grows a tree from `[n_pad]` per-row arrays split
over the mesh (parallel/learners.py `RowLayout`). A driver that keeps the
score on one chip pays, every tree, for moving the gradients onto the mesh
and the leaf ids back, while the other chips wait. `ResidentRows` holds the
score and the objective's per-row constants in that layout instead, placed
once at set-up: the gradients, the pack and the score update then read and
write each chip's own rows, the tree takes its rows in place
(`train_rows_async`), and nothing per-row crosses a chip boundary or the
host between trees. The arithmetic of every row is what it is outside the
layout; pad rows (weight and label 0) never reach a histogram, because the
pack zeroes all three of their channels, nor the score, because their leaf
id is -1.

models/gbdt.py decides once, at set-up, whether a run takes this path, and
says there what it asks of the learner, the objective and the configuration.
"""
from __future__ import annotations

import copy
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..objectives import ObjectiveFunction
from ..parallel.dist import put_global
from ..parallel.learners import RowLayout
from ..utils.timer import SCOPE_FINISH, SCOPE_GRADIENTS, SCOPE_TREE_SETUP


class RowPrograms(NamedTuple):
    """The jitted per-row programs of one layout and objective, each over
    operands in the layout and each a chip's own rows, so none holds a
    collective (tests/test_sharded_device.py; tests/test_chip_compile.py on
    the described v5e 2x2). The score update is the driver's own program
    (models/gbdt.py), handed operands in the layout."""

    gradients: Callable  # (score [n_pad], constants) -> (grad, hess) [n_pad]
    pack: Callable       # (grad, hess) -> [n_pad, 3], pad rows all zero
    cut: Callable        # score [n_pad] -> [1, N] on every chip: the one
    #                      all_gather a reader outside the iteration asks for


def row_programs(layout: RowLayout,
                 objective: ObjectiveFunction) -> RowPrograms:
    mesh, spec = layout.rows.mesh, layout.rows.spec
    n, n_pad = layout.num_data, layout.n_pad

    def gradients(score, constants):
        # the objective's own expressions over the placed constants: a
        # shallow copy whose per-row attributes are this trace's operands
        placed = copy.copy(objective)
        placed.__dict__.update(constants)
        with jax.named_scope(SCOPE_GRADIENTS):
            return placed.get_gradients(score)

    def pack(grad, hess):
        with jax.named_scope(SCOPE_TREE_SETUP):
            gh = jnp.stack([grad, hess, jnp.ones_like(grad)], axis=1)
            real = jnp.arange(n_pad, dtype=jnp.int32) < n
            return jnp.where(real[:, None], gh, 0.0)

    def gather(rows):
        return jax.lax.all_gather(rows, "data", tiled=True)

    # an explicit all_gather: the collective the compiler would make of a
    # replicated slice is an all-reduce with no name stack for a scope to
    # be in, and twice the bytes over ICI
    whole = shard_map(gather, mesh=mesh, in_specs=P("data"), out_specs=P(),
                      check_vma=False)

    def cut(score):
        with jax.named_scope(SCOPE_FINISH):
            return whole(score)[None, :n]

    return RowPrograms(
        jax.jit(gradients),
        jax.jit(pack, out_shardings=NamedSharding(mesh, P(*spec, None))),
        jax.jit(cut, out_shardings=NamedSharding(mesh, P())))


class ResidentRows:
    """`score` is the training score `[n_pad]` float32 in the layout (one
    tree an iteration: the driver asks for nothing wider); the driver
    replaces it with what its update programs return."""

    def __init__(self, layout: RowLayout, objective: ObjectiveFunction,
                 score: jax.Array) -> None:
        self.layout = layout
        self.programs = row_programs(layout, objective)
        self.score = self.place(score)
        self._view: Optional[Tuple[jax.Array, jax.Array]] = None
        self._constants = {name: self.place(getattr(objective, name))
                           for name in objective.row_constants
                           if getattr(objective, name) is not None}

    def place(self, rows) -> jax.Array:
        """`[N]` rows (host or device) as a committed `[n_pad]` array in
        the layout, the pad zero. A host round trip: set-up and restores
        only, never inside an iteration."""
        lay = self.layout
        host = np.asarray(rows).reshape(lay.num_data)
        return put_global(np.pad(host, (0, lay.n_pad - lay.num_data)),
                          lay.rows.mesh, lay.rows.spec)

    def gradients(self) -> Tuple[jax.Array, jax.Array]:
        """(grad, hess) `[n_pad]` of the score as it stands, each chip its
        own rows; what they read on the pad rows the pack throws away."""
        return self.programs.gradients(self.score, self._constants)

    def view(self) -> jax.Array:
        """The score as every reader outside the iteration knows it:
        `[1, N]`, cut on demand on the mesh (replicated; one program), and
        kept until the score is replaced."""
        if self._view is None or self._view[0] is not self.score:
            self._view = (self.score, self.programs.cut(self.score))
        return self._view[1]
