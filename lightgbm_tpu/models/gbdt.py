"""GBDT boosting driver.

Counterpart of GBDT (src/boosting/gbdt.cpp): gradient boosting loop with
boost-from-average, per-class tree training, leaf-value renewal, shrinkage,
train/valid score maintenance, eval, and model export. The TrainOneIter
control flow mirrors gbdt.cpp:352-460 (init-score handling, constant trees,
should_continue semantics); score updates are device scatter-adds over the
partition's per-leaf index sets (the CUDAScoreUpdater analog).
"""
from __future__ import annotations

import os
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry, tracing
from ..config import Config
from ..health import create_monitor
from ..io.dataset import Dataset
from ..metrics import create_metric
from ..objectives import ObjectiveFunction
from ..ops.partition import bucket_size, pad_indices
from ..ops.predict import (PredictorCache, pack_ensemble, predict_dtype,
                           predict_raw, predict_raw_streamed,
                           stream_chunk_rows)
from ..ops.score import (add_tree_to_score, cat_table_words,
                         feature_split_fields, replay_tree_to_score,
                         split_table)
from ..parallel import elastic
from ..treelearner import create_tree_learner
from ..utils import faults, sanitize
from ..utils.log import Log
from ..utils.timer import (SCOPE_GRADIENTS, SCOPE_TREE_SETUP,
                           SCOPE_UPDATE_SCORE, SCOPE_VALID_SCORE,
                           SPAN_EVAL_VALID, SPAN_GRADIENTS,
                           SPAN_PREDICT_CALL, SPAN_PREDICT_FETCH,
                           SPAN_PREDICT_TRAVERSE, SPAN_PREDICT_UPLOAD,
                           global_timer)
from .resident import ResidentRows
from .sample_strategy import DeviceBag, create_sample_strategy
from .serialize import GBDTModel
from .tree import Tree

K_EPSILON = 1e-15
# the fewest bitset words of a per-tree validation pack (_valid_cat_words)
VALID_CAT_WORDS = 4096


# graftlint: disable=R6 -- the gradients outlive the call (linear-tree fitting and the health monitor read them after the tree), and no [N] input matches the [N+1, 3] output
@jax.jit
def _pack_gh(grad: jax.Array, hess: jax.Array) -> jax.Array:
    """[N] grad/hess -> [N+1, 3] with count channel and zero sentinel row:
    one program a tree, part of the tree's set-up."""
    with jax.named_scope(SCOPE_TREE_SETUP):
        gh = jnp.stack([grad, hess, jnp.ones_like(grad)], axis=1)
        return jnp.concatenate([gh, jnp.zeros((1, 3), gh.dtype)], axis=0)


# score is donated: the caller replaces it with the returned array, so XLA
# updates the [N] vector in place instead of double buffering it.
@partial(jax.jit, static_argnames=("num_leaves",), donate_argnums=(0,))
def _apply_split_log_to_score(score: jax.Array, rec_store: jax.Array,
                              leaf_ids: jax.Array, rate: jax.Array,
                              num_leaves: int) -> jax.Array:
    """Tree-t score update straight from the DEVICE split log — the async
    pipeline's replacement for the host-side leaf-value gather, applied
    before the log ever reaches the host.

    rec_store rows are [leaf, parent_output, depth, valid] + SPLIT_FIELDS;
    valid row t re-splits leaf `rec[0]` (left child keeps the id, right
    child becomes leaf t+1), so replaying left_output/right_output (store
    cols 14/15) into a leaf-value table reproduces tree.leaf_value exactly.
    Rows past the first invalid row are all-zero (valid == 0) and write to
    the dump slot. The f32 multiply by `rate` is bit-identical to the host
    path's f64 shrink + f32 cast whenever rate is exactly representable in
    f32 — _async_enabled gates on that. A stub tree (no valid rows) yields
    an all-zero table: the update is exactly a no-op."""
    L = num_leaves

    def body(t, lv):
        row = rec_store[t]
        valid = row[3] > 0.5
        wb = jnp.where(valid, row[0].astype(jnp.int32), L)
        wn = jnp.where(valid, t + 1, L)
        return lv.at[wb].set(row[14]).at[wn].set(row[15])

    with jax.named_scope(SCOPE_UPDATE_SCORE):
        lv = jax.lax.fori_loop(0, rec_store.shape[0], body,
                               jnp.zeros(L + 1, jnp.float32))
        lv = lv[:L] * rate
        return score + jnp.where(
            leaf_ids >= 0, lv[jnp.clip(leaf_ids, 0, L - 1)], 0.0)


# graftlint: disable=R6 -- the leaf ids are the tree's partition and outlive the call; the score row is left undonated as the eager expression left it (donation is ROADMAP S6's change, not the naming's)
@jax.jit
def _add_leaf_values_to_score(score: jax.Array, leaf_ids: jax.Array,
                              leaf_values: jax.Array) -> jax.Array:
    """The sync path's score update: score [N] plus each row's leaf value,
    one float32 add a row; bagged-out rows carry leaf id -1 and add
    nothing. leaf_values is padded to the configuration's num_leaves, so
    one program serves every tree."""
    with jax.named_scope(SCOPE_UPDATE_SCORE):
        last = leaf_values.shape[0] - 1
        return score + jnp.where(
            leaf_ids >= 0, leaf_values[jnp.clip(leaf_ids, 0, last)], 0.0)


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _add_valid_delta(score: jax.Array, out: jax.Array,
                     class_id: int) -> jax.Array:
    """A validation set's scores [C, Nv] plus one tree's outputs [Nv, 1]
    (what `predict_raw` of the one-tree pack returned): one program, so the
    column slice and the row update carry a scope of the program's. The
    old scores are donated: the caller keeps only the sum."""
    with jax.named_scope(SCOPE_VALID_SCORE):
        return score.at[class_id].add(out[:, 0])


def _colocate(arr: jax.Array, ref: jax.Array) -> jax.Array:
    """Move `arr` onto `ref`'s device when the two live on different device
    sets. The mesh-sharded tree learner hands back outputs spanning the whole
    mesh while the score vector lives on one device; jit refuses to mix the
    two. device_put here is an async transfer — it overlaps the host replay
    just like the copy_to_host_async pulls."""
    if not (isinstance(arr, jax.Array) and isinstance(ref, jax.Array)):
        return arr
    if not arr.is_fully_addressable:
        # multi-process mesh output: this process only holds its shards, so
        # device_put cannot assemble the value — allgather the global array
        # across the gang (every rank calls this in lockstep each iteration)
        from jax.experimental import multihost_utils

        host = multihost_utils.process_allgather(arr, tiled=True)
        return jax.device_put(jnp.asarray(host),
                              next(iter(ref.sharding.device_set)))
    if arr.sharding.device_set != ref.sharding.device_set:
        return jax.device_put(arr, next(iter(ref.sharding.device_set)))
    return arr


class _ValidData:
    """Holds one validation set's device raw matrix, metadata, score and,
    where its trees are added in bin space (`GBDT._valid_plane`), its
    binned plane [G, Nv] at the plane's own width."""

    def __init__(self, dataset: Dataset, raw, metrics,
                 plane: Optional[jax.Array] = None) -> None:
        self.dataset = dataset
        self._raw_host = raw
        self._raw: Optional[jax.Array] = None
        self.plane = plane
        self.metrics = metrics
        self.score: Optional[jax.Array] = None
        if plane is None:  # every tree takes the one-tree pack
            _ = self.raw

    @property
    def raw(self) -> jax.Array:
        """The set's raw float32 matrix on the device, placed when a tree
        first needs the one-tree pack (at `add_valid` for a set without a
        plane). A sparse set is made dense here and nowhere else, so a set
        whose every tree takes the bin-space update never is."""
        if self._raw is None:
            raw = self._raw_host
            if hasattr(raw, "toarray"):
                # astype BEFORE toarray: the f32 conversion on the sparse
                # side halves the transient peak vs densify-then-cast
                raw = raw.astype(np.float32).toarray()
            self._raw = jnp.asarray(raw, dtype=jnp.float32)
            self._raw_host = None
        return self._raw


class GBDT:
    """The training driver. One instance per Booster."""

    # the per-row state in the sharded learner's row layout, where the run
    # is eligible (`_resident_rows`); None: the score is `_score`, [C, N]
    _rows: Optional[ResidentRows] = None
    # (feature_split_fields, cat_table_words) of the training set, made with
    # the first validation plane (`_valid_plane`)
    _split_fields: Optional[Tuple[np.ndarray, int]] = None

    @property
    def score(self) -> jax.Array:
        """The training scores [C, N]. A reader of scores or leaf ids
        outside an iteration takes this N-row view (cut on demand where the
        rows are resident) and never places a per-row array itself."""
        return self._score if self._rows is None else self._rows.view()

    @score.setter
    def score(self, value: jax.Array) -> None:
        if self._rows is None:
            self._score = value
        else:  # a restore, the first iteration's average: off the tree path
            self._rows.score = self._rows.place(value)

    def __init__(self, config: Config, train_set: Optional[Dataset],
                 objective: Optional[ObjectiveFunction],
                 train_raw: Optional[np.ndarray] = None) -> None:
        self.config = config
        self.train_set = train_set
        self.objective = objective
        self.iter_ = 0
        self.models: List[Tree] = []
        self.best_iteration = 0
        self.average_output = False  # RF sets True (rf.hpp)
        self.shrinkage_rate = config.learning_rate
        self.num_class = max(config.num_class, 1)
        if objective is not None:
            self.num_tree_per_iteration = objective.num_model_per_iteration
        else:
            self.num_tree_per_iteration = self.num_class if self.num_class > 1 else 1
        self.class_need_train = [True] * self.num_tree_per_iteration
        if objective is not None and hasattr(objective, "class_need_train"):
            pass  # resolved after objective.init (below)
        self._predictor = PredictorCache()
        self.valid_sets: List[_ValidData] = []
        self.valid_names: List[str] = []
        # async per-tree pipeline state (device learner only): the pending
        # handle of the last dispatched tree, finalized one iteration later
        self._pending = None
        self._async_stub_stop = False
        # numerical-health guardrails (None unless health_check_policy set)
        self._health = create_monitor(config)

        if train_set is not None:
            n = train_set.num_data
            self.num_data = n
            if objective is not None:
                objective.init(train_set.metadata, n)
                if hasattr(objective, "class_need_train"):
                    self.class_need_train = [
                        objective.class_need_train(c)
                        for c in range(self.num_tree_per_iteration)]
            self.tree_learner = create_tree_learner(
                config.tree_learner, config.device_type, config, train_set)
            self.sample_strategy = create_sample_strategy(
                config, n, train_set.metadata, self.num_tree_per_iteration)
            self._cur_bag: Optional[np.ndarray] = None
            self.train_metrics = [m for m in
                                  (create_metric(name, config) for name in config.metric)
                                  if m is not None]
            for m in self.train_metrics:
                m.init(train_set.metadata, n)
            # scores [C, N]
            self.score = jnp.zeros((self.num_tree_per_iteration, n), dtype=jnp.float32)
            init = train_set.metadata.init_score
            self._has_init_score = init is not None
            if self._has_init_score:
                self.score = jnp.asarray(
                    np.asarray(init, dtype=np.float32).reshape(
                        self.num_tree_per_iteration, n))
            if objective is None:
                self._grad_fn = None
            elif objective.jit_gradients:
                self._grad_fn = jax.jit(self._compute_gh)
            else:
                self._grad_fn = self._compute_gh
            self.train_raw = train_raw
            self._rows = self._resident_rows()

    # ------------------------------------------------------------------ valid

    def add_valid(self, valid: Dataset, raw: np.ndarray, name: str) -> None:
        metrics = [m for m in (create_metric(nm, self.config) for nm in self.config.metric)
                   if m is not None]
        for m in metrics:
            m.init(valid.metadata, valid.num_data)
        vd = _ValidData(valid, raw, metrics, self._valid_plane(valid))
        vd.score = jnp.zeros((self.num_tree_per_iteration, valid.num_data),
                             dtype=jnp.float32)
        if valid.metadata.init_score is not None:
            vd.score = jnp.asarray(np.asarray(valid.metadata.init_score, dtype=np.float32)
                                   .reshape(self.num_tree_per_iteration, valid.num_data))
        self.valid_sets.append(vd)
        self.valid_names.append(name)

    def _has_categorical(self) -> bool:
        ds = self.train_set
        return ds is not None and any(ds.mappers[f].bin_type == 1
                                      for f in ds.used_features)

    def _valid_plane(self, valid: Dataset) -> Optional[jax.Array]:
        """Decided once a set, from what the run shows: a validation set
        takes each tree in bin space (`_update_valid_scores`) when the
        training set has a categorical used feature (whose trees the dense
        predict program cannot score) or an EFB bundle (whose raw matrix
        is as wide as the table: thousands of one-hot columns, in the
        tables bundling is for), and the set was binned with the
        training set's mappers into its groups (constructed with
        `reference=`). Its plane is then placed on the device once, rows on
        the lanes. A run with neither a categorical feature nor a bundle
        keeps the one-tree pack: the dense program on the TPU, the gather
        traversal off it."""
        ds = self.train_set
        bundled = any(g.is_multi for g in ds.groups)
        if (not (self._has_categorical() or bundled) or valid.bins is None
                or valid.mappers is not ds.mappers
                or [g.feature_indices for g in valid.groups]
                != [g.feature_indices for g in ds.groups]):
            return None
        if self._split_fields is None:
            # no categorical node, no bitset words in the table
            self._split_fields = (
                feature_split_fields(ds),
                cat_table_words(ds) if self._has_categorical() else 0)
        return jnp.asarray(valid.bins, dtype=valid.bins.dtype)

    # --------------------------------------------------------------- boosting

    def _resident_rows(self) -> Optional[ResidentRows]:
        """Decided once, here, from what the run shows: the scores, the
        objective's per-row constants, the gradients and each tree's leaf
        ids stay in the learner's row layout (models/resident.py) when

        * the learner offers one (rows sharded over a one-process mesh:
          `DeviceDataParallelTreeLearner.row_layout`; the one-chip and the
          host-driven learners have none);
        * the objective's gradient of a row reads that row alone
          (`row_constants`), inside one jitted program, and it refits no
          leaf on the host (`renew_tree_output` is the base's);
        * every tree is grown from all rows by plain GBDT, one tree an
          iteration: no bagging or GOSS (their bags are host index sets),
          no linear leaves or health monitor (both read the gradients as
          [N] host rows), no DART / RF (they rewrite the score by
          traversal). Custom gradients come without an objective
          (`Booster.update`), so such a run is never here.

        Anything else keeps the score on the default device, [C, N]."""
        offer = getattr(self.tree_learner, "row_layout", None)
        layout = offer() if offer is not None else None
        obj = self.objective
        if (layout is None or type(self) is not GBDT or obj is None
                or not obj.row_constants or not obj.jit_gradients
                or (type(obj).renew_tree_output
                    is not ObjectiveFunction.renew_tree_output)
                or self.num_tree_per_iteration != 1
                or self.sample_strategy.samples_rows
                or self.config.linear_tree or self._health is not None):
            return None
        return ResidentRows(layout, obj, self._score)

    def _pack(self, grad: jax.Array, hess: jax.Array) -> jax.Array:
        """One tree's gradients as its learner takes them: [N+1, 3] with
        the sentinel row, or [n_pad, 3] in the row layout."""
        if self._rows is None:
            return _pack_gh(grad, hess)
        return self._rows.programs.pack(grad, hess)

    def _compute_gh(self, score):
        """score [N] (C==1) or [C, N] -> (grad, hess) matching shapes — the
        whole-iteration gradient pass (kept unpacked so the sample strategy
        can rescale GOSS's small-gradient rows before packing)."""
        with jax.named_scope(SCOPE_GRADIENTS):
            return self.objective.get_gradients(score)

    def prepare_training_score(self) -> None:
        """Hook run before custom gradients read the training score
        (GetTrainingScore, boosting.h); DART drops trees here."""

    def boost_from_average(self, class_id: int) -> float:
        """gbdt.cpp:327-350."""
        if (not self.models and not self._has_init_score
                and self.objective is not None and self.config.boost_from_average):
            init = self.objective.boost_from_score(class_id)
            if abs(init) > K_EPSILON:
                self.score = self.score.at[class_id].add(init)
                for vd in self.valid_sets:
                    vd.score = vd.score.at[class_id].add(init)
                Log.info("Start training from score %f", init)
                return init
        return 0.0

    # --------------------------------------------------- async tree pipeline

    def _async_enabled(self) -> bool:
        """Eligibility gate for the async per-tree pipeline: the device
        learner's train_async/finalize split overlaps tree t's on-device
        growth with the host replay of tree t-1. Every condition below
        protects BIT-IDENTICAL semantics with the sync path:

        * plain GBDT, one tree per iteration, no linear leaves — subclasses
          (DART/RF) reorder score updates around training;
        * DeviceTreeLearner, unquantized — quantized renewal rewrites leaf
          values after replay and reads per-tree host state;
        * objective present with the BASE renew_tree_output no-op (L1-style
          objectives refit leaf values on the host before the score update);
        * the learning rate is exactly representable in f32, so the device
          f32 (leaf * rate) equals the host f64 shrink + f32 cast bit for
          bit. LGBM_TPU_ASYNC=1 forces the pipeline on regardless of the
          rate; LGBM_TPU_ASYNC=0 disables it."""
        env = os.environ.get("LGBM_TPU_ASYNC", "")
        if env == "0":
            return False
        from ..treelearner.device import DeviceTreeLearner

        learner = getattr(self, "tree_learner", None)
        if not isinstance(learner, DeviceTreeLearner) or learner.quantized:
            return False
        if type(self) is not GBDT:
            return False
        if self.num_tree_per_iteration != 1 or self.config.linear_tree:
            return False
        if not self.class_need_train[0] or self.train_set.num_features <= 0:
            return False
        obj = self.objective
        if obj is None or (type(obj).renew_tree_output
                           is not ObjectiveFunction.renew_tree_output):
            return False
        if env == "1":
            return True
        rate = float(self.shrinkage_rate)
        return float(np.float32(rate)) == rate

    def _flush_pending(self) -> None:
        """Finalize the in-flight tree, if any: replay its split log into
        the placeholder Tree already sitting in self.models, shrink it, and
        apply the deferred valid-score updates. A stub (no splits found)
        unwinds the whole iteration — the placeholder is removed and iter_
        decremented — and raises the _async_stub_stop flag so the next
        train_one_iter reports is_finished, matching the sync stop one
        iteration late. Called from every state reader (eval, predict,
        rollback, refit, export) and from the sync training path."""
        pending = self._pending
        if pending is None:
            return
        self._pending = None
        with global_timer.scope("tree_train"):
            tree = self.tree_learner.finalize(pending)
        if tree.num_leaves <= 1:
            for i in range(len(self.models) - 1, -1, -1):
                if self.models[i] is tree:
                    del self.models[i]
                    break
            self.iter_ -= 1
            self._predictor.invalidate()
            self._async_stub_stop = True
            return
        tree.shrink(self.shrinkage_rate)
        with global_timer.scope("update_score"):
            self._update_valid_scores(tree, 0)

    def train_one_iter(self, gradients: Optional[np.ndarray] = None,
                       hessians: Optional[np.ndarray] = None) -> bool:
        """Returns True when training should STOP (no more valid splits) —
        matching LGBM_BoosterUpdateOneIter's is_finished flag."""
        rt = elastic.active()
        if rt is not None:
            # beat the collective watchdog + (without a health monitor to
            # piggyback on) run the windowed heartbeat collective. The beat
            # precedes the fault hooks: a real worker enters the iteration
            # alive and blocks INSIDE it, so the last-good count the
            # watchdog reports equals the completed iterations (= the
            # snapshot a restarted gang resumes from).
            rt.on_iteration_start(self.iter_,
                                  piggyback=self._health is not None)
        faults.check_kill(self.iter_)
        faults.check_distributed(self.iter_)
        if self._async_stub_stop:
            self._async_stub_stop = False
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            return True
        C = self.num_tree_per_iteration
        init_scores = [0.0] * C
        custom = gradients is not None
        rows = self._rows  # never with custom gradients: no objective
        if not custom:
            if self.objective is None:
                Log.fatal("No object function provided")
            for c in range(C):
                init_scores[c] = self.boost_from_average(c)
        should_continue = False
        with global_timer.scope("boosting"):
            if custom:
                grads = jnp.asarray(gradients, dtype=jnp.float32).reshape(
                    C, self.num_data)
                hesses = jnp.asarray(hessians, dtype=jnp.float32).reshape(
                    C, self.num_data)
                if C == 1:
                    grads, hesses = grads[0], hesses[0]
            else:
                with global_timer.scope(SPAN_GRADIENTS):
                    if rows is not None:
                        grads, hesses = rows.gradients()
                    else:
                        grads, hesses = self._grad_fn(
                            self.score if C > 1 else self.score[0])
        grads, hesses = faults.maybe_poison_gh(grads, hesses, self.iter_)
        if self._health is not None:
            grads, hesses = self._health.admit(self, grads, hesses)
        with global_timer.scope("bagging"):
            bag, grads, hesses = self.sample_strategy.bagging(
                self.iter_, grads, hesses)
            self._refresh_bag_cache(bag)
        # async pipeline: not on the first iteration (its stub path seeds
        # init scores) and not under bagging (OOB updates need the host
        # tree before the next gradient pass)
        if (not custom and bag is None and len(self.models) >= C
                and self._async_enabled()):
            return self._train_one_iter_async(grads, hesses)
        self._flush_pending()
        if self._async_stub_stop:
            self._async_stub_stop = False
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            return True
        for c in range(C):
            with global_timer.scope("boosting"):
                if C > 1:
                    gh_ext = _pack_gh(grads[c], hesses[c])
                else:
                    gh_ext = self._pack(grads, hesses)
            new_tree = Tree(2)
            if self.class_need_train[c] and self.train_set.num_features > 0:
                with global_timer.scope("tree_train"):
                    new_tree = (self.tree_learner.train(gh_ext, bag)
                                if rows is None
                                else self.tree_learner.train_rows(gh_ext))
            if new_tree.num_leaves > 1:
                should_continue = True
                if self._health is not None:
                    self._health.observe_tree(new_tree)
                if self.config.linear_tree:
                    from ..treelearner.linear import fit_leaf_linear_models

                    gvec = grads[c] if C > 1 else grads
                    hvec = hesses[c] if C > 1 else hesses
                    with global_timer.scope("linear_fit"):
                        fit_leaf_linear_models(
                            new_tree, self.train_set, self.train_raw,
                            self.tree_learner.partition,
                            np.asarray(gvec), np.asarray(hvec),
                            self.config.linear_lambda,
                            is_first_tree=len(self.models) < C)
                # resident rows: the objective's hook is the base's no-op
                # (`_resident_rows`), and its argument would cut a view
                if self.objective is not None and rows is None:
                    self.objective.renew_tree_output(
                        new_tree, self.score[c], self.tree_learner.partition)
                new_tree.shrink(self.shrinkage_rate)
                with global_timer.scope("update_score"):
                    self._update_train_score(new_tree, c)
                    self._update_valid_scores(new_tree, c)
                if abs(init_scores[c]) > K_EPSILON:
                    new_tree.add_bias(init_scores[c])
            else:
                if len(self.models) < C:
                    if (self.objective is not None and not self.config.boost_from_average
                            and not self._has_init_score):
                        init_scores[c] = self.objective.boost_from_score(c)
                        self.score = self.score.at[c].add(init_scores[c])
                        for vd in self.valid_sets:
                            vd.score = vd.score.at[c].add(init_scores[c])
                    new_tree.as_constant_tree(init_scores[c])
                else:
                    new_tree.as_constant_tree(0.0)
            self.models.append(new_tree)
        self._predictor.invalidate()
        if not should_continue:
            Log.warning("Stopped training because there are no more leaves that "
                        "meet the split requirements")
            if len(self.models) > C:
                del self.models[-C:]
            return True
        self.iter_ += 1
        if telemetry.enabled():
            telemetry.sample_hbm()  # per-tree HBM high-water
        return False

    def _train_one_iter_async(self, grads: jax.Array,
                              hesses: jax.Array) -> bool:
        """One async-pipelined iteration (eligibility checked by caller):
        dispatch tree t, apply its score update straight from the device
        split log, then — while the device is still growing tree t —
        host-replay tree t-1's log into its placeholder Tree. The only
        blocking transfer per iteration is t-1's split log, which has been
        copying since its dispatch. Semantics stay bit-identical to the
        sync path; only the stop on a no-split tree lands one iteration
        late (the extra dispatched tree is provably the same stub with a
        zero score delta, and is dropped)."""
        rows = self._rows
        with global_timer.scope("boosting"):
            gh_ext = self._pack(grads, hesses)
        with global_timer.scope("tree_train"):
            pending = (self.tree_learner.train_async(gh_ext, None)
                       if rows is None
                       else self.tree_learner.train_rows_async(gh_ext))
        apply_log = sanitize.guard(
            _apply_split_log_to_score, (0,),
            "_apply_split_log_to_score (models/gbdt.py async score update)")
        rate = jnp.float32(self.shrinkage_rate)
        with global_timer.scope("update_score"):
            if rows is None:
                self.score = self.score.at[0].set(apply_log(
                    self.score[0], _colocate(pending.rec_store, self.score),
                    _colocate(pending.leaf_id, self.score), rate,
                    self.config.num_leaves))
            else:  # the log is on every chip, the ids where the tree wrote
                rows.score = apply_log(rows.score, pending.rec_store,
                                       pending.leaf_id, rate,
                                       self.config.num_leaves)
        self.models.append(pending.tree)
        self._predictor.invalidate()
        self._flush_pending()  # overlaps t-1's replay with t's growth
        if self._async_stub_stop:
            self._async_stub_stop = False
            self.models.pop()  # tree t: same gradients => the same stub
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            return True
        self._pending = pending
        self.iter_ += 1
        return False

    # ------------------------------------------------------------------ score

    def _refresh_bag_cache(self, bag: Optional[np.ndarray]) -> None:
        """The bag is reused across bagging_freq iterations, so the padded
        out-of-bag index array is computed once per bag change."""
        if bag is self._cur_bag and getattr(self, "_oob_padded_ready", False):
            return
        self._cur_bag = bag
        self._oob_padded_ready = True
        if bag is None or len(bag) >= self.num_data:
            self._oob_padded = None
        elif isinstance(bag, DeviceBag):
            # device bag: build the padded OOB index set from the mask
            # without pulling it to host — sentinel rows (id == num_data,
            # same as pad_indices) sort past every real index
            n = self.num_data
            p = bucket_size(n - bag.n_bag)
            base = jnp.where(bag.mask, n,
                             jnp.arange(n, dtype=jnp.int32))
            if p > n:
                base = jnp.concatenate(
                    [base, jnp.full(p - n, n, dtype=jnp.int32)])
            self._oob_padded = jnp.sort(base)[:p]
        else:
            oob = np.setdiff1d(np.arange(self.num_data, dtype=np.int32), bag)
            self._oob_padded = jnp.asarray(pad_indices(oob, self.num_data))

    @property
    def _depth_bound(self) -> int:
        return (self.config.max_depth if self.config.max_depth > 0
                else self.config.num_leaves - 1)

    def _all_rows_padded(self) -> jax.Array:
        if getattr(self, "_all_rows_cache", None) is None:
            self._all_rows_cache = jnp.asarray(pad_indices(
                np.arange(self.num_data, dtype=np.int32), self.num_data))
        return self._all_rows_cache

    def _add_tree_to_train_score(self, tree: Tree, class_id: int) -> None:
        """Add an arbitrary (e.g. previously trained) tree's outputs to the
        train score of every row via bin-space traversal — the train-time
        ScoreUpdater::AddScore(tree) path DART/RF renormalization needs."""
        if tree.is_linear:
            self._add_linear_tree_score(tree, class_id)
            return
        score = self._score_tree_rows(tree, self.score[class_id],
                                      self._all_rows_padded())
        self.score = self.score.at[class_id].set(score)

    def _score_tree_rows(self, tree: Tree, score: jax.Array,
                         rows_padded: jax.Array) -> jax.Array:
        """Bin-space tree traversal over padded rows. A streamed learner
        keeps no device plane (bins_dev is None) — route through its
        block-sharded traversal, which is bitwise-equal (each valid row
        scattered exactly once with the identical leaf value)."""
        learner = self.tree_learner
        if getattr(learner, "bins_dev", None) is None:
            return learner.add_tree_to_score_blocked(
                tree, score, rows_padded, self._depth_bound)
        return add_tree_to_score(tree, self.train_set, learner.bins_dev,
                                 score, rows_padded, self.num_data,
                                 self._depth_bound)

    def _multiply_score(self, class_id: int, val: float) -> None:
        """ScoreUpdater::MultiplyScore on train + valid (RF averaging)."""
        self.score = self.score.at[class_id].multiply(val)
        for vd in self.valid_sets:
            vd.score = vd.score.at[class_id].multiply(val)

    def _train_raw_dev(self) -> jax.Array:
        if getattr(self, "_train_raw_dev_cache", None) is None:
            self._train_raw_dev_cache = jnp.asarray(self.train_raw,
                                                    dtype=jnp.float32)
        return self._train_raw_dev_cache

    def _add_linear_tree_score(self, tree: Tree, class_id: int) -> None:
        """Linear leaves need raw feature values, not leaf constants: score
        through the packed linear predictor (AddPredictionToScore with
        is_linear, gbdt.cpp)."""
        packed = pack_ensemble([tree], fixed_leaves=self.config.num_leaves,
                               fixed_depth=self._depth_bound)
        delta = predict_raw(packed, self._train_raw_dev())[:, 0]
        self.score = self.score.at[class_id].add(delta)

    def _leaf_values(self, tree: Tree) -> jax.Array:
        """The tree's leaf values padded to the configuration's num_leaves:
        one update program serves every tree."""
        lv = np.zeros(max(self.config.num_leaves, tree.num_leaves),
                      dtype=np.float32)
        lv[: tree.num_leaves] = tree.leaf_value[: tree.num_leaves]
        return jnp.asarray(lv)

    def _update_train_score(self, tree: Tree, class_id: int) -> None:
        if tree.is_linear:
            self._add_linear_tree_score(tree, class_id)
            return
        part = self.tree_learner.partition
        rows = self._rows
        if rows is not None:
            # every tree of the run left its [n_pad] ids in the layout:
            # each chip adds to its own rows (pad rows carry -1)
            rows.score = _add_leaf_values_to_score(
                rows.score, part.leaf_ids_dev(), self._leaf_values(tree))
            return
        score = self.score[class_id]
        ids_fn = getattr(part, "leaf_ids_dev", None)
        if ids_fn is not None:
            # vectorized path: one gather over the device leaf-id vector
            # (bagged-out rows carry -1 and contribute nothing)
            ids = _colocate(ids_fn(), score)
            score = _add_leaf_values_to_score(score, ids,
                                              self._leaf_values(tree))
        else:
            for leaf in range(tree.num_leaves):
                idx = part.indices(leaf)
                score = score.at[idx].add(tree.leaf_value[leaf], mode="drop")
        bag = self._cur_bag
        if bag is not None and self._oob_padded is not None:
            # out-of-bag rows: bin-space tree traversal (the train-time
            # AddPredictionToScore path, gbdt.cpp out_of_bag update)
            score = self._score_tree_rows(tree, score, self._oob_padded)
        self.score = self.score.at[class_id].set(score)

    def _valid_cat_words(self, tree: Tree) -> int:
        """The bitset words of a per-tree validation pack: none where the
        training set has no categorical column, else the tree's own words
        rounded up to a power of two, at least VALID_CAT_WORDS (a 16 KB
        pack). A few shapes, and so a few programs, serve every tree, and
        the pack grows with the words the tree's nodes hold, never with the
        size of the category codes alone."""
        if not self._has_categorical():
            return 0
        return max(VALID_CAT_WORDS,
                   1 << (len(tree.cat_threshold) - 1).bit_length())

    def _update_valid_scores(self, tree: Tree, class_id: int) -> None:
        """Add one tree to every validation set's scores. A set with a
        plane (`_valid_plane`) takes a tree whose bin fields are its own
        (grown in this process; not linear) by replaying its splits over
        the plane (ops/score.py `replay_tree_to_score`): the decisions
        LightGBM's ScoreUpdater makes, the same float32 add. Every other
        (set, tree) scores the one-tree pack over the raw matrix. One
        `valid_update` note a set and tree says which."""
        table = packed = None
        splits = max(tree.num_leaves - 1, 0)
        for vd in self.valid_sets:
            binned = (vd.plane is not None and tree.has_bin_thresholds
                      and not tree.is_linear)
            tracing.note("valid_update", binned=int(binned),
                         rows=int(vd.dataset.num_data), splits=splits)
            if binned:
                if table is None:
                    fields, n_words = self._split_fields
                    table = jnp.asarray(split_table(
                        tree, fields, n_words, self.config.num_leaves - 1))
                global_timer.add_count("valid_binned_updates", 1)
                vd.score = replay_tree_to_score(
                    vd.score, vd.plane, table, np.int32(splits),
                    self._leaf_values(tree), class_id)
                continue
            if packed is None:
                packed = pack_ensemble(
                    [tree], fixed_leaves=self.config.num_leaves,
                    fixed_depth=self._depth_bound,
                    fixed_cat_words=self._valid_cat_words(tree))
            vd.score = _add_valid_delta(vd.score, predict_raw(packed, vd.raw),
                                        class_id)

    # ------------------------------------------------------------------- eval

    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        self._flush_pending()
        out = []
        for m in self.train_metrics:
            for name, val in zip(m.name, m.eval(self.score[0] if self.num_tree_per_iteration == 1
                                                else self.score, self.objective)):
                out.append(("training", name, val, m.greater_is_better))
        return out

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        self._flush_pending()
        out = []
        with global_timer.scope(SPAN_EVAL_VALID):
            for vname, vd in zip(self.valid_names, self.valid_sets):
                for m in vd.metrics:
                    score = (vd.score[0] if self.num_tree_per_iteration == 1
                             else vd.score)
                    for name, val in zip(m.name, m.eval(score, self.objective)):
                        out.append((vname, name, val, m.greater_is_better))
        return out

    # ---------------------------------------------------------------- predict

    @staticmethod
    def _sharded_predict_enabled(n_rows: int,
                                 min_rows: Optional[int] = None) -> bool:
        from ..parallel.predict import sharded_predict_enabled

        return sharded_predict_enabled(n_rows, min_rows=min_rows)

    def _packed(self, num_iteration: int = 0, start_iteration: int = 0,
                dtype=jnp.float32):
        self._flush_pending()
        C = self.num_tree_per_iteration
        start = max(start_iteration, 0) * C
        n_trees = len(self.models)
        if num_iteration > 0:
            n_trees = min(n_trees, start + num_iteration * C)
        return self._predictor.get(self.models, start, n_trees, dtype=dtype)

    def predict(self, X: np.ndarray, raw_score: bool = False,
                num_iteration: int = 0, start_iteration: int = 0,
                early_stop: Optional[Tuple[int, float]] = None,
                chunk_rows: Optional[int] = None,
                shard_rows: Optional[int] = None) -> np.ndarray:
        dtype = predict_dtype(X)
        packed = self._packed(num_iteration, start_iteration, dtype=dtype)
        C = self.num_tree_per_iteration
        n = X.shape[0]
        chunk = stream_chunk_rows(n, chunk_rows)

        def upload() -> jax.Array:
            with global_timer.scope(SPAN_PREDICT_UPLOAD):
                return jnp.asarray(X, dtype=dtype)

        # the device part of the call: upload, traverse, fetch (the streamed
        # path opens the three per chunk, under its predict_chunk spans)
        with global_timer.scope(SPAN_PREDICT_CALL):
            if early_stop is not None and packed.num_trees > 0:
                from ..ops.predict import predict_raw_early_stop

                freq, margin = early_stop
                out = predict_raw_early_stop(packed, upload(), C, freq,
                                             margin)
            elif packed.num_trees > 0 and chunk_rows is not None \
                    and chunk > 0:
                # explicit pred_chunk_rows wins over auto-sharding
                out = predict_raw_streamed(
                    packed, np.asarray(X, dtype=np.dtype(dtype)), C, chunk,
                    dtype)
            elif packed.num_trees > 0 and not packed.linear \
                    and self._sharded_predict_enabled(n, shard_rows):
                # linear ensembles keep single-chip dispatch: their score
                # math runs eagerly for bit-stability (ops/predict.predict_raw)
                from ..parallel.predict import predict_raw_sharded

                out = predict_raw_sharded(
                    packed, np.asarray(X, dtype=np.dtype(dtype)), C)
            elif chunk > 0 and packed.num_trees > 0:
                out = predict_raw_streamed(
                    packed, np.asarray(X, dtype=np.dtype(dtype)), C, chunk,
                    dtype)
            else:
                # serving warm start: a key-matched AOT executable answers
                # without consulting (or populating) the jit cache — a cold
                # replica's first bucket-shaped request skips the XLA compile
                fn = None
                if packed.num_trees > 0 and not packed.linear:
                    fn = self._predictor.aot_get(
                        packed, n, X.shape[1], C, np.dtype(dtype))
                xd = upload()
                if fn is not None:
                    with global_timer.scope(SPAN_PREDICT_TRAVERSE):
                        out = fn(packed, xd)
                else:
                    out = predict_raw(packed, xd, C)
            if self.average_output and packed.num_trees > 0:
                out = out / (packed.num_trees // C)
            if not raw_score and self.objective is not None:
                out = self.objective.convert_output(out)
            with global_timer.scope(SPAN_PREDICT_FETCH):
                res = np.asarray(out)
        return res[:, 0] if res.shape[1] == 1 else res

    def predict_leaf_index(self, X: np.ndarray, num_iteration: int = 0,
                           start_iteration: int = 0) -> np.ndarray:
        from ..ops.predict import predict_leaf_indices

        dtype = predict_dtype(X)
        packed = self._packed(num_iteration, start_iteration, dtype=dtype)
        return np.asarray(predict_leaf_indices(packed, jnp.asarray(X, dtype=dtype)))

    # ------------------------------------------------------------------ model

    def refit(self, pred_leaf: np.ndarray) -> None:
        """GBDT::RefitTree (gbdt.cpp:266-305): keep every tree's structure,
        refit the leaf outputs on the current training data. pred_leaf is
        [num_data, num_trees] leaf assignments of the OLD model on the new
        data; gradients are recomputed per iteration from the accumulating
        refit score, and each leaf output becomes

            refit_decay_rate * old + (1 - refit_decay_rate) * fit * shrinkage

        (SerialTreeLearner::FitByExistingTree, serial_tree_learner.cpp:250-283
        — per-leaf sums here are one device scatter-add per tree).
        """
        self._flush_pending()
        C = self.num_tree_per_iteration
        T = len(self.models)
        if pred_leaf.shape != (self.num_data, T):
            Log.fatal("Refit leaf predictions shape %s != (%d, %d)",
                      pred_leaf.shape, self.num_data, T)
        decay = self.config.refit_decay_rate
        cfg = self.config
        leaf_dev = jnp.asarray(pred_leaf.astype(np.int32))
        for it in range(T // C):
            grads, hesses = self._grad_fn(
                self.score if C > 1 else self.score[0])
            for c in range(C):
                m = it * C + c
                tree = self.models[m]
                g = grads[c] if C > 1 else grads
                h = hesses[c] if C > 1 else hesses
                leaf = leaf_dev[:, m]
                L = tree.num_leaves
                sum_g = np.asarray(jnp.zeros(L).at[leaf].add(g))
                sum_h = np.asarray(jnp.zeros(L).at[leaf].add(h))
                from ..treelearner.serial import _leaf_output_host

                for i in range(L):
                    out = _leaf_output_host(
                        float(sum_g[i]), float(sum_h[i]) + K_EPSILON,
                        cfg.lambda_l1, cfg.lambda_l2, cfg.max_delta_step)
                    tree.set_leaf_output(
                        i, decay * float(tree.leaf_value[i])
                        + (1.0 - decay) * out * tree.shrinkage)
                lv = jnp.asarray(tree.leaf_value[:L], dtype=jnp.float32)
                self.score = self.score.at[c].add(lv[leaf])
        self._predictor.invalidate()

    def rollback_one_iter(self) -> None:
        """RollbackOneIter (gbdt.cpp:462): drop the last iteration's trees and
        back out their score contributions."""
        self._flush_pending()
        if self.iter_ <= 0:
            return
        C = self.num_tree_per_iteration
        for c in range(C):
            tree = self.models[-C + c]
            inv = Tree(max(tree.max_leaves, 2))
            # subtract by re-adding the negated tree through the packed path
            tree.shrink(-1.0)
            self._update_train_score(tree, c)
            self._update_valid_scores(tree, c)
            tree.shrink(-1.0)
        del self.models[-C:]
        self.iter_ -= 1
        self._predictor.invalidate()

    def to_model(self) -> GBDTModel:
        self._flush_pending()
        ds = self.train_set
        model = GBDTModel()
        model.num_class = self.num_class
        model.num_tree_per_iteration = self.num_tree_per_iteration
        model.max_feature_idx = (ds.num_total_features - 1) if ds is not None else 0
        model.objective_str = self.objective.to_string() if self.objective else None
        model.feature_names = ds.feature_names if ds is not None else []
        model.feature_infos = ds.feature_infos() if ds is not None else []
        model.monotone_constraints = list(ds.monotone_constraints) if ds is not None else []
        model.trees = self.models
        model.best_iteration = self.best_iteration
        model.average_output = self.average_output
        model.parameters_str = self.config.to_string()
        return model


def create_boosting(config: Config, train_set: Optional[Dataset],
                    objective: Optional[ObjectiveFunction],
                    train_raw: Optional[np.ndarray] = None) -> GBDT:
    """Boosting factory (boosting.cpp:41-101): gbdt / dart / rf; the legacy
    boosting=goss spelling trains a GBDT with the GOSS sample strategy."""
    b = config.boosting
    if b == "dart":
        from .dart import DART

        return DART(config, train_set, objective, train_raw)
    if b in ("rf", "random_forest"):
        from .rf import RF

        return RF(config, train_set, objective, train_raw)
    if b in ("gbdt", "gbrt", "gbm", "goss"):
        return GBDT(config, train_set, objective, train_raw)
    Log.fatal("Unknown boosting type %s", b)
