"""Per-label accumulating wall-clock timer.

Counterpart of the reference's Common::Timer/FunctionTimer RAII scopes
(include/LightGBM/utils/common.h:979-1063) that feed `global_timer`, printed
at exit under -DUSE_TIMETAG. Here: a context-manager / decorator that
accumulates per-label seconds, plus jax.profiler trace annotation so the same
labels appear in TPU traces.

The timer doubles as the span source for the structured telemetry stack
(lightgbm_tpu/telemetry.py): a session installs `span_hook`, every closed
scope reports (label, start, end) to it, and the Chrome-trace exporter turns
those into B/E span events. `new_epoch()` gives each engine.train() call a
fresh accumulation window so back-to-back runs in one process stop
conflating totals (counters survive — perf tests read them after train).
"""
from __future__ import annotations

import contextlib
import functools
import os
import time
from collections import defaultdict
from typing import Callable, Dict, Iterator, List, Optional

# Host span labels of the two roots and their children: one root per unit
# of work (a boosting iteration, a predict call, a streamed chunk), opened
# through `global_timer.scope` like every other host label.
SPAN_ITERATION = "iteration"
SPAN_PREDICT_CALL = "predict_call"
SPAN_PREDICT_CHUNK = "predict_chunk"
SPAN_PREDICT_UPLOAD = "predict_upload"
SPAN_PREDICT_TRAVERSE = "predict_traverse"
SPAN_PREDICT_FETCH = "predict_fetch"
# The row-sharded device learners' two per-tree host phases around the
# whole-tree dispatch (parallel/learners.py train_async): the gradients,
# leaf ids and feature mask padded and placed across the mesh, and the
# tree's per-row leaf ids brought back to the score's chip.
SPAN_SHARD_INPUTS = "shard_inputs"
SPAN_GATHER_LEAF_IDS = "gather_leaf_ids"
# A quantized-gradient learner's per-tree step (treelearner/serial.py
# `_prepare_gh`): the host's dispatch of the one jitted program that turns
# the float gradient pack into the tree's int8 pack and its two scales.
SPAN_QUANTIZE = "quantize"
# The objective's gradient pass (models/gbdt.py train_one_iter: the host's
# dispatch of the gradient program, one an iteration for every objective but
# rank_xendcg) and an iteration's evaluation of the validation sets
# (GBDT.eval_valid: the metrics' programs and the fetch of their values).
SPAN_GRADIENTS = "gradients"
SPAN_EVAL_VALID = "eval_valid"

# Device scopes (`jax.named_scope`): every device operation of the training
# and predict hot paths carries one of these in its name stack, under ONE
# prefix so a trace reader can tell the program's scopes from JAX's own
# name-stack components (`jit(...)`, `while`, `body`, `closed_call`). Where
# scopes nest, the innermost is the operation's scope. Nothing else spells
# these names (docs/OBSERVABILITY.md lists what each covers).
SCOPE_PREFIX = "lgbm."
SCOPE_TREE_SETUP = SCOPE_PREFIX + "tree_setup"
SCOPE_SELECT = SCOPE_PREFIX + "select"
SCOPE_ROUTE = SCOPE_PREFIX + "route"
SCOPE_COMPACT = SCOPE_PREFIX + "compact"
SCOPE_HIST = SCOPE_PREFIX + "hist"
SCOPE_SCAN = SCOPE_PREFIX + "scan"
SCOPE_ALLREDUCE = SCOPE_PREFIX + "allreduce"
SCOPE_REPLAY = SCOPE_PREFIX + "replay"
SCOPE_COMMIT = SCOPE_PREFIX + "commit"
SCOPE_FINISH = SCOPE_PREFIX + "finish"
SCOPE_GRADIENTS = SCOPE_PREFIX + "gradients"
SCOPE_UPDATE_SCORE = SCOPE_PREFIX + "update_score"
SCOPE_QUANTIZE = SCOPE_PREFIX + "quantize"
# lambdarank's gradient program, inside lgbm.gradients (objectives/rank.py)
SCOPE_RANK_SORT = SCOPE_PREFIX + "rank_sort"
SCOPE_RANK_PAIRS = SCOPE_PREFIX + "rank_pairs"
SCOPE_RANK_SCATTER = SCOPE_PREFIX + "rank_scatter"
# the per-tree update of the validation scores and the ranking metrics'
# programs (models/gbdt.py _update_valid_scores, metrics/rank.py)
SCOPE_VALID_SCORE = SCOPE_PREFIX + "valid_score"
SCOPE_EVAL_NDCG = SCOPE_PREFIX + "eval_ndcg"
SCOPE_RENEW_LEAVES = SCOPE_PREFIX + "renew_leaves"
# the categorical lane of the split scan (ops/split.py, inside lgbm.scan)
# and the categorical decision of the wave's routing (inside lgbm.route)
SCOPE_CAT_SCAN = SCOPE_PREFIX + "cat_scan"
SCOPE_CAT_ROUTE = SCOPE_PREFIX + "cat_route"
# the split scan of a data set with an EFB bundle, its features gathered
# in width classes (ops/split.py, inside lgbm.scan)
SCOPE_BUNDLE_SCAN = SCOPE_PREFIX + "bundle_scan"
# the binary AUC metric's one program (metrics/binary.py)
SCOPE_EVAL_AUC = SCOPE_PREFIX + "eval_auc"
SCOPE_NODE_GATHER = SCOPE_PREFIX + "node_gather"
SCOPE_FEATURE_GATHER = SCOPE_PREFIX + "feature_gather"
SCOPE_DECIDE = SCOPE_PREFIX + "decide"
SCOPE_PATH_MATCH = SCOPE_PREFIX + "path_match"
SCOPE_LEAF_VALUES = SCOPE_PREFIX + "leaf_values"
SCOPE_ACCUMULATE = SCOPE_PREFIX + "accumulate"


class GlobalTimer:
    def __init__(self) -> None:
        self.totals: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self.counters: Dict[str, int] = defaultdict(int)
        # labels published via set_count (levels, not accumulations) — lets
        # telemetry report gauges absolute and accumulators as deltas
        self.gauges: set = set()
        self.enabled = bool(os.environ.get("LGBM_TPU_TIMETAG"))
        self.epoch = 0
        # telemetry sink: called as span_hook(label, t0, t1) on every closed
        # scope (perf_counter seconds). None when no session is recording.
        self.span_hook: Optional[Callable[[str, float, float], None]] = None
        # always-maintained stack of open scope labels (a list push/pop is
        # nanoseconds): the sanitizer attributes counted device syncs to
        # the innermost scope even when wall-clock timing is off, so
        # sync-free assertions (utils/sanitize.py) work without TIMETAG.
        self.label_stack: List[str] = []

    @contextlib.contextmanager
    def scope(self, label: str) -> Iterator[None]:
        if not self.enabled:
            self.label_stack.append(label)
            try:
                yield
            finally:
                self.label_stack.pop()
            return
        try:
            import jax.profiler

            ctx = jax.profiler.TraceAnnotation(label)
        except Exception:  # pragma: no cover - profiler unavailable
            ctx = contextlib.nullcontext()
        start = time.perf_counter()
        self.label_stack.append(label)
        try:
            with ctx:
                yield
        finally:
            self.label_stack.pop()
        end = time.perf_counter()
        self.totals[label] += end - start
        self.counts[label] += 1
        if self.span_hook is not None:
            self.span_hook(label, start, end)

    def add_count(self, label: str, n: int) -> None:
        """Accumulate a work counter (rows histogrammed, bytes moved, ...).

        Always on, unlike the wall-clock scopes: counters are cheap ints
        and the perf tests assert on them (e.g. `device_hist_rows` proving
        the rows-in-leaf wave path is O(selected rows), not O(N * waves)).
        """
        self.counters[label] += int(n)

    def set_count(self, label: str, n: int) -> None:
        """Set a gauge counter (a level, not an accumulation): idempotent,
        so per-tree code can re-publish a static figure — e.g. the sharded
        learner's `device_ici_bytes_per_wave` — without inflating it."""
        self.counters[label] = int(n)
        self.gauges.add(label)

    def report(self) -> str:
        lines = ["LightGBM-TPU timer summary:"]
        # deterministic: totals descending, equal totals tie-broken by label
        for label in sorted(self.totals, key=lambda k: (-self.totals[k], k)):
            lines.append(f"  {label}: {self.totals[label]:.3f}s ({self.counts[label]} calls)")
        for label in sorted(self.counters):
            lines.append(f"  {label}: {self.counters[label]}")
        return "\n".join(lines)

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()
        self.counters.clear()

    def new_epoch(self) -> int:
        """Start a fresh per-run accumulation window: wall-clock totals and
        call counts reset; work counters SURVIVE (the learner perf tests
        read them after training returns). Returns the new epoch
        id so telemetry records can name the run they belong to."""
        self.totals.clear()
        self.counts.clear()
        self.epoch += 1
        return self.epoch


global_timer = GlobalTimer()


def timed(label: str):
    """Decorator form of global_timer.scope."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with global_timer.scope(label):
                return fn(*args, **kwargs)

        return wrapper

    return deco
