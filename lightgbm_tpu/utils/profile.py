"""Device-level profiling: jax.profiler trace capture around training.

The reference's tracing story is the CHECK/timer macros summarized at exit
(src/utils/common.h timers, Log::Info dumps); ours is two layers:

  * `global_timer` (utils/timer.py) — host-side scoped wall-clock sums,
    printed via `print_timer_summary()` like the reference's timer table.
  * THIS module — XLA device traces. `maybe_trace()` wraps a training run
    in `jax.profiler.trace` when LGBM_TPU_PROFILE=<dir> is set (or a dir is
    passed explicitly), producing a TensorBoard-loadable xplane profile of
    every kernel the run dispatched. Used by engine.train and the CLI, so

        LGBM_TPU_PROFILE=/tmp/prof python -m lightgbm_tpu.cli config=...

    captures the whole training run with zero code changes.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

from .log import Log

ENV_VAR = "LGBM_TPU_PROFILE"


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str] = None):
    """Trace into `trace_dir` (or $LGBM_TPU_PROFILE); no-op when unset.
    With LGBM_TPU_TIMETAG=1 the program's host spans are in the trace too,
    beside the device operations and their `lgbm.` scopes."""
    target = trace_dir or os.environ.get(ENV_VAR)
    if not target:
        yield
        return
    _check_writable(target)
    import jax

    Log.info("Profiling to %s (load with TensorBoard's profile plugin)",
             target)
    try:
        with jax.profiler.trace(target):
            yield
    finally:
        # the partial profile of a crashed run is often the most useful
        # artifact it leaves behind — always say where it landed
        Log.info("Profile written to %s", target)


def _check_writable(target: str) -> None:
    """Fail fast with a named invariant instead of the deep TraceMe/XLA
    traceback jax.profiler.trace raises mid-run on an unwritable target."""
    probe = target
    while probe and not os.path.exists(probe):
        parent = os.path.dirname(probe)
        if parent == probe:
            break
        probe = parent
    if os.path.isfile(target):
        Log.fatal("Profile target %s is a file, not a directory", target)
    if not probe or not os.access(probe, os.W_OK):
        Log.fatal("Profile target %s is not writable (nearest existing "
                  "ancestor: %s) — fix LGBM_TPU_PROFILE or the trace_dir "
                  "argument", target, probe or "<none>")

