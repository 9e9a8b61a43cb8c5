"""Runtime donation/sync sanitizer (LGBM_TPU_SANITIZE=1).

The dynamic counterpart of graftlint's static R1/R10 passes: where the
linter proves properties over the call graph, the sanitizer enforces them
on a real run —

* **Use-after-donation poisoning.** `guard(fn, donate, site)` wraps a
  dispatch whose jit donates buffer arguments. After the call, every
  donated `jax.Array` positional arg is deleted and registered; any later
  host access to that Python reference raises `UseAfterDonationError`
  naming the donation site, instead of silently reading a recycled buffer
  on TPU (on CPU, where XLA ignores donation, the bug would otherwise pass
  tests and only corrupt results on the accelerator).

* **Sync accounting.** Host-sync entry points on `jax.Array`
  (`item`/`tolist`/`block_until_ready`/`__bool__`/`__float__`/`__int__`)
  are counted per innermost `global_timer.scope` label (the timer keeps
  its label stack even with LGBM_TPU_TIMETAG off). Scopes listed in
  `SYNC_FREE` assert zero syncs: any counted sync while such a scope is
  open raises `SyncInScopeError` naming the scope and the sync kind.

* **Collective-order cross-check.** The dynamic oracle for graftlint
  R12: when enabled, `jax.lax.psum` / `psum_scatter` / `all_gather` are
  wrapped to record each (op, axis_name) the process TRACES, as a
  deterministic rolling CRC per step. `check_collective_order()` — called
  from the elastic heartbeat's existing sync slot and directly by tests —
  all-gathers the per-rank prefix fingerprints and raises a typed
  `CollectiveOrderError(rank, first_divergent_op)` naming the first op
  where this rank's sequence left the gang's. Trace-time recording is
  deliberate: it is sync-free (R12's sequences are trace properties), and
  a rank that traces a collective the others never trace is exactly the
  static rule's deadlock — caught here before the mesh hangs. A
  re-executed cached jit does not re-trace, so sequences are compared per
  distinct traced program, not per dispatch.

* **Compaction pair-list overflow.** ops/compact_pallas.py sizes its pair
  list from a static bound derived for the learner's range masks; with the
  sanitizer on, a list that outgrows it raises from a host callback
  instead of being truncated (and rows dropped) in silence.

Known gap: `np.asarray(arr)` reaches the host through the buffer protocol
without calling any patchable `jax.Array` method (patching `__array__` on
ArrayImpl does not intercept it), so asarray pulls are invisible to the
sync counter. They ARE covered by the poison pass — asarray on a deleted
array still goes through `_check_if_deleted` — and by graftlint R1
statically.

Everything here is inert unless enabled: `guard` returns its argument
unchanged and no class is patched, so the production path pays one
function call and an env lookup per tree dispatch.
"""
from __future__ import annotations

import os
import zlib
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .timer import global_timer


class UseAfterDonationError(RuntimeError):
    """A host access hit a buffer that was donated to an earlier dispatch."""


class SyncInScopeError(RuntimeError):
    """A device sync happened inside a scope declared sync-free."""


class CollectiveOrderError(RuntimeError):
    """This rank's traced collective sequence diverged from the gang's.

    `rank` is the process that detected the divergence (the raiser),
    `first_divergent_op` names this rank's op at the first step where the
    prefix fingerprints disagree ("<none>" when this rank posted fewer
    collectives than the others)."""

    def __init__(self, message: str, rank: int = -1,
                 first_divergent_op: str = "") -> None:
        super().__init__(message)
        self.rank = int(rank)
        self.first_divergent_op = first_divergent_op


# scopes asserted to perform ZERO countable device syncs while open
SYNC_FREE = {"tree_device", "goss_device_select"}

_forced: Optional[bool] = None
_installed = False
_orig: Dict[str, Callable] = {}
# id(arr) -> (arr, site): strong refs keep id() stable for the run
_poisoned: Dict[int, Tuple[Any, str]] = {}
_sync_counts: Dict[str, Dict[str, int]] = defaultdict(
    lambda: defaultdict(int))
# traced collective sequence: (op, axis_repr) in trace order, plus the
# rolling CRC after each step (process-independent: zlib.crc32, no string
# hash salting)
_collective_seq: List[Tuple[str, str]] = []
_collective_crcs: List[int] = []
# prefix slots exchanged by check_collective_order: enough that real
# divergence (which appears at the first differing op) is always visible
_FP_SLOTS = 32

_COLLECTIVE_OPS = ("psum", "psum_scatter", "all_gather")


def enabled() -> bool:
    if _forced is not None:
        return _forced
    return os.environ.get("LGBM_TPU_SANITIZE", "") not in ("", "0")


def enable() -> None:
    """Force-on regardless of the env var; installs the jax.Array patches."""
    global _forced
    _forced = True
    _install()


def disable() -> None:
    """Force-off regardless of the env var; patches stay installed but
    become pass-throughs (they consult `enabled()` per call)."""
    global _forced
    _forced = False


def clear_override() -> None:
    """Back to env-var-driven (undoes enable()/disable())."""
    global _forced
    _forced = None


def reset() -> None:
    """Drop the poison registry, sync counters and collective sequence
    (between test cases)."""
    _poisoned.clear()
    _sync_counts.clear()
    _collective_seq.clear()
    _collective_crcs.clear()


def sync_counts() -> Dict[str, Dict[str, int]]:
    """Per-scope-label sync counts: {label: {kind: n}}."""
    return {label: dict(kinds) for label, kinds in _sync_counts.items()}


def _note_sync(kind: str) -> None:
    stack = global_timer.label_stack
    label = stack[-1] if stack else "<no-scope>"
    _sync_counts[label][kind] += 1
    bad = SYNC_FREE.intersection(stack)
    if bad:
        scope = sorted(bad)[0]
        raise SyncInScopeError(
            f"device sync ({kind}) inside the sync-free scope {scope!r}: "
            f"this region is asserted to stay on-device end to end — a "
            f"sync here serializes the async pipeline (see "
            f"docs/PERF_NOTES.md)")


def _install() -> None:
    """Patch jax.Array's concrete class once per process.

    The poison check rides `_check_if_deleted`, which every host-facing
    accessor (item, __array__, np.asarray, device_get, ...) calls first;
    the sync counters wrap the explicit sync entry points.
    """
    global _installed
    if _installed:
        return
    from jax._src.array import ArrayImpl

    _orig["_check_if_deleted"] = ArrayImpl._check_if_deleted

    def _checked(self):
        ent = _poisoned.get(id(self))
        if ent is not None:
            raise UseAfterDonationError(
                f"this array's buffer was donated to {ent[1]}; XLA reuses "
                f"donated buffers in place, so reading the old reference "
                f"returns garbage on TPU — copy before the dispatch or "
                f"read the dispatch's output instead")
        return _orig["_check_if_deleted"](self)

    ArrayImpl._check_if_deleted = _checked

    def _counted(name: str):
        orig = _orig[name]

        def wrapper(self, *args, **kwargs):
            if enabled():
                _note_sync(name)
            return orig(self, *args, **kwargs)

        wrapper.__name__ = name
        return wrapper

    for name in ("item", "tolist", "block_until_ready",
                 "__bool__", "__float__", "__int__"):
        _orig[name] = getattr(ArrayImpl, name)
        setattr(ArrayImpl, name, _counted(name))

    import jax

    def _probed(op: str):
        orig = _orig["lax." + op]

        def wrapper(x, axis_name=None, *args, **kwargs):
            if axis_name is None and "axis_name" in kwargs:
                axis_name = kwargs["axis_name"]
            if enabled():
                _note_collective(op, axis_name)
            if axis_name is None:
                return orig(x, *args, **kwargs)
            return orig(x, axis_name, *args, **kwargs)

        wrapper.__name__ = op
        return wrapper

    for op in _COLLECTIVE_OPS:
        _orig["lax." + op] = getattr(jax.lax, op)
        setattr(jax.lax, op, _probed(op))
    _installed = True


def _note_collective(op: str, axis_name: Any) -> None:
    """Record one traced collective: append (op, axis) and roll the CRC.
    Runs at TRACE time inside jit, which is host-side and sync-free."""
    axis = repr(axis_name)
    _collective_seq.append((op, axis))
    prev = _collective_crcs[-1] if _collective_crcs else 0
    step = ("%s@%s" % (op, axis)).encode("utf-8")
    _collective_crcs.append(zlib.crc32(step, prev) & 0xFFFFFFFF)


def collective_sequence() -> List[Tuple[str, str]]:
    """The (op, axis) pairs this process has traced, in order."""
    return list(_collective_seq)


def collective_fingerprint() -> Tuple[int, int]:
    """(count, rolling CRC of the full sequence) — cheap equality probe."""
    return (len(_collective_seq),
            _collective_crcs[-1] if _collective_crcs else 0)


def _fingerprint_vector() -> "Any":
    """[count, crc_1..crc_K]: the per-rank row exchanged by the check.
    Slot i holds the CRC of the first i+1 ops (0 when fewer were traced),
    so the first differing slot IS the first divergent op index."""
    import numpy as np

    vec = np.zeros((_FP_SLOTS + 1,), dtype=np.uint32)
    vec[0] = min(len(_collective_seq), np.iinfo(np.uint32).max)
    for i, crc in enumerate(_collective_crcs[:_FP_SLOTS]):
        vec[1 + i] = crc
    return vec


def check_collective_order(gather_fn: Optional[Callable] = None) -> None:
    """Cross-check the traced collective sequence against every rank.

    Rides the elastic heartbeat's sync slot (heartbeat_sync calls this
    when the sanitizer is on and the world is multi-process); tests call
    it directly. `gather_fn(vec) -> [world, len(vec)]` defaults to
    `multihost_utils.process_allgather` — inject a fake for single-process
    tests. No-op when disabled or when the gathered world is 1.

    Raises CollectiveOrderError(rank, first_divergent_op) on the first
    rank whose prefix fingerprints disagree with any other rank's.
    """
    if not enabled():
        return
    import numpy as np

    mine = _fingerprint_vector()
    if gather_fn is None:
        import jax
        from jax.experimental import multihost_utils

        if jax.process_count() <= 1:
            return
        rank = jax.process_index()
        rows = np.asarray(multihost_utils.process_allgather(mine))
    else:
        import jax

        rank = int(getattr(jax, "process_index", lambda: 0)())
        rows = np.asarray(gather_fn(mine))
    if rows.ndim != 2 or rows.shape[0] <= 1:
        return
    for other in range(rows.shape[0]):
        if np.array_equal(rows[other], mine):
            continue
        # first prefix slot (op index) where this rank and `other` split
        div = None
        for i in range(_FP_SLOTS):
            if rows[other][1 + i] != mine[1 + i]:
                div = i
                break
        if div is None:
            # prefixes agree through every slot: the counts differ
            div = min(int(mine[0]), int(rows[other][0]))
        if div < len(_collective_seq):
            op = "%s@%s" % _collective_seq[div]
        else:
            op = "<none: this rank traced %d collective(s), rank %d "\
                 "traced %d>" % (int(mine[0]), other, int(rows[other][0]))
        raise CollectiveOrderError(
            "collective order divergence: rank %d and rank %d traced "
            "different collective sequences, first divergent op #%d is "
            "%s on this rank — every rank must issue the same collectives "
            "in the same order or the mesh deadlocks (graftlint R12 is "
            "the static form of this check)" % (rank, other, div, op),
            rank=rank, first_divergent_op=op)


def guard(fn: Callable, donate: Sequence[int], site: str) -> Callable:
    """Wrap a donating dispatch so its donated args are poisoned after use.

    `donate` lists the POSITIONAL indices the jit donates (its
    donate_argnums); `site` names the dispatch for the eventual error.
    Identity when the sanitizer is off. Args that reappear in the output
    pytree (possible when XLA aliases through) are left alone.
    """
    if not enabled():
        return fn
    _install()
    import jax

    def wrapper(*args, **kwargs):
        out = fn(*args, **kwargs)
        out_ids = {id(leaf) for leaf in jax.tree_util.tree_leaves(out)}
        for i in donate:
            if i >= len(args):
                continue
            arr = args[i]
            if isinstance(arr, jax.Array) and id(arr) not in out_ids:
                # when the jit really donated (TPU, or CPU backends that
                # honor it) the buffer is ALREADY deleted — registering it
                # upgrades jax's generic "Array has been deleted" into an
                # error naming the donation site; on backends that ignore
                # donation, delete() poisons it ourselves (async-safe: the
                # runtime holds the buffer until in-flight consumers
                # finish)
                if not arr.is_deleted():
                    arr.delete()
                _poisoned[id(arr)] = (arr, site)
        return out

    return wrapper
