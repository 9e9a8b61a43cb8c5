"""Device self time by the program's own scopes.

The program puts every phase of its hot paths under a `jax.named_scope`
with one prefix (`lgbm.`: lightgbm_tpu/utils/timer.py); the compiler keeps
the name stack in each operation's metadata and the profiler writes it
into the trace (benchmark/xplane.py reads it). An operation's scope is the
innermost `lgbm.` component of ITS OWN name stack: a fusion takes what its
own metadata carries, and an operation with no metadata, or none of the
program's scopes in it, is `unscoped`, never dropped and never guessed
from its name. Self time is `trace.self_times`' (a `while` does not count
its body again), the mean over the devices as `device.busy_s` is, so the
scopes' seconds and the unscoped seconds add up to the busy time.

  {"scope": "^lgbm\\.(select|replay)$", "per": "window_trees", "scale": 1000}
        seconds of the scopes the pattern matches, per a count, scaled
  {"unscoped": true}
        the share (%) of device busy time with no scope of the program's

A trace in which no operation carries a scope (a program from before the
scopes) reads nothing at all.
"""
import re

import trace as trace_mod
import xplane

SCOPE = re.compile(r"lgbm\.\w+")
UNSCOPED = "unscoped"


def scope_of(name_stack: str) -> str:
    hits = SCOPE.findall(name_stack)
    return hits[-1] if hits else UNSCOPED


def self_seconds(devices: dict, lo: float, hi: float, label) -> dict:
    """{label(name, name stack): self seconds} of the operations that start
    inside [lo, hi), the mean over the devices; `devices` as xplane.load
    gives them."""
    out = {}
    for events in devices.values():
        labelled = [(label(name, stack), start, dur)
                    for name, start, dur, stack in events]
        self_ns, _ = trace_mod.self_times(labelled, lo, hi)
        for key, ns in self_ns.items():
            out[key] = out.get(key, 0.0) + ns / 1e9 / len(devices)
    return out


def scope_seconds(devices: dict, lo: float, hi: float) -> dict:
    """{scope: self seconds}, `unscoped` among them."""
    return self_seconds(devices, lo, hi, lambda _, stack: scope_of(stack))


def unscoped_ops(devices: dict, lo: float, hi: float) -> list:
    """[(operation, self seconds)] of the unscoped operations, longest
    first: what PERF.md lists by name."""
    by_name = self_seconds(
        devices, lo, hi,
        lambda name, stack: name if scope_of(stack) == UNSCOPED else "")
    by_name.pop("", None)
    return sorted(by_name.items(), key=lambda kv: -kv[1])


def read(ctx, spec):
    if ctx.trace_summary() is None:
        return None
    raw = xplane.of(ctx)
    lo, hi = trace_mod.window_of(raw["host"])
    seconds = scope_seconds(raw["devices"], lo, hi)
    if not any(scope != UNSCOPED for scope in seconds):
        return None
    if spec.get("unscoped"):
        busy = sum(seconds.values())
        return 100.0 * seconds.get(UNSCOPED, 0.0) / busy if busy else None
    rx = re.compile(spec["scope"])
    hits = [s for scope, s in seconds.items()
            if scope != UNSCOPED and rx.search(scope)]
    per = ctx.counts.get(spec["per"]) if "per" in spec else 1
    if not hits or not per:
        return None
    return float(spec.get("scale", 1.0)) * sum(hits) / per
