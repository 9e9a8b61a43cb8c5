"""A scope's share of its roofline: the least time the chip could take for
the work the traffic kind counted under the metric's `work` (a
`(bytes, operations, operand)` triple in `ctx.counts`, from the shapes
alone: benchmark/work_rank.py) over the device self time of the scopes the
metric's `scope` matches. A trace without the scope, or a kind that
counted no such work, reads nothing."""
import re

import trace as trace_mod
import work
import xplane
from harness import load_module


def read(ctx, spec):
    needed = ctx.counts.get(spec["work"])
    if ctx.trace_summary() is None or not needed:
        return None
    trace_scope = load_module("readers", "trace_scope", ctx.roots)
    raw = xplane.of(ctx)
    lo, hi = trace_mod.window_of(raw["host"])
    rx = re.compile(spec["scope"])
    seconds = sum(s for scope, s in trace_scope.scope_seconds(
        raw["devices"], lo, hi).items() if rx.search(scope))
    if not seconds:
        return None
    least, _ = work.least_seconds(work.Work(*needed), ctx.device["kind"])
    return 100.0 * least / seconds
