"""R5 untimed-hot-func: big hot-path functions must feed the global timer.

Perf accounting is only trustworthy when it is complete: the
`device_hist_rows` counter proving the rows-in-leaf wave design is
O(selected rows) lives next to a `global_timer.scope("tree_device")`
wall-clock scope, and a 100-line helper that bypasses both is invisible
in every perf report. Any function of more than 50 source lines in
treelearner/, parallel/ or the serving hot path ops/predict.py must
reference `utils.timer.global_timer` (a scope, an add_count, anything) or
wear the `@timed(...)` decorator.

Exemptions, because they are structurally untimeable from the inside:
  * jit-decorated functions — host timers inside a traced body measure
    trace time once, then nothing; the call site owns the scope (that is
    exactly how grow_tree_on_device is accounted, device.py's
    `global_timer.scope("tree_device")`).
  * nested defs — they execute inside their parent's scope.
"""
from __future__ import annotations

import ast
from typing import Iterable, List

from ..core import Package, Violation, dotted_name
from .base import Rule, module_functions
from .jit_boundary import _is_jitted

_MAX_LINES = 50


def _uses_timer(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and node.id == "global_timer":
            return True
        if isinstance(node, ast.Attribute) \
                and dotted_name(node).endswith("global_timer"):
            return True
    for dec in getattr(fn, "decorator_list", []):
        name = dotted_name(dec.func if isinstance(dec, ast.Call) else dec)
        if name.endswith("timed"):
            return True
    return False


class TimerDisciplineRule(Rule):
    name = "untimed-hot-func"
    code = "R5"
    description = (">50-line function in treelearner/, parallel/, or "
                   "ops/predict.py without a global_timer scope/counter "
                   "(perf accounting gap)")
    scope_prefixes = ("treelearner/", "parallel/")
    scope_exact = ("ops/predict.py",)

    def check(self, pkg: Package) -> Iterable[Violation]:
        out: List[Violation] = []
        for ctx in self.scoped(pkg):
            for qual, fn in module_functions(ctx.tree):
                span = (fn.end_lineno or fn.lineno) - fn.lineno + 1
                if span <= _MAX_LINES:
                    continue
                if _is_jitted(fn):
                    continue  # traced body; the call site owns the scope
                if _uses_timer(fn):
                    continue
                out.append(self.violation(
                    ctx, fn,
                    "%r spans %d lines with no global_timer scope or "
                    "counter — its cost is invisible to perf reports "
                    "(wrap the hot section, decorate with @timed, or "
                    "suppress with the reason it is cold)" % (qual, span)))
        return out
