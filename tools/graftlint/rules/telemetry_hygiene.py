"""R9 telemetry-hygiene: hot-path event emission must be guard-gated.

`telemetry.emit()` is a single module-global None-check when no session is
active — but only AFTER its arguments are evaluated. An unguarded

    telemetry.emit("tree_wave", waves=int(n_waves), ...)

in a per-wave or per-chunk loop builds the whole payload dict (and any
device syncs hiding in the field expressions) on EVERY trip, telemetry on
or off — exactly the overhead the <1% claim forbids. In the hot-path set
(R5's scope: treelearner/, parallel/, ops/predict.py) every `*.emit(...)`
call on a telemetry object must sit under an `if` whose test references
`enabled` (idiomatically `if telemetry.enabled():`). The always-cheap
counter APIs (`global_timer.add_count` / `set_count`) need no guard and
are the right tool for per-wave integers.
"""
from __future__ import annotations

import ast
from typing import Iterable, List

from ..core import Package, Violation, dotted_name
from .base import Rule


def _test_mentions_enabled(test: ast.AST) -> bool:
    for node in ast.walk(test):
        if isinstance(node, ast.Name) and "enabled" in node.id:
            return True
        if isinstance(node, ast.Attribute) and "enabled" in node.attr:
            return True
    return False


def _emit_calls_with_guards(tree: ast.AST):
    """Yield (call_node, guarded) for every telemetry-style emit call;
    guarded = an ancestor `if`/ternary whose test references `enabled`."""
    def walk(node: ast.AST, guarded: bool):
        for child in ast.iter_child_nodes(node):
            child_guarded = guarded
            if isinstance(child, ast.If) and _test_mentions_enabled(
                    child.test):
                child_guarded = True
            if isinstance(child, ast.IfExp) and _test_mentions_enabled(
                    child.test):
                child_guarded = True
            if isinstance(child, ast.Call):
                # `from .. import telemetry; telemetry.emit(...)` is the
                # package idiom; a bare aliased `emit(...)` is ambiguous
                # (logging.Handler.emit) — keep the rule conservative
                if dotted_name(child.func).endswith("telemetry.emit"):
                    yield child, child_guarded
            yield from walk(child, child_guarded)
    yield from walk(tree, False)


class TelemetryHygieneRule(Rule):
    name = "telemetry-hygiene"
    code = "R9"
    description = ("telemetry.emit() in a hot-path file outside an "
                   "`if ...enabled...:` guard — payload construction runs "
                   "even with telemetry off (use the counter APIs or guard "
                   "the emission)")
    scope_prefixes = ("treelearner/", "parallel/", "serving/", "streaming/")
    # exposition sits on the scrape path: a /metrics render runs with
    # telemetry off too, so unguarded emits there cost every caller, not
    # just telemetry users. tracing.py
    # is IN scope on purpose: its recorder append (tracing.note) is the
    # one sanctioned unguarded hot-path emit — O(1), allocation-bounded,
    # no I/O — so any telemetry.emit added there must still be guarded.
    scope_exact = ("ops/predict.py", "exposition.py", "tracing.py")

    def check(self, pkg: Package) -> Iterable[Violation]:
        out: List[Violation] = []
        for ctx in self.scoped(pkg):
            for call, guarded in _emit_calls_with_guards(ctx.tree):
                if guarded:
                    continue
                out.append(self.violation(
                    ctx, call,
                    "telemetry.emit() outside an enabled-guard in a "
                    "hot-path file — the event payload is built on every "
                    "call even when telemetry is off; wrap it in "
                    "`if telemetry.enabled():` or publish the figure "
                    "through global_timer.add_count/set_count instead"))
        return out
