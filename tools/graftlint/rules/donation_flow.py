"""R10 use-after-donation: a read of a binding after its buffer was donated.

`donate_argnums` / `donate_argnames` / pallas `input_output_aliases` hand
the input buffer to XLA for reuse: after the dispatch returns, the
caller's reference points at memory the output may already occupy. On CPU
donation is silently ignored, so the bug ships green and detonates on the
TPU — the exact trap the `donate_argnums=(0,1,2)` device learner can
grow, and a pallas_call that aliases an input to an output with it.

The pass finds donating call sites through the package call graph, so
every dispatch shape the codebase actually uses is covered:

* decorator donation (`@partial(jax.jit, donate_argnums=(0,))`) on a
  directly-called function, cross-module included;
* `g = jax.jit(f, donate_argnums=...)` assignment aliases (module-level
  or local);
* factory products: `self._grow_fn(key)(bins, gh, ...)` where the factory
  returns `jax.jit(shard_map(body), donate_argnums=(0,1,2))` — partial()
  offsets shift the donated positions;
* `pallas_call(kernel, ..., input_output_aliases={4: 0})(args)` with a
  literal dict (a dynamically-built dict degrades to no-check, not to a
  false positive);
* interprocedural flow: a function that forwards its own parameter into a
  donated position donates that parameter, so ITS callers are checked at
  their own call sites (fixpoint over the graph, cycles safe).

Tracked bindings are bare names and `self.attr` chains. Subscripts
(`self.score[0]`) are deliberately untracked: indexing a jax array makes
a fresh buffer, which is the package's compliant donation idiom — the
caller keeps the container, donates the temp. A read is flagged when it
follows the donating call in source order with no intervening rebinding
(inside a loop, any read in the loop body counts unless the binding is
reassigned somewhere in the loop — the donated object is dead on the
next iteration too).
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..callgraph import (CallGraph, Node, _own_calls, _own_statements,
                         get_callgraph)
from ..core import Package, Violation, dotted_name, keyword_arg
from .base import Rule

_LOOPS = (ast.For, ast.AsyncFor, ast.While)
# fresh-buffer constructors: donating their result donates a temp
_FRESH_CALLS = {"copy", "asarray", "array", "zeros", "ones", "full",
                "empty", "zeros_like", "ones_like"}


def _binding_key(expr: ast.AST) -> Optional[str]:
    """'name' for bare names, 'self.attr[.attr...]' for attribute chains
    rooted at a name. Anything else (subscripts, calls) is not a binding
    this pass tracks."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        base = _binding_key(expr.value)
        return base + "." + expr.attr if base else None
    return None


def _pallas_donated(call: ast.Call) -> Tuple[int, ...]:
    """Donated positions of a `pallas_call(...)(args)` dispatch via a
    LITERAL input_output_aliases dict. Non-literal forms return ()."""
    inner = call.func
    if not isinstance(inner, ast.Call):
        return ()
    if dotted_name(inner.func).rsplit(".", 1)[-1] != "pallas_call":
        return ()
    aliases = keyword_arg(inner, "input_output_aliases")
    if not isinstance(aliases, ast.Dict):
        return ()
    out: List[int] = []
    for k in aliases.keys:
        if isinstance(k, ast.Constant) and isinstance(k.value, int):
            out.append(k.value)
        else:
            return ()
    return tuple(sorted(out))


class DonationFlowRule(Rule):
    name = "use-after-donation"
    code = "R10"
    description = ("binding read after its buffer was donated to a jit/"
                   "pallas dispatch (donate_argnums / input_output_aliases)")
    scope_prefixes = ("treelearner/", "models/", "parallel/", "ops/",
                      "streaming/")
    whole_program = True

    def check(self, pkg: Package) -> Iterable[Violation]:
        graph = get_callgraph(pkg)
        summaries = self._param_summaries(graph)
        out: List[Violation] = []
        for node in graph.nodes.values():
            if node.node is None:
                continue
            if not any(node.ctx is c for c in self.scoped(pkg)):
                continue
            out.extend(self._check_function(graph, node, summaries))
        return out

    # -------------------------------------------------- donation sites

    def _donated_positions(self, graph: CallGraph, node: Node,
                           call: ast.Call,
                           summaries: Dict[str, Set[int]]) -> Tuple[int, ...]:
        """Positional indices of `call`'s own args whose buffers the call
        donates (wrapper offsets already applied)."""
        positions: Set[int] = set()
        pallas = _pallas_donated(call)
        positions.update(pallas)
        for ref in graph.resolve_call(node, call):
            if ref.target is None:
                continue
            donate = set(ref.donate)
            for tq in ref.target.split("|"):
                donate |= summaries.get(tq, set())
            for pos in donate:
                arg_idx = pos - ref.offset
                if 0 <= arg_idx < len(call.args):
                    positions.add(arg_idx)
        return tuple(sorted(positions))

    def _param_summaries(self, graph: CallGraph) -> Dict[str, Set[int]]:
        """qual -> parameter positions the function (transitively) passes
        into a donated slot. Fixpoint; cycles converge because the sets
        only grow."""
        summaries: Dict[str, Set[int]] = {}
        params: Dict[str, List[str]] = {}
        for q, node in graph.nodes.items():
            if node.node is None:
                continue
            a = node.node.args
            names = [p.arg for p in list(a.posonlyargs) + list(a.args)]
            if node.cls is not None and names and names[0] in ("self", "cls"):
                names = names[1:]  # callers don't pass the receiver
            params[q] = names
        for _ in range(20):
            changed = False
            for q, node in graph.nodes.items():
                if node.node is None:
                    continue
                my_params = params.get(q, [])
                if not my_params:
                    continue
                for call in _own_calls(node.node):
                    donated = self._donated_positions(graph, node, call,
                                                      summaries)
                    for idx in donated:
                        arg = call.args[idx]
                        if isinstance(arg, ast.Name) \
                                and arg.id in my_params:
                            p = my_params.index(arg.id)
                            if p not in summaries.setdefault(q, set()):
                                summaries[q].add(p)
                                changed = True
            if not changed:
                break
        return summaries

    # ---------------------------------------------------------- checking

    def _check_function(self, graph: CallGraph, node: Node,
                        summaries: Dict[str, Set[int]]) -> List[Violation]:
        out: List[Violation] = []
        body = node.node
        loops = [s for s in _own_statements(body) if isinstance(s, _LOOPS)]

        def enclosing_loops(stmt: ast.AST) -> List[ast.AST]:
            return [lp for lp in loops
                    if any(sub is stmt for sub in ast.walk(lp))]

        for call in _own_calls(body):
            donated = self._donated_positions(graph, node, call, summaries)
            if not donated:
                continue
            call_loops = enclosing_loops(call)
            for idx in donated:
                arg = call.args[idx]
                if isinstance(arg, ast.Call):
                    last = dotted_name(arg.func).rsplit(".", 1)[-1]
                    if last in _FRESH_CALLS:
                        continue  # jnp.copy(...) temp: the compliant idiom
                key = _binding_key(arg)
                if key is None:
                    continue  # subscript / expression: fresh buffer
                out.extend(self._reads_after(node, body, call, call_loops,
                                             key, idx))
        return out

    def _reads_after(self, node: Node, body: ast.AST, call: ast.Call,
                     call_loops: Sequence[ast.AST], key: str,
                     idx: int) -> List[Violation]:
        rebind_lines = self._rebind_lines(body, key)
        call_end = getattr(call, "end_lineno", call.lineno)
        out: List[Violation] = []
        for expr in _own_statements(body):
            if not isinstance(expr, (ast.Name, ast.Attribute)):
                continue
            if not isinstance(getattr(expr, "ctx", None), ast.Load):
                continue
            if _binding_key(expr) != key:
                continue
            line = expr.lineno
            in_call = call.lineno <= line <= call_end
            after = line > call_end
            same_loop = any(any(sub is expr for sub in ast.walk(lp))
                            for lp in call_loops)
            if in_call:
                continue
            if not after and not same_loop:
                continue
            if same_loop and not after:
                # earlier in the loop body: dead on the NEXT iteration
                # unless something rebinds the name within the loop
                lp_lines = [r for r in rebind_lines
                            if any(self._line_in(lp, r)
                                   for lp in call_loops)]
                if lp_lines:
                    continue
            elif any(call.lineno <= r <= line for r in rebind_lines):
                # rebound between donation and read — including by the
                # assignment consuming the call itself (`buf = f(buf)`,
                # the donate-and-replace idiom): the old binding is dead
                # once that statement completes
                continue
            out.append(self.violation(
                node.ctx, expr,
                "%r is read here but its buffer was donated at line %d "
                "(arg %d of the dispatch) — on TPU the memory may already "
                "hold the output; copy before donating or rebind first"
                % (key, call.lineno, idx)))
        return out

    @staticmethod
    def _line_in(stmt: ast.AST, line: int) -> bool:
        return stmt.lineno <= line <= getattr(stmt, "end_lineno",
                                              stmt.lineno)

    @staticmethod
    def _rebind_lines(body: ast.AST, key: str) -> List[int]:
        lines: List[int] = []
        for stmt in _own_statements(body):
            targets: List[ast.AST] = []
            if isinstance(stmt, ast.Assign):
                targets = list(stmt.targets)
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                targets = [stmt.target]
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                targets = [stmt.target]
            for tgt in targets:
                for sub in ast.walk(tgt):
                    if _binding_key(sub) == key:
                        lines.append(stmt.lineno)
        return lines
