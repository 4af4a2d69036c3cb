"""Jit-readiness rules (family ``jit``).

ROADMAP item 1 moves the hot O(n·K) paths — bitplane state helpers,
the budgeted-round matching engine, the fair-share water-fill — into
jitted JAX/Pallas kernels at n=5k-50k.  Everything a tracer cannot
stage must surface first: Python ``if``/``while`` branching on array
*values* (concretization error under ``jit``), ``float()``/``int()``/
``bool()``/``.item()`` host round-trips, and data-dependent Python
loops (``while alive.any()``, ``for i in np.flatnonzero(...)``) that
need ``lax.while_loop``/masking rewrites.

Findings here are ``warning`` severity: they are a *worklist* for the
scaling PR (emitted as the scorecard), not bugs — each target function
is correct today and baselined with that justification.
"""
from __future__ import annotations

import ast

from .findings import Finding
from .registry import AnalyzerRule, register_rule
from .resolve import call_name, import_aliases, unparse_trim

# Functions slated for the jitted engine: module-path suffix -> final
# qualname segments.  Extend this table as kernels are promoted.
JIT_TARGETS = {
    "repro/core/state.py": (
        "owner_windows", "eligible_supply", "candidate_columns"),
    "repro/net/fairshare.py": ("maxmin_rates", "transport",
                               "_maxmin_fill"),
    # Promoted kernels of the jitted engine (PR 8): staying on the
    # scorecard keeps host-coercion regressions visible.
    "repro/core/jit_engine.py": ("_slot_rounds", "_rank_counts",
                                 "_extract_ranked", "_kth_set_bit"),
}

_ARRAY_METHODS = {"any", "all", "sum", "min", "max", "item", "argmax",
                  "argmin", "nonzero", "prod", "mean"}
_ARRAY_PROPS = {"size", "shape", "ndim"}
_DATA_ITER = {"numpy.flatnonzero", "numpy.nonzero", "numpy.argwhere",
              "numpy.unique", "numpy.where"}


def jit_targets(ctx):
    """Yield (path, qualname, FunctionDef) for every slated function
    present in the analyzed set.  Under ``assume_library`` every module
    is matched against the union of slated names (rule fixtures)."""
    all_names = {n for names in JIT_TARGETS.values() for n in names}
    for path, tree in ctx.modules.items():
        if ctx.assume_library:
            wanted = all_names
        else:
            wanted = {n for suffix, names in JIT_TARGETS.items()
                      if path.endswith(suffix) for n in names}
        if not wanted:
            continue
        for qual, fn in ctx.walk_functions(tree):
            if qual.rsplit(".", 1)[-1] in wanted:
                yield path, qual, fn


def _array_tainted_names(fn, aliases) -> set:
    """One-level taint: locals assigned from an array-smelling
    expression (``t = min(tu.min(), td.min())``)."""
    tainted: set = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and _smells_array(
                node.value, aliases, tainted):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    tainted.add(tgt.id)
    return tainted


def _smells_array(node, aliases, tainted=frozenset()) -> bool:
    """Does this expression read an array value (method reductions,
    shape/size props, numpy calls, or tainted scalars)?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute):
            if sub.attr in _ARRAY_PROPS:
                return True
            # reduction METHOD call: x.any() — the Call parent decides,
            # but seeing the attribute inside a call test is enough
            if sub.attr in _ARRAY_METHODS:
                return True
        elif isinstance(sub, ast.Call):
            if call_name(sub, aliases).startswith("numpy."):
                return True
        elif isinstance(sub, ast.Name) and sub.id in tainted:
            return True
    return False


class _JitRuleBase(AnalyzerRule):
    family = "jit"
    severity = "warning"

    def check(self, ctx):
        out = []
        for path, tree in ctx.modules.items():
            aliases = import_aliases(tree)
            seen: set = set()
            for tpath, qual, fn in jit_targets(ctx):
                if tpath != path or (path, qual) in seen:
                    continue
                seen.add((path, qual))
                tainted = _array_tainted_names(fn, aliases)
                out.extend(self.check_function(path, qual, fn, aliases,
                                               tainted))
        return out

    def check_function(self, path, qual, fn, aliases, tainted):
        raise NotImplementedError

    def _finding(self, path, node, qual, kind, message, hint):
        return Finding(
            rule=self.rule, severity=self.severity, path=path,
            line=node.lineno, scope=qual,
            detail=f"{kind}:{unparse_trim(node, 40)}",
            message=message, hint=hint)


@register_rule
class ArrayBranchRule(_JitRuleBase):
    """JIT101: Python ``if`` branching on an array value inside a
    jit-slated function — concretizes the trace."""

    rule = "JIT101"
    title = "Python if on array value in jit-slated function"

    def check_function(self, path, qual, fn, aliases, tainted):
        out = []
        for node in ast.walk(fn):
            if isinstance(node, ast.If) and _smells_array(
                    node.test, aliases, tainted):
                out.append(self._finding(
                    path, node.test, qual, "if",
                    f"{qual}: `if {unparse_trim(node.test)}` branches "
                    f"on an array value — untraceable under jit",
                    "rewrite with jnp.where / lax.cond or hoist the "
                    "branch out of the kernel"))
        return out


@register_rule
class HostCoercionRule(_JitRuleBase):
    """JIT102: ``float()``/``int()``/``bool()``/``.item()`` host
    round-trips of computed (array-derived) values."""

    rule = "JIT102"
    title = "host scalar coercion in jit-slated function"

    def check_function(self, path, qual, fn, aliases, tainted):
        out = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node, aliases)
            is_cast = (name in ("float", "int", "bool")
                       and len(node.args) == 1)
            is_item = (isinstance(node.func, ast.Attribute)
                       and node.func.attr == "item")
            if is_cast:
                arg = node.args[0]
                computed = not isinstance(arg, (ast.Constant, ast.Name)) \
                    or (isinstance(arg, ast.Name) and arg.id in tainted)
                if not computed:
                    continue
            elif not is_item:
                continue
            out.append(self._finding(
                path, node, qual, "coerce",
                f"{qual}: `{unparse_trim(node)}` forces a device->host "
                f"sync — blocks tracing/async dispatch",
                "keep the value as a 0-d array inside the kernel; "
                "coerce only at the jit boundary"))
        return out


@register_rule
class DataDependentLoopRule(_JitRuleBase):
    """JIT103: data-dependent Python loops (``while`` on array state,
    ``while True``, ``for`` over nonzero/unique index sets)."""

    rule = "JIT103"
    title = "data-dependent Python loop in jit-slated function"

    def check_function(self, path, qual, fn, aliases, tainted):
        out = []
        for node in ast.walk(fn):
            if isinstance(node, ast.While):
                is_true = (isinstance(node.test, ast.Constant)
                           and node.test.value is True)
                if is_true or _smells_array(node.test, aliases, tainted):
                    out.append(self._finding(
                        path, node.test, qual, "while",
                        f"{qual}: `while {unparse_trim(node.test)}` — "
                        f"trip count depends on array data",
                        "rewrite as lax.while_loop or a bounded "
                        "fori_loop with masking"))
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                it = node.iter
                if (isinstance(it, ast.Call)
                        and call_name(it, aliases) in _DATA_ITER):
                    out.append(self._finding(
                        path, it, qual, "for",
                        f"{qual}: `for … in {unparse_trim(it)}` — "
                        f"iteration set is data-dependent",
                        "vectorize over the full axis with a mask "
                        "instead of gathering indices"))
        return out


def scorecard(ctx, findings) -> list:
    """Per-target jit-readiness rows: (path, qualname, {rule: count},
    ready?).  Functions with zero jit findings are kernel-ready."""
    by_scope: dict = {}
    for f in findings:
        if f.rule.startswith("JIT"):
            by_scope.setdefault((f.path, f.scope), {}).setdefault(
                f.rule, 0)
            by_scope[(f.path, f.scope)][f.rule] += 1
    rows = []
    for path, qual, _fn in sorted(jit_targets(ctx)):
        counts = by_scope.get((path, qual), {})
        rows.append((path, qual, counts, not counts))
    return rows
