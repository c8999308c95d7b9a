"""recompile churn: jit construction in loops, bad static args.

The sparse path's performance contract (PR 6) is one compiled block
program per bucket rung: shapes are fixed per rung by ``_bucket_cap``, so
the jit cache holds exactly one entry per (A, E) and nothing recompiles in
steady state.  Two anti-patterns silently break that:

- ``jit-in-loop``: constructing a jitted callable (``jax.jit(...)`` or
  ``functools.partial(jax.jit, ...)``) inside a ``for``/``while`` body —
  each iteration builds a fresh callable with an empty cache, so every
  call compiles;
- ``unhashable-static`` / ``loop-varying-static``: feeding a
  ``static_argnums`` position an unhashable value (list/dict/set literal,
  ``np.array``) — a ``TypeError`` at best, a per-call retrace at worst —
  or a loop variable, which compiles once per distinct iteration value.
  Static-jitted callables are discovered locally (same module), like the
  runner's ``jax.jit(init_metrics, static_argnums=0)``.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from repro.check.engine import (
    CheckConfig,
    Finding,
    Rule,
    call_suffix,
    dotted_name,
)

_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
               ast.SetComp, ast.GeneratorExp)
_UNHASHABLE_CALLS = {"array", "asarray", "zeros", "ones", "arange"}


def _is_jit_construction(call: ast.Call) -> bool:
    name = dotted_name(call.func)
    if name in ("jax.jit", "jit"):
        return True
    if name in ("functools.partial", "partial") and call.args:
        inner = dotted_name(call.args[0])
        return inner in ("jax.jit", "jit")
    return False


def _static_argnums(call: ast.Call) -> Tuple[int, ...] | None:
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            try:
                val = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                return None
            if isinstance(val, int):
                return (val,)
            if isinstance(val, (tuple, list)):
                return tuple(int(v) for v in val)
    return None


def _static_jitted_names(tree: ast.Module) -> Dict[str, Tuple[int, ...]]:
    """Locally visible name -> static arg positions of its jit."""
    found: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if not _is_jit_construction(node.value):
                continue
            nums = _static_argnums(node.value)
            if nums is None:
                continue
            for target in node.targets:
                name = dotted_name(target)
                if name is not None:
                    found[name.rsplit(".", 1)[-1]] = nums
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and _is_jit_construction(dec):
                    nums = _static_argnums(dec)
                    if nums is not None:
                        found[node.name] = nums
    return found


class RecompileChurnRule(Rule):
    rule_id = "jit-in-loop"
    aliases = ("unhashable-static", "loop-varying-static")

    def check(
        self, tree: ast.Module, path: str, config: CheckConfig
    ) -> List[Finding]:
        findings: List[Finding] = []
        static_names = _static_jitted_names(tree)

        def visit(node: ast.AST, loop_depth: int, loop_vars: Set[str]) -> None:
            for child in ast.iter_child_nodes(node):
                depth, lvars = loop_depth, loop_vars
                if isinstance(child, (ast.For, ast.AsyncFor)):
                    names = {
                        n.id
                        for n in ast.walk(child.target)
                        if isinstance(n, ast.Name)
                    }
                    depth, lvars = loop_depth + 1, loop_vars | names
                elif isinstance(child, ast.While):
                    depth = loop_depth + 1
                elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                        ast.Lambda)):
                    # a def inside a loop runs once per call of the outer
                    # fn, not per iteration of an enclosing textual loop
                    depth, lvars = 0, set()
                if isinstance(child, ast.Call):
                    self._check_call(
                        child, path, depth, lvars, static_names, findings
                    )
                visit(child, depth, lvars)

        visit(tree, 0, set())
        return findings

    def _check_call(
        self,
        call: ast.Call,
        path: str,
        loop_depth: int,
        loop_vars: Set[str],
        static_names: Dict[str, Tuple[int, ...]],
        findings: List[Finding],
    ) -> None:
        if _is_jit_construction(call) and loop_depth > 0:
            findings.append(
                Finding(
                    rule="jit-in-loop",
                    path=path,
                    line=call.lineno,
                    col=call.col_offset,
                    message=(
                        "jax.jit constructed inside a loop body: each "
                        "iteration gets a fresh callable with an empty "
                        "compile cache, so every call recompiles — hoist "
                        "the jit out of the loop"
                    ),
                )
            )
            return
        suffix = call_suffix(call)
        if suffix not in static_names:
            return
        for idx in static_names[suffix]:
            if idx >= len(call.args):
                continue
            arg = call.args[idx]
            if isinstance(arg, _UNHASHABLE) or (
                isinstance(arg, ast.Call)
                and (name := dotted_name(arg.func)) is not None
                and name.rsplit(".", 1)[-1] in _UNHASHABLE_CALLS
            ):
                findings.append(
                    Finding(
                        rule="unhashable-static",
                        path=path,
                        line=arg.lineno,
                        col=arg.col_offset,
                        message=(
                            f"static arg {idx} of `{suffix}` is unhashable "
                            "(list/dict/set/ndarray); static_argnums keys "
                            "the compile cache by hash — pass a tuple or "
                            "scalar"
                        ),
                    )
                )
            elif isinstance(arg, ast.Name) and arg.id in loop_vars:
                findings.append(
                    Finding(
                        rule="loop-varying-static",
                        path=path,
                        line=arg.lineno,
                        col=arg.col_offset,
                        message=(
                            f"static arg {idx} of `{suffix}` is the loop "
                            f"variable `{arg.id}`: every distinct value "
                            "compiles a fresh program (recompile churn); "
                            "make it a traced arg or hoist the distinct "
                            "values"
                        ),
                    )
                )
