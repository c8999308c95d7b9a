"""use-after-donate: donated buffers are dead after the dispatch call.

``build_sparse_event_scan`` compiles blocks with
``donate_argnums`` over the ``(W, S, y, ptr, ...)`` carry (PR 6): XLA reuses
the donated buffers for the outputs, so any read of the *argument* after the
call observes freed (or silently overwritten) memory.  The sanctioned shape
is the runner's self-clearing assignment::

    self.W, self.S, self.y, self._ptr = self._sparse(self.W, self.S, ...)

which this rule accepts (the assignment rebinds every donated name on the
same statement).  It flags

- ``use-after-donate``: a read of a donated argument name after the donating
  call, with no intervening rebind — including reads on error/warning paths,
  which is exactly where these bugs hide (the happy path rebinds, the
  ``raise``/log path reads the stale name);
- ``missing-alias-break``: a function that builds one of the donating block
  factories without the documented alias-break
  (``jax.tree.map(jnp.array, ...)``) — with ``same_init`` the snapshot S
  *is* W, and donating one buffer through two arguments is an XLA error.

Donating callees come from :class:`~repro.check.engine.CheckConfig`
(``donating_callees``) plus any locally visible ``jax.jit(...,
donate_argnums=...)`` / ``functools.partial(jax.jit, donate_argnums=...)``
definitions discovered in the module.
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
    walk_functions,
)

# Event priorities at identical source positions: the donating call *reads*
# its arguments legitimately, and the enclosing assignment rebinds them
# after the call returns.
_READ, _DONATE, _ASSIGN = 0, 1, 2


def _donate_argnums_from_call(call: ast.Call) -> Tuple[int, ...] | None:
    """``jax.jit(f, donate_argnums=(0, 1))`` -> (0, 1); None if absent."""
    callee = dotted_name(call.func)
    if callee not in ("jax.jit", "jit", "functools.partial", "partial"):
        return None
    if callee in ("functools.partial", "partial"):
        if not call.args:
            return None
        inner = dotted_name(call.args[0])
        if inner not in ("jax.jit", "jit"):
            return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            try:
                val = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                return None
            if isinstance(val, int):
                return (val,)
            if isinstance(val, (tuple, list)):
                return tuple(int(v) for v in val)
    return None


def _local_donating_names(tree: ast.Module) -> Dict[str, Tuple[int, ...]]:
    """Names bound in this module to donate-jitted callables.

    Catches ``fn = jax.jit(step, donate_argnums=(0,))`` assignments and
    ``@functools.partial(jax.jit, donate_argnums=(0,))``-decorated defs.
    """
    found: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            nums = _donate_argnums_from_call(node.value)
            if nums is not None:
                for target in node.targets:
                    name = dotted_name(target)
                    if name is not None:
                        found[name.rsplit(".", 1)[-1]] = nums
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    nums = _donate_argnums_from_call(dec)
                    if nums is not None:
                        found[node.name] = nums
    return found


def _assigned_names(target: ast.AST) -> List[str]:
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for elt in target.elts:
            out.extend(_assigned_names(elt))
        return out
    if isinstance(target, ast.Starred):
        return _assigned_names(target.value)
    name = dotted_name(target)
    return [name] if name is not None else []


class UseAfterDonateRule(Rule):
    rule_id = "use-after-donate"
    aliases = ("missing-alias-break",)

    def check(
        self, tree: ast.Module, path: str, config: CheckConfig
    ) -> List[Finding]:
        donating: Dict[str, Tuple[int, ...]] = dict(config.donating_callees)
        donating.update(_local_donating_names(tree))
        findings: List[Finding] = []
        for fn, _stack in walk_functions(tree):
            findings.extend(self._check_function(fn, path, donating, config))
        return findings

    # -- per-function linear taint walk ----------------------------------
    def _check_function(
        self,
        fn: ast.FunctionDef | ast.AsyncFunctionDef,
        path: str,
        donating: Dict[str, Tuple[int, ...]],
        config: CheckConfig,
    ) -> List[Finding]:
        # (line, col, priority, kind, payload) events in source order
        events: List[Tuple[int, int, int, str, object]] = []
        builder_call: ast.Call | None = None
        has_alias_break = False
        # Skip nested defs: their bodies execute at call time, not at this
        # position in the enclosing function's flow.
        own_nodes: List[ast.AST] = []
        stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            own_nodes.append(node)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(node))

        for node in own_nodes:
            if isinstance(node, (ast.Name, ast.Attribute)) and isinstance(
                getattr(node, "ctx", None), ast.Load
            ):
                name = dotted_name(node)
                if name is not None:
                    events.append((node.lineno, node.col_offset, _READ, "read", name))
            elif isinstance(node, ast.Call):
                suffix = call_suffix(node)
                if suffix in config.donating_builders:
                    builder_call = node
                if suffix == "map":
                    # jax.tree.map(jnp.array, ...) — the alias-break
                    first = dotted_name(node.args[0]) if node.args else None
                    if first is not None and first.rsplit(".", 1)[-1] in (
                        "array",
                        "asarray",
                        "copy",
                    ):
                        has_alias_break = True
                if suffix in donating:
                    nums = donating[suffix]
                    names = []
                    for idx in nums:
                        # a *args splat makes positional indices at or past
                        # it unresolvable — skip those donations
                        if any(
                            isinstance(a, ast.Starred)
                            for a in node.args[: idx + 1]
                        ):
                            continue
                        if idx < len(node.args):
                            arg_name = dotted_name(node.args[idx])
                            if arg_name is not None:
                                names.append(arg_name)
                    if names:
                        end = node.end_lineno or node.lineno
                        events.append(
                            (end, 10_000, _DONATE, "donate", (suffix, names))
                        )
            elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (
                    node.targets
                    if isinstance(node, ast.Assign)
                    else [node.target]
                )
                names = []
                for t in targets:
                    names.extend(_assigned_names(t))
                if names:
                    end = node.end_lineno or node.lineno
                    events.append((end, 20_000, _ASSIGN, "assign", names))
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                names = _assigned_names(node.target)
                if names:
                    events.append(
                        (node.lineno, node.col_offset, _ASSIGN, "assign", names)
                    )

        events.sort(key=lambda e: (e[0], e[2], e[1]))
        tainted: Dict[str, Tuple[str, int]] = {}  # name -> (callee, line)
        findings: List[Finding] = []
        for line, col, _prio, kind, payload in events:
            if kind == "read":
                name = payload
                if name in tainted:
                    callee, at = tainted[name]
                    findings.append(
                        Finding(
                            rule=self.rule_id,
                            path=path,
                            line=line,
                            col=col,
                            message=(
                                f"`{name}` was donated to `{callee}(...)` on "
                                f"line {at} and read here without a rebind; "
                                "donated buffers are invalid after dispatch"
                            ),
                        )
                    )
            elif kind == "assign":
                for name in payload:
                    tainted.pop(name, None)
            elif kind == "donate":
                callee, names = payload
                for name in names:
                    tainted[name] = (callee, line)

        if builder_call is not None and not has_alias_break:
            findings.append(
                Finding(
                    rule="missing-alias-break",
                    path=path,
                    line=builder_call.lineno,
                    col=builder_call.col_offset,
                    message=(
                        "this factory compiles a donate_argnums block over "
                        "(W, S, ...); break the same_init W/S alias with "
                        "`jax.tree.map(jnp.array, ...)` before first dispatch "
                        "(see runner._ensure_sparse)"
                    ),
                )
            )
        return findings
