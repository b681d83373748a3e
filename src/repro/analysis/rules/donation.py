"""R1xx — buffer donation hygiene.

R101: a variable passed in a donated position of a jitted call is read
again after the call without being rebound to the call's result. Donation
invalidates the input buffer (`donate_argnums`): the old array is
deleted and any later use raises — the streaming-session contract in this repo is
always `state = step(state, ...)`.
"""

from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis.findings import Finding
from repro.analysis.rules import (
    ModuleContext,
    assigned_names,
    is_jit_call,
    int_literals,
    jit_kwarg,
    names_loaded,
    rule,
    walk_functions,
)


def _donating_callables(tree: ast.Module) -> dict[str, list[int]]:
    """{bound name: donated positions} for `f = jax.jit(g, donate_argnums=...)`
    assignments anywhere in the module (literal positions only)."""
    out: dict[str, list[int]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        value = node.value
        if not (isinstance(target, ast.Name) and isinstance(value, ast.Call)
                and is_jit_call(value)):
            continue
        donated = jit_kwarg(value, "donate_argnums")
        positions = int_literals(donated) if donated is not None else None
        if positions:
            out[target.id] = positions
    return out


def _scan_block(body: list[ast.stmt], donating: dict[str, list[int]],
                ctx: ModuleContext) -> Iterator[Finding]:
    """Linear scan of one statement block: find donated-arg vars read after
    the donating call without rebinding."""
    for i, stmt in enumerate(body):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue  # nested scopes get their own scan via walk_functions
        for call in ast.walk(stmt):
            if not (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Name)
                    and call.func.id in donating):
                continue
            # every name rebound anywhere inside this statement subtree
            # counts: a loop whose body does `acc = step(acc, x)` rebinds
            # acc on the very statement that donates it
            rebound = set().union(*(
                assigned_names(s) for s in ast.walk(stmt)
                if isinstance(s, ast.stmt)
            ))
            donated_vars = {
                call.args[p].id
                for p in donating[call.func.id]
                if p < len(call.args) and isinstance(call.args[p], ast.Name)
            } - rebound
            if not donated_vars:
                continue
            for later in body[i + 1:]:
                rebinds = assigned_names(later)
                used = names_loaded(later) & donated_vars
                for name in sorted(used):
                    if name in rebinds:
                        # `x = f(x)` style statements consume then rebind:
                        # legitimate, and after them the name is live again
                        continue
                    yield ctx.finding(
                        "R101", later,
                        f"'{name}' was donated to '{call.func.id}' (donate_"
                        f"argnums) and is read again after the call",
                        "rebind the result (`x = step(x, ...)`) or drop "
                        "donate_argnums for this argument",
                    )
                donated_vars -= rebinds
                if not donated_vars:
                    break
        # nested blocks: recurse so donation inside loops/ifs is scanned too
        for attr in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, attr, None)
            if sub:
                yield from _scan_block(sub, donating, ctx)


@rule("R101", "donated-buffer-reuse")
def check_donated_reuse(ctx: ModuleContext) -> Iterator[Finding]:
    """Flag reads of a donated buffer after the donating jitted call."""
    donating = _donating_callables(ctx.tree)
    if not donating:
        return
    yield from _scan_block(ctx.tree.body, donating, ctx)
    for fn in walk_functions(ctx.tree):
        yield from _scan_block(fn.body, donating, ctx)
