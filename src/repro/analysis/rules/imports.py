"""R6xx — import-time compute: keep `import repro` free of device work.

R601: a module-level statement (or a function default argument) calls
      into `jnp.*`: building arrays at import time initializes the
      backend, allocates on whatever device is default, and runs BEFORE
      any mesh/sharding/flag setup the launcher does — the classic "works
      in the test, hangs on the pod" bug. Pure dtype references
      (`jnp.float32`) are attributes, not calls, and stay legal.
R602: device-topology probes at import time (`jax.devices()`,
      `jax.device_count()`, `jax.local_devices()`,
      `jax.default_backend()`): they force backend initialization and
      pin the process to whatever topology existed at import, breaking
      late `XLA_FLAGS`/mesh configuration (the multi-device CI forces 8
      host devices AFTER deciding to import).
R603: process-wide JAX configuration at import time (`jax.config.update`,
      `enable_compile_cache`): importing a module must not move every
      other caller's compile cache or flags; entry points set them at
      start-up (`repro.launch.compile_cache`).
"""

from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis.findings import Finding
from repro.analysis.rules import (
    ModuleContext,
    dotted_name,
    rule,
)

_DEVICE_PROBES = {
    "jax.devices", "jax.device_count", "jax.local_devices",
    "jax.local_device_count", "jax.default_backend", "jax.process_index",
}


_PROCESS_CONFIG = {
    "jax.config.update", "enable_compile_cache",
    "compile_cache.enable_compile_cache",
}


def _jnp_call(node: ast.Call) -> bool:
    name = dotted_name(node.func)
    return name.startswith("jnp.") or name.startswith("jax.numpy.")


def _module_level_exprs(tree: ast.Module) -> Iterator[ast.expr]:
    """Expressions evaluated at import: module-level statements (descending
    through top-level if/try bodies, NOT into defs/classes) plus every
    function's default-argument expressions."""
    stack: list[ast.stmt] = list(tree.body)
    while stack:
        stmt = stack.pop()
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield from stmt.args.defaults
            yield from (d for d in stmt.args.kw_defaults if d is not None)
            # the body only runs when called: don't descend
            continue
        if isinstance(stmt, ast.ClassDef):
            stack.extend(stmt.body)  # class bodies DO run at import
            continue
        for attr in ("body", "orelse", "finalbody", "handlers"):
            sub = getattr(stmt, attr, None)
            if isinstance(sub, list):
                for s in sub:
                    if isinstance(s, ast.excepthandler):
                        stack.extend(s.body)
                    elif isinstance(s, ast.stmt):
                        stack.append(s)
        for field in ("value", "test", "iter", "targets", "target"):
            val = getattr(stmt, field, None)
            if isinstance(val, ast.expr):
                yield val
            elif isinstance(val, list):
                yield from (v for v in val if isinstance(v, ast.expr))


@rule("R601", "import-time-jnp-compute")
def check_import_time_compute(ctx: ModuleContext) -> Iterator[Finding]:
    """Module-scope / default-arg jnp calls run at import time."""
    for expr in _module_level_exprs(ctx.tree):
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call) and _jnp_call(sub):
                yield ctx.finding(
                    "R601", sub,
                    f"import-time jnp compute: "
                    f"'{dotted_name(sub.func)}(...)' runs (and initializes "
                    f"the backend) when the module is imported",
                    "build the array lazily inside the function that uses "
                    "it (or functools.lru_cache a builder)",
                )


@rule("R602", "device-probe-at-import")
def check_device_probe(ctx: ModuleContext) -> Iterator[Finding]:
    """Module-scope device/topology probes pin the backend at import."""
    for expr in _module_level_exprs(ctx.tree):
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call) and \
                    dotted_name(sub.func) in _DEVICE_PROBES:
                yield ctx.finding(
                    "R602", sub,
                    f"device probe '{dotted_name(sub.func)}()' at import "
                    f"time: forces backend init before XLA_FLAGS/mesh "
                    f"setup can happen",
                    "probe inside the function that needs the topology",
                )


@rule("R603", "process-config-at-import")
def check_config_at_import(ctx: ModuleContext) -> Iterator[Finding]:
    """Module-scope JAX configuration changes every importer's process."""
    for expr in _module_level_exprs(ctx.tree):
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call) and \
                    dotted_name(sub.func) in _PROCESS_CONFIG:
                yield ctx.finding(
                    "R603", sub,
                    f"'{dotted_name(sub.func)}()' at import time changes "
                    f"the JAX configuration of every process that imports "
                    f"this module",
                    "call it at start-up, inside the entry point's main()",
                )
