"""R001: retrace / stale-cache-key risk in trace-reachable code.

The compiled-plan cache (exec/plan_cache.py) keys executables by plan
structure + mesh + a ``_kernel_mode()`` string built from the
registered kernel-form env knobs. Anything ELSE a traced function
reads from ambient process state -- an unregistered env var, the
clock, a random source, a mutable module global -- constant-folds into
the lowered program at trace time and then silently serves stale
behavior on every cache hit. This is exactly the bug class PR 2 fixed
by adding the kernel-mode envs to the cache key; R001 keeps the next
such knob from shipping unkeyed.

Rules over ``presto_tpu/ops/`` and ``presto_tpu/exec/``:

  1. ``os.environ.get/[...]`` / ``os.getenv`` reads anywhere in these
     modules must name an env var registered in
     ``exec.plan_cache.KERNEL_MODE_ENVS`` (ops modules run at trace
     time, so module- and function-level reads both bake into the
     traced program).
  2. Inside ``@jax.jit``-decorated functions: ``time.*`` /
     ``random.*`` / ``np.random.*`` calls constant-fold at trace time
     -- the cached executable replays one frozen sample forever.
  3. Inside ``@jax.jit``-decorated functions: reads of module-level
     MUTABLE globals (names bound to dict/list/set literals at module
     scope) -- mutating the global later does not retrace, so the
     compiled program keeps the capture-time contents.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from ..core import (Finding, LintPass, ModuleSource, dotted_context,
                    has_jit_decorator, register)

__all__ = ["RetracePass", "kernel_mode_envs"]

# fallback when exec.plan_cache cannot import (keeps the linter usable
# in stripped environments); the test suite pins this against the real
# KERNEL_MODE_ENVS so the two cannot drift silently
_KNOWN_KEYED_ENVS = ("PRESTO_TPU_SMALLG", "PRESTO_TPU_SMALLG_PALLAS",
                     "PRESTO_TPU_NARROW", "PRESTO_TPU_BF16",
                     "PRESTO_TPU_GROUPBY", "PRESTO_TPU_FUSION",
                     "PRESTO_TPU_KERNEL_AUDIT", "PRESTO_TPU_BATCHING",
                     "PRESTO_TPU_DONATION")

_ENV_ROOTS = ("os", "_os")
_CLOCK_CALLS = {("time", "time"), ("time", "monotonic"),
                ("time", "perf_counter"), ("time", "time_ns"),
                ("random", "random"), ("random", "randint"),
                ("random", "uniform"), ("random", "choice"),
                ("random", "shuffle"), ("random", "sample")}


def kernel_mode_envs() -> Tuple[str, ...]:
    """The env vars the plan cache keys on (single source of truth:
    exec.plan_cache.KERNEL_MODE_ENVS; falls back to the pinned copy
    when jax is unavailable to the lint process)."""
    try:
        from ...exec.plan_cache import KERNEL_MODE_ENVS
        return tuple(name for name, _default in KERNEL_MODE_ENVS)
    except Exception:  # pragma: no cover - stripped environments
        return _KNOWN_KEYED_ENVS


def _mutable_module_globals(tree: ast.Module) -> Set[str]:
    out: Set[str] = set()
    for stmt in tree.body:
        if not isinstance(stmt, ast.Assign):
            continue
        v = stmt.value
        mutable = isinstance(v, (ast.Dict, ast.List, ast.Set)) or (
            isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
            and v.func.id in ("dict", "list", "set"))
        if not mutable:
            continue
        for t in stmt.targets:
            if isinstance(t, ast.Name):
                out.add(t.id)
    return out


def _env_var_name(node: ast.Call) -> Optional[str]:
    if node.args and isinstance(node.args[0], ast.Constant) \
            and isinstance(node.args[0].value, str):
        return node.args[0].value
    return None


@register
class RetracePass(LintPass):
    code = "R001"
    name = "retrace-risk"
    description = ("ambient-state reads (unkeyed env vars, clocks, "
                   "randomness, mutable globals) baked into traced "
                   "programs")
    TARGETS = ("presto_tpu/ops/*.py", "presto_tpu/exec/*.py")

    def run(self, ms: ModuleSource) -> List[Finding]:
        keyed = set(kernel_mode_envs())
        mutable_globals = _mutable_module_globals(ms.tree)
        findings: List[Finding] = []
        stack: List[str] = []
        jit_depth = 0
        local_names: List[Set[str]] = []  # per-function locals/params

        def context() -> str:
            return dotted_context(stack)

        def emit(node: ast.AST, message: str) -> None:
            findings.append(ms.finding("R001", node, context(), message))

        def fn_locals(node) -> Set[str]:
            names: Set[str] = set()
            a = node.args
            for arg in [*a.posonlyargs, *a.args, *a.kwonlyargs,
                        *([a.vararg] if a.vararg else []),
                        *([a.kwarg] if a.kwarg else [])]:
                names.add(arg.arg)
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and \
                        isinstance(sub.ctx, ast.Store):
                    names.add(sub.id)
            return names

        class V(ast.NodeVisitor):
            def visit_FunctionDef(self, node):
                nonlocal jit_depth
                jitted = has_jit_decorator(node)
                stack.append(node.name)
                local_names.append(fn_locals(node))
                jit_depth += 1 if jitted else 0
                self.generic_visit(node)
                jit_depth -= 1 if jitted else 0
                local_names.pop()
                stack.pop()

            visit_AsyncFunctionDef = visit_FunctionDef

            def visit_ClassDef(self, node):
                stack.append(node.name)
                self.generic_visit(node)
                stack.pop()

            def visit_Call(self, node):
                fn = node.func
                # rule 1: env reads must be cache-keyed
                if isinstance(fn, ast.Attribute):
                    root = fn.value
                    if fn.attr == "get" and isinstance(root, ast.Attribute) \
                            and root.attr == "environ" \
                            and isinstance(root.value, ast.Name) \
                            and root.value.id in _ENV_ROOTS:
                        self._check_env(node)
                    elif fn.attr == "getenv" and \
                            isinstance(root, ast.Name) and \
                            root.id in _ENV_ROOTS:
                        self._check_env(node)
                    # rule 2: clocks/randomness under jit
                    elif jit_depth > 0 and isinstance(root, ast.Name) and \
                            (root.id, fn.attr) in _CLOCK_CALLS:
                        emit(node,
                             f"{root.id}.{fn.attr}() constant-folds at "
                             f"trace time; the cached executable "
                             f"replays one frozen sample forever")
                    elif jit_depth > 0 and isinstance(root, ast.Attribute) \
                            and root.attr == "random" \
                            and isinstance(root.value, ast.Name) \
                            and root.value.id in ("np", "numpy"):
                        emit(node,
                             f"np.random.{fn.attr}() constant-folds at "
                             f"trace time inside a jit'd function")
                self.generic_visit(node)

            def _check_env(self, node):
                var = _env_var_name(node)
                if var is not None and var in keyed:
                    return  # registered kernel-mode knob: cache-keyed
                shown = var or "<dynamic>"
                emit(node,
                     f"env read {shown!r} at trace/import time is "
                     f"invisible to the plan-cache key (register it in "
                     f"exec.plan_cache.KERNEL_MODE_ENVS or route it "
                     f"through the session)")

            def visit_Subscript(self, node):
                # os.environ["X"] reads (rule 1)
                v = node.value
                if isinstance(node.ctx, ast.Load) and \
                        isinstance(v, ast.Attribute) and \
                        v.attr == "environ" and \
                        isinstance(v.value, ast.Name) and \
                        v.value.id in _ENV_ROOTS:
                    var = None
                    if isinstance(node.slice, ast.Constant) and \
                            isinstance(node.slice.value, str):
                        var = node.slice.value
                    if var is None or var not in keyed:
                        emit(node,
                             f"env read {var or '<dynamic>'!r} at "
                             f"trace/import time is invisible to the "
                             f"plan-cache key")
                self.generic_visit(node)

            def visit_Name(self, node):
                # rule 3: mutable-global capture under jit
                if jit_depth > 0 and isinstance(node.ctx, ast.Load) \
                        and node.id in mutable_globals \
                        and not any(node.id in ls for ls in local_names):
                    emit(node,
                         f"mutable module global {node.id!r} captured "
                         f"by a jit'd function: later mutations never "
                         f"retrace the cached executable")
                self.generic_visit(node)

        V().visit(ms.tree)
        return findings
