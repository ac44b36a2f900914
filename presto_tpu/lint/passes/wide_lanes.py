"""W001: hot-path kernel modules stay narrow-lane disciplined.

Narrow-width execution (plan/widths.py, PERF.md roofline) depends on
the hot-path kernels never silently re-widening lanes: on v5e an int64
lane is emulated as an i32 pair, so one accidental wide array doubles
the HBM traffic the narrowing PR exists to remove. Two rules:

  1. IMPLICIT-DTYPE array creation is banned everywhere in the target
     modules: under jax x64 (this engine enables it) ``jnp.arange(n)``
     silently makes int64 lanes and ``jnp.zeros(n)`` float64 lanes.
     Every zeros/ones/full/empty/arange/iota call must name its dtype.
  2. EXPLICIT int64 construction (``dtype=jnp.int64`` /
     ``.astype(jnp.int64)`` / ``jnp.int64(...)``) is allowed only
     inside whitelisted functions -- the limb-widening/accumulator/
     order-word sites where 64-bit math is the exactness contract, not
     an accident.

Originally shipped as ``scripts/check_no_wide_lanes.py`` over
aggregation.py/keys.py (PR 2); that script is now a thin shim over
this pass, and coverage extends to join.py, sort.py, and window.py.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from ..core import (Finding, LintPass, ModuleSource, dotted_context,
                    register)

__all__ = ["WideLanesPass", "scan_module"]

# array constructors that default to wide lanes under jax x64
# (jnp.array infers int64/float64 from python scalars the same way)
_CREATORS = {"zeros", "ones", "full", "empty", "arange", "array",
             "broadcasted_iota", "iota"}

# Functions where 64-bit lanes are the exactness contract, keyed by
# basename. New int64 in any OTHER hot-path function fails the check.
WIDE_OK_FUNCS: Dict[str, Set[str]] = {
    "aggregation.py": {
        # limb-widening / exact-accumulation sites
        "_fused_limb_sums", "_limb_matmul_sum", "_seg_add", "_seg_count",
        "_sum128", "_SegSumPool.add", "_seg_total", "_padded_cumsum",
        # int64 state tables / finalizers (G-sized, not row-sized)
        "_acc_columns", "_sorted_states", "finalize_states",
        "finalize_variance", "hll_estimate", "_group_by_sorted",
        # order-word / argbest reductions (uint64 words, int64 row ids)
        "_argbest", "_hll_registers_from_values", "_seg_scan_extreme",
        "_seg_extreme_at",
        # planner-facing glue
        "group_by", "merge_partials",
    },
    # keys.py widens VALUES to uint64 order words by design; int64
    # appears only as the cast-through in _fixed_words
    "keys.py": {"_fixed_words", "key_words", "_string_words"},
    # join row-id packing: build-side positions and packed rank words
    # are int64 by contract (row ids can exceed 2^31 at SF1k; the
    # packed (rank, pos) word needs the full 64 bits); the running
    # sum of what the probe rows emit and its total are int64
    # (`_probe_slots`, hash_join's probe side)
    "join.py": {"_pack_ranks", "hash_join", "_probe_slots",
                "semi_join_mask"},
    "sort.py": set(),
    # window positions/ranks/frame bounds are int64 row ids and exact
    # 64-bit accumulators (rank arithmetic, padded-cumsum frame totals)
    "window.py": {"window", "_seg_search", "_range_extreme"},
}


_func_name = dotted_context


def _is_int64_attr(node: ast.AST) -> bool:
    return isinstance(node, ast.Attribute) and node.attr in ("int64",)


def _is_int64(node: ast.AST) -> bool:
    """jnp.int64 / np.int64 attribute, or the "int64" string spelling
    (.astype("int64"), dtype="int64")."""
    return _is_int64_attr(node) or (
        isinstance(node, ast.Constant) and node.value == "int64")


def scan_module(ms: ModuleSource,
                whitelist: Optional[Set[str]] = None,
                code: str = "W001") -> List[Finding]:
    """The W001 rule engine over one parsed module. ``whitelist``
    overrides the per-basename WIDE_OK_FUNCS entry (the
    check_no_wide_lanes.py shim threads its own table through here)."""
    allowed = WIDE_OK_FUNCS.get(ms.basename, set()) \
        if whitelist is None else whitelist
    findings: List[Finding] = []
    stack: List[str] = []

    def in_allowed() -> bool:
        name = _func_name(stack)
        return name in allowed or bool(stack and stack[0] in allowed)

    def emit(node: ast.AST, message: str) -> None:
        findings.append(ms.finding(code, node, _func_name(stack), message))

    class V(ast.NodeVisitor):
        def visit_FunctionDef(self, node):
            stack.append(node.name)
            self.generic_visit(node)
            stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_ClassDef(self, node):
            stack.append(node.name)
            self.generic_visit(node)
            stack.pop()

        def visit_Call(self, node):
            fn = node.func
            # rule 1: jnp/np array creators must name a dtype
            if isinstance(fn, ast.Attribute) and fn.attr in _CREATORS \
                    and isinstance(fn.value, ast.Name) \
                    and fn.value.id in ("jnp", "np"):
                has_dtype = any(k.arg == "dtype" for k in node.keywords)
                # dtype may ride positionally: full(shape, fill, dtype)
                # and array(obj, dtype)
                if not has_dtype and fn.attr == "full" \
                        and len(node.args) >= 3:
                    has_dtype = True
                if not has_dtype and fn.attr == "array" \
                        and len(node.args) >= 2:
                    has_dtype = True
                if not has_dtype:
                    emit(node,
                         f"jnp.{fn.attr}() without an explicit dtype "
                         f"(implicit wide lanes under x64)")
            # rule 2: explicit int64 outside the whitelist -- as a
            # direct call, an astype argument (attribute or "int64"
            # string), or a positional dtype to a CREATOR (non-creator
            # calls like np.iinfo(np.int64) take dtypes without making
            # lanes, so only constructors are checked positionally)
            if _is_int64_attr(fn) and not in_allowed():
                emit(node, "jnp.int64(...) outside the whitelisted "
                           "limb-widening sites")
            if isinstance(fn, ast.Attribute) and fn.attr == "astype" \
                    and node.args and _is_int64(node.args[0]) \
                    and not in_allowed():
                emit(node, ".astype(int64) outside the whitelisted "
                           "limb-widening sites")
            if isinstance(fn, ast.Attribute) and fn.attr in _CREATORS \
                    and isinstance(fn.value, ast.Name) \
                    and fn.value.id in ("jnp", "np") \
                    and not in_allowed():
                for a in node.args:
                    if _is_int64_attr(a):
                        emit(node, "int64 passed as a positional dtype "
                                   "outside the whitelisted "
                                   "limb-widening sites")
            self.generic_visit(node)

        def visit_keyword(self, node):
            if node.arg == "dtype" and _is_int64(node.value) \
                    and not in_allowed():
                findings.append(Finding(
                    code=code, path=ms.rel_path,
                    line=getattr(node.value, "lineno", 0),
                    col=getattr(node.value, "col_offset", 0),
                    context=_func_name(stack),
                    message="dtype=int64 outside the whitelisted "
                            "limb-widening sites"))
            self.generic_visit(node)

    V().visit(ms.tree)
    return findings


@register
class WideLanesPass(LintPass):
    code = "W001"
    name = "wide-lanes"
    description = ("implicit-dtype array creation and un-whitelisted "
                   "int64 in hot-path kernel modules")
    TARGETS = ("presto_tpu/ops/aggregation.py",
               "presto_tpu/ops/keys.py",
               "presto_tpu/ops/join.py",
               "presto_tpu/ops/sort.py",
               "presto_tpu/ops/window.py")

    def run(self, module: ModuleSource) -> List[Finding]:
        return scan_module(module)
