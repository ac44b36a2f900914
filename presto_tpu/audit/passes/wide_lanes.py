"""K001: wide-lane escapes -- 64-bit avals CREATED from all-narrow
inputs, outside the sanctioned widening kernels.

This is the IR-level ground truth behind tpulint's W001: after
narrow-width execution stages every range-proven column at int32 or
less, any eqn that manufactures an int64/uint64/float64 output from
inputs that are ALL narrower is a lane someone widened -- either a
sanctioned exactness site (int128 limb math, 64-bit key/order words,
count accumulators) or an escape that doubles HBM traffic on v5e.

Wide-in/wide-out eqns are deliberately NOT findings: wideness entering
the program through staged inputs (int128 hi/lo columns, BIGINT lanes
the width-inference layer could not narrow) was sanctioned at staging
time by the planner's range guard, and limb math flowing those lanes
through is the exactness contract, not an escape. The pass looks for
the moment narrow data turns wide IN-IR.

The whitelist mirrors W001's ``WIDE_OK_FUNCS`` (same spirit, same
granularity: enclosing function, matched against the eqn's provenance)
extended with the sites only visible at IR level: the decimal compare/
rescale helpers in expr/functions.py widen narrowed lanes before exact
scaled-int64 arithmetic, and the planner's row-id/grouping-id iotas
are logical BIGINT columns.
"""

from __future__ import annotations

from typing import Dict, List, Set

from ..core import CALL_PRIMITIVES, AuditPass, KernelIR, register

__all__ = ["WideLaneEscapePass", "WIDE_OK_FILES", "WIDE_OK_SITES"]

# whole files where 64-bit lanes ARE the contract: the int128 limb
# kernels (every value is an (int64 hi, uint64 lo) pair by definition)
WIDE_OK_FILES: Set[str] = {"int128.py"}

# (basename -> enclosing functions) sanctioned to create 64-bit lanes
# from narrow inputs; matched against the eqn's full def stack like
# W001 matches its AST stack
WIDE_OK_SITES: Dict[str, Set[str]] = {
    # exact accumulators / packed order words (W001's whitelist, seen
    # from the IR side)
    "aggregation.py": {
        "_fused_limb_sums", "_limb_matmul_sum", "_seg_add", "_seg_count",
        "_sum128", "_SegSumPool.add", "_seg_total", "_padded_cumsum",
        "_acc_columns", "_sorted_states", "finalize_states",
        "finalize_variance", "hll_estimate", "_group_by_sorted",
        "_argbest", "_hll_registers_from_values", "_seg_scan_extreme",
        "_seg_extreme_at", "group_by", "merge_partials",
    },
    "keys.py": {"_fixed_words", "key_words", "_string_words"},
    "join.py": {"_pack_ranks", "hash_join", "_probe_slots",
                "semi_join_mask"},
    "window.py": {"window", "_seg_search", "_range_extreme"},
    # decimal comparison/arithmetic widens narrowed lanes to the exact
    # scaled-int64 (or int128 limb) domain before comparing -- the
    # "compute sites widen before arithmetic" half of the narrow-width
    # contract (plan/widths.py)
    "functions.py": {"_as128", "_as128_at_scale", "_binary_cmp",
                     "_cmp_values", "_multiply", "_divide128", "_civil",
                     "_decimal_round", "_date_arith",
                     # the $hashValue analog: a 64-bit hash IS the
                     # contract partitioned exchanges route by
                     "hash64_block", "_mix64"},
    # range-exchange splitter sampling packs order words and sample
    # positions in 64 bits (position arithmetic (2s-1)*count must not
    # wrap at large per-worker counts)
    # ... and a routed batch's 64-bit lanes travel as two 32-bit words
    # each: putting them together again makes the lane the batch had
    "exchange.py": {"exchange_by_range", "exchange_by_hash",
                    "_pack_lanes", "_pack_lanes.unpack"},
    # row-id / grouping-set-id iotas are logical BIGINT output columns
    # (AssignUniqueIdNode / GroupIdNode lowering)
    "planner.py": {"compile_plan"},
}

_WIDE = 8  # itemsize threshold: int64/uint64/float64


def _dtype(v):
    return getattr(getattr(v, "aval", None), "dtype", None)


def _is_wide(dt) -> bool:
    return dt is not None and dt.kind in "iuf" and dt.itemsize >= _WIDE


def _site_allowed(kernel: KernelIR, eqn) -> bool:
    src, context, _line = kernel.site(eqn)
    base = src.rsplit("/", 1)[-1]
    if base in WIDE_OK_FILES:
        return True
    allowed = WIDE_OK_SITES.get(base)
    if not allowed:
        return False
    if context in allowed:
        return True
    stack = kernel.site_stack(eqn)
    return any(name in allowed for name in stack)


@register
class WideLaneEscapePass(AuditPass):
    code = "K001"
    name = "wide-lane-escape"
    description = ("64-bit avals created from all-narrow inputs outside "
                   "the whitelisted limb/key/accumulator kernels (the "
                   "IR ground truth behind W001)")

    def run(self, kernel: KernelIR) -> List:
        findings = []
        for _jx, eqn in kernel.eqns():
            prim = str(eqn.primitive)
            if prim in CALL_PRIMITIVES:
                continue  # creation sites live inside the sub-jaxpr
            out_dts = [_dtype(o) for o in eqn.outvars]
            if not any(_is_wide(d) for d in out_dts):
                continue
            in_dts = [_dtype(i) for i in eqn.invars]
            if any(_is_wide(d) for d in in_dts):
                continue  # wideness flowed in; sanctioned at staging
            if _site_allowed(kernel, eqn):
                continue
            wide = next(d for d in out_dts if _is_wide(d))
            findings.append(kernel.finding(
                "K001", eqn,
                f"{wide} lanes created by `{prim}` from all-narrow "
                f"inputs -- a wide-lane escape narrow-width execution "
                f"pays for in HBM traffic; widen at a whitelisted "
                f"exactness site or keep the lane narrow"))
        return findings
