"""K005: intermediate-footprint estimate vs budget.

A fused fragment program's peak live bytes -- staged inputs plus every
intermediate alive at the widest point of the schedule -- is what
actually has to fit HBM, and nothing at the AST or plan level sees it:
it emerges from the jaxpr's schedule. The estimate here walks eqns in
program order with a last-use liveness map (sub-jaxprs contribute
their own peak as a transient at the call site), the standard
linear-scan upper bound XLA's allocator will generally beat (it
reorders and fuses away intermediates) but never by orders of
magnitude on this engine's shapes.

Kernels whose estimate exceeds the kernel's budget
(``KernelIR.footprint_budget_bytes``; 0 = report-only) are findings.
Whatever the verdict, the estimate lands in ``KernelIR.notes
["peak_bytes_estimate"]`` so the staging hook can feed it to
``exec/memory.py``'s pool accounting (``MemoryPool.note_audit_
estimate``) and QueryStats.
"""

from __future__ import annotations

import itertools
from typing import List

from ..core import AuditPass, KernelIR, eqn_subjaxprs, register

__all__ = ["FootprintPass", "estimate_peak_bytes"]


def _aval_bytes(v) -> int:
    a = getattr(v, "aval", None)
    shape = getattr(a, "shape", None)
    dt = getattr(a, "dtype", None)
    if shape is None or dt is None:
        return 0
    n = 1
    for s in shape:
        try:
            n *= int(s)
        except (TypeError, ValueError):  # symbolic dims: count as 1
            pass
    return n * dt.itemsize


def _jaxpr_peak(jx) -> int:
    from jax.extend.core import Literal
    last = {}
    for i, e in enumerate(jx.eqns):
        for v in e.invars:
            if not isinstance(v, Literal):
                last[v] = i
    outset = {id(v) for v in jx.outvars}
    live = sum(_aval_bytes(v)
               for v in itertools.chain(jx.invars, jx.constvars))
    peak = live
    for i, e in enumerate(jx.eqns):
        transient = max((_jaxpr_peak(s) for s in eqn_subjaxprs(e)),
                        default=0)
        live += sum(_aval_bytes(o) for o in e.outvars)
        peak = max(peak, live + transient)
        seen = set()
        for v in itertools.chain(e.invars, e.outvars):
            if isinstance(v, Literal) or id(v) in seen:
                continue
            seen.add(id(v))
            if last.get(v, -1) <= i and id(v) not in outset:
                live -= _aval_bytes(v)
    return peak


def estimate_peak_bytes(closed_or_jaxpr) -> int:
    """Liveness-walk upper bound on a program's peak live bytes."""
    jx = getattr(closed_or_jaxpr, "jaxpr", closed_or_jaxpr)
    return _jaxpr_peak(jx)


@register
class FootprintPass(AuditPass):
    code = "K005"
    name = "intermediate-footprint"
    description = ("liveness estimate of peak live bytes from eqn "
                   "out-avals, gated against a configurable budget and "
                   "fed to the memory pool's accounting")

    def run(self, kernel: KernelIR) -> List:
        est = estimate_peak_bytes(kernel.jaxpr)
        kernel.notes["peak_bytes_estimate"] = est
        budget = kernel.footprint_budget_bytes
        if budget and est > budget:
            return [kernel.kernel_finding(
                "K005",
                f"estimated peak live bytes {est} exceed the footprint "
                f"budget {budget} -- shrink capacities, stream the "
                f"scan (split_rows), or raise "
                f"kernel_audit_budget_bytes if the footprint is "
                f"intended")]
        return []
