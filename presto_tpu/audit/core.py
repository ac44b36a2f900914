"""kernaudit core: KernelIR (a walkable, provenance-aware closed
jaxpr), the audit-pass registry, and the run engine.

The contract mirrors ``lint/core.py`` one level down the stack:

  * ``KernelIR`` -- one staged kernel: the closed jaxpr traced from a
    plan's fused function (or a fixture), a stable label
    (``tpch/q01``, a query id), the exchange-axis spec the kernel is
    ALLOWED to communicate over (from ``parallel/stages.py``'s mesh
    wiring -- empty for single-chip kernels), and a footprint budget.
    It owns recursive eqn iteration (descending into pjit / scan /
    cond / shard_map sub-jaxprs) and eqn provenance: each eqn maps
    back through ``source_info`` to a repo file, line, and dotted
    enclosing-function context, which is what makes findings
    fingerprintable, whitelistable, and suppressible exactly like
    tpulint's.
  * ``AuditPass`` -- subclass per IR rule (K001...), registered with
    ``@register``; ``presto_tpu.audit.passes`` imports every pass
    module so importing the package populates the registry (the same
    loading scheme as the lint registry, kept separate so pass codes
    and CLI selection cannot collide).
  * ``run_audit`` -- map selected passes over kernels, drop findings
    whose provenance line carries ``# kernaudit: disable=CODE``,
    return an ``AuditResult``.

Findings reuse ``lint.core.Finding`` (same fingerprint law, so
``lint/baseline.py`` applies unchanged to ``kernaudit_baseline.json``):
``path`` is the KERNEL label (the corpus gate's stable unit), ``line``/
``col`` point at the source site the eqn traces to, ``context`` is the
dotted enclosing function there, and the message names the source file
(line-independent) so fingerprints survive edits above a site.
"""

from __future__ import annotations

import ast
import dataclasses
import functools
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..lint.core import REPO, Finding

__all__ = ["KernelIR", "IRFinding", "AuditPass", "register", "all_passes",
           "get_pass", "AuditResult", "run_audit", "eqn_subjaxprs",
           "CALL_PRIMITIVES"]

_SUPPRESS_RE = re.compile(
    r"#\s*kernaudit:\s*disable=([A-Za-z0-9_,\s]+|all)")

# call-like primitives own sub-jaxprs; dtype rules skip the call eqn
# itself (a pjit whose OUTPUT is int64 is not a widening site -- the
# creation happens inside and is audited there)
CALL_PRIMITIVES = frozenset([
    "pjit", "xla_call", "closed_call", "core_call", "shard_map", "scan",
    "while", "cond", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "remat", "remat2", "checkpoint", "named_call",
])


@dataclasses.dataclass(frozen=True)
class IRFinding(Finding):
    """A lint Finding plus the source file its eqn traces to --
    ``src_path`` feeds ``--format github`` annotations; it is NOT part
    of the fingerprint or the ``--json`` schema (both stay identical to
    tpulint's)."""

    src_path: str = ""


def eqn_subjaxprs(eqn):
    """Sub-jaxprs owned by one eqn (pjit/scan/cond/shard_map/...),
    normalized to open ``Jaxpr`` objects."""

    def norm(v):
        if hasattr(v, "jaxpr"):  # ClosedJaxpr
            return v.jaxpr
        if hasattr(v, "eqns"):   # already an open Jaxpr
            return v
        return None

    for v in eqn.params.values():
        j = norm(v)
        if j is not None:
            yield j
        elif isinstance(v, (list, tuple)):
            for x in v:
                j = norm(x)
                if j is not None:
                    yield j


@functools.lru_cache(maxsize=256)
def _def_spans(abs_path: str) -> Tuple[Tuple[int, int, Tuple[str, ...]], ...]:
    """(start, end, def-name stack) for every function/class in a
    source file -- provenance lines resolve to dotted contexts the same
    way lint passes compute theirs from the AST."""
    try:
        with open(abs_path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=abs_path)
    except (OSError, SyntaxError, ValueError):
        return ()
    spans: List[Tuple[int, int, Tuple[str, ...]]] = []

    def walk(node, stack):
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                st = stack + (ch.name,)
                end = getattr(ch, "end_lineno", ch.lineno) or ch.lineno
                spans.append((ch.lineno, max(end, ch.lineno), st))
                walk(ch, st)
            else:
                walk(ch, stack)

    walk(tree, ())
    return tuple(spans)


@functools.lru_cache(maxsize=256)
def _suppressions(abs_path: str) -> Dict[int, frozenset]:
    """{line: codes} of ``# kernaudit: disable=...`` comments in a
    source file (the IR-level analog of lint's inline suppressions:
    the comment sits on the source line the eqn traces back to)."""
    out: Dict[int, frozenset] = {}
    try:
        with open(abs_path, encoding="utf-8") as f:
            lines = f.read().splitlines()
    except OSError:
        return out
    for i, line in enumerate(lines, start=1):
        if "kernaudit" not in line:
            continue
        m = _SUPPRESS_RE.search(line)
        if m:
            out[i] = frozenset(
                c.strip() for c in m.group(1).split(",") if c.strip())
    return out


def _user_frame(eqn):
    """The first non-jax frame of an eqn's traceback, or None (e.g.
    jaxprs built programmatically)."""
    from jax._src import source_info_util
    return source_info_util.user_frame(eqn.source_info.traceback)


class KernelIR:
    """One staged kernel under audit: closed jaxpr + metadata."""

    def __init__(self, closed, label: str, *,
                 exchange_axes: Iterable[str] = (),
                 footprint_budget_bytes: int = 0,
                 repo: str = REPO):
        self.closed = closed
        self.jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
        self.label = label
        # axis names this kernel is SANCTIONED to run collectives over
        # (the mesh/stage spec); empty = single-chip kernel, where any
        # collective is a finding
        self.exchange_axes = frozenset(exchange_axes)
        self.footprint_budget_bytes = int(footprint_budget_bytes)
        self.repo = repo
        # pass-computed observations (K005 peak estimate, ...) the
        # staging hook forwards into QueryStats / the memory pool
        self.notes: Dict[str, int] = {}

    @classmethod
    def trace(cls, fn, args: Sequence, label: str, **kw) -> "KernelIR":
        """Trace ``fn(*args)`` to a closed jaxpr (no execution)."""
        import jax
        return cls(jax.make_jaxpr(fn)(*args), label, **kw)

    # -- IR iteration ---------------------------------------------------

    def eqns(self):
        """Yield ``(owner_jaxpr, eqn)`` over the whole program,
        descending into every sub-jaxpr."""

        def walk(jx):
            for e in jx.eqns:
                yield jx, e
                for s in eqn_subjaxprs(e):
                    yield from walk(s)

        yield from walk(self.jaxpr)

    # -- provenance -----------------------------------------------------

    def site(self, eqn) -> Tuple[str, str, int]:
        """(source path, dotted context, line) of an eqn. The path is
        repo-relative when the frame lies inside the repo; context is
        the last two def-stack segments (lint's ``dotted_context``
        rendering) or ``<module>``."""
        frame = _user_frame(eqn)
        if frame is None:
            return "", "<unknown>", 0
        abs_path = frame.file_name
        line = int(frame.start_line or 0)
        best: Optional[Tuple[str, ...]] = None
        for lo, hi, stack in _def_spans(abs_path):
            if lo <= line <= hi and (best is None or len(stack) > len(best)):
                best = stack
        context = ".".join(best[-2:]) if best else "<module>"
        rel = abs_path
        try:
            if os.path.commonpath([abs_path, self.repo]) == self.repo:
                rel = os.path.relpath(abs_path, self.repo).replace(
                    os.sep, "/")
        except ValueError:
            pass
        return rel, context, line

    def site_stack(self, eqn) -> Tuple[str, ...]:
        """Full def-name stack at an eqn's source line (whitelists can
        match the top-level function the way W001's do)."""
        frame = _user_frame(eqn)
        if frame is None:
            return ()
        line = int(frame.start_line or 0)
        best: Tuple[str, ...] = ()
        for lo, hi, stack in _def_spans(frame.file_name):
            if lo <= line <= hi and len(stack) > len(best):
                best = stack
        return best

    def suppressed(self, finding: "IRFinding") -> bool:
        """True when the source line a finding traces to carries a
        ``# kernaudit: disable=<code>`` comment (engine-applied, like
        lint's per-line suppressions)."""
        if not finding.src_path or not finding.line:
            return False
        abs_path = finding.src_path if os.path.isabs(finding.src_path) \
            else os.path.join(self.repo, finding.src_path)
        codes = _suppressions(abs_path).get(finding.line)
        return bool(codes) and (finding.code in codes or "all" in codes)

    # -- finding construction -------------------------------------------

    def finding(self, code: str, eqn, message: str) -> IRFinding:
        """Build a finding anchored at the eqn's provenance. The source
        FILE rides in the message (line-independent, so the fingerprint
        pins code|kernel|context|site-file|claim); the line/col locate
        it for humans and ``--format github``."""
        src, context, line = self.site(eqn)
        if src:
            message = f"{message} [at {src}]"
        return IRFinding(code=code, path=self.label, line=line, col=0,
                         context=context, message=message, src_path=src)

    def kernel_finding(self, code: str, message: str) -> IRFinding:
        """A whole-kernel finding (no single source site -- K005)."""
        return IRFinding(code=code, path=self.label, line=0, col=0,
                         context="<kernel>", message=message, src_path="")


class AuditPass:
    """Base class for IR passes: subclass, set the class attributes,
    implement ``run(kernel) -> [Finding]``. Inline suppression is the
    engine's job -- passes just report."""

    code: str = "K000"
    name: str = "unnamed"
    description: str = ""

    def run(self, kernel: KernelIR) -> List[Finding]:
        raise NotImplementedError


_REGISTRY: Dict[str, AuditPass] = {}


def register(cls):
    """Class decorator: instantiate and index the pass by its code
    (separate registry from the lint one -- AST and IR passes are
    selected by different CLIs and must not collide)."""
    inst = cls()
    assert inst.code not in _REGISTRY or \
        type(_REGISTRY[inst.code]) is cls, \
        f"duplicate audit pass code {inst.code}"
    _REGISTRY[inst.code] = inst
    return cls


def all_passes() -> List[AuditPass]:
    _load_builtin_passes()
    return [_REGISTRY[c] for c in sorted(_REGISTRY)]


def get_pass(code: str) -> AuditPass:
    _load_builtin_passes()
    return _REGISTRY[code]


def _load_builtin_passes() -> None:
    from . import passes  # noqa: F401


@dataclasses.dataclass
class AuditResult:
    findings: List[Finding]
    suppressed: int
    kernels: List[str]        # labels actually audited
    pass_codes: List[str]

    @property
    def kernels_audited(self) -> int:
        return len(self.kernels)


def run_audit(kernels: Sequence[KernelIR],
              codes: Optional[Iterable[str]] = None) -> AuditResult:
    """Run the selected IR passes (all registered, by default) over the
    given kernels. Source-comment suppressions are applied here;
    baselining is the caller's concern (lint/baseline.py)."""
    _load_builtin_passes()
    selected = [get_pass(c) for c in sorted(codes)] if codes else \
        all_passes()
    findings: List[Finding] = []
    suppressed = 0
    labels: List[str] = []
    for k in kernels:
        labels.append(k.label)
        for p in selected:
            for f in p.run(k):
                if isinstance(f, IRFinding) and k.suppressed(f):
                    suppressed += 1
                else:
                    findings.append(f)
    findings.sort(key=Finding.sort_key)
    return AuditResult(findings=findings, suppressed=suppressed,
                       kernels=labels,
                       pass_codes=[p.code for p in selected])
