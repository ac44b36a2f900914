"""C002: whole-program lock-acquisition-order graph, cycle = finding.

Two threads acquiring the same two locks in opposite orders is the
deadlock that only fires under load -- and the server tier now has
dozens of locks across drain/adopt migration, speculation, failover,
batching formation, and the watchdogs. This pass builds the
acquisition-order graph statically (see lint/lockmodel.py for the
extraction rules: ``with`` nesting, ``*_locked`` conventions, call
edges resolved through class-attribute lock identities) over the
server tier + failpoints + the threaded exec/ modules, and reports
every cycle with both participating acquisition paths.

The acyclic graph itself is a committed artifact: ``LOCK_ORDER.json``
at the repo root, regenerated by ``scripts/lockgraph.py`` (which also
renders DOT and gates drift/cycles in CI via ``--check``). The runtime
complement is utils/locks.py's witness: same node identities, enforced
at acquire time under chaos.

Fixture files outside the target set are analyzed standalone (the
sensitivity corpus seeds an intra-module cycle); target files share
ONE memoized whole-program graph, and each cycle is reported in the
file anchoring its first edge so per-file partial runs stay coherent.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

from ..core import REPO, Finding, LintPass, ModuleSource, register
from ..lockmodel import LockProgram, build_program

__all__ = ["LockOrderPass", "program_for_targets", "CONCURRENCY_TARGETS"]

# the concurrency-audit surface: server tier, failpoints, and the
# exec/ modules whose registries are shared across request-handler,
# runner, and service threads (one list, shared by C002/C003/C004)
CONCURRENCY_TARGETS = (
    "presto_tpu/server/*.py",
    "presto_tpu/failpoints/*.py",
    "presto_tpu/exec/progress.py",
    "presto_tpu/exec/batching.py",
    "presto_tpu/exec/plan_cache.py",
    "presto_tpu/exec/regions.py",
    "presto_tpu/exec/datapath.py",
    "presto_tpu/exec/accuracy.py",
    "presto_tpu/exec/resident.py",
    "presto_tpu/exec/memory.py",
    "presto_tpu/connectors/memory.py",
    "presto_tpu/utils/locks.py",
)

_PROGRAM_CACHE: Dict[tuple, LockProgram] = {}


def program_for_targets(files: List[str], repo: str = REPO
                        ) -> LockProgram:
    """The memoized whole-program lock model over `files` (repo-
    relative), keyed by path+mtime so one CLI run builds it once and
    an edited file invalidates it."""
    key_parts = []
    for rel in sorted(files):
        ap = os.path.join(repo, rel)
        try:
            key_parts.append((rel, os.path.getmtime(ap)))
        except OSError:
            key_parts.append((rel, 0.0))
    key = (repo, tuple(key_parts))
    prog = _PROGRAM_CACHE.get(key)
    if prog is None:
        sources = [ModuleSource(rel, repo) for rel in sorted(set(files))]
        prog = build_program(sources)
        _PROGRAM_CACHE.clear()  # one live entry; edits invalidate
        _PROGRAM_CACHE[key] = prog
    return prog


def _cycle_findings(ms: ModuleSource, prog: LockProgram,
                    only_this_file: bool) -> List[Finding]:
    findings: List[Finding] = []
    for cyc in prog.cycles():
        ring = cyc + [cyc[0]]
        evidence = []
        for a, b in zip(ring, ring[1:]):
            ev = prog.edges.get((a, b), {})
            via = f" via {ev['via']}" if ev.get("via") else ""
            evidence.append((ev, f"{b} under {a} in "
                                 f"{ev.get('context', '?')}{via}"))
        anchor = evidence[0][0]
        if only_this_file and anchor.get("file") != ms.rel_path:
            continue
        msg = (f"lock-order cycle: {' -> '.join(ring)} "
               f"({'; '.join(e for _, e in evidence)}) -- potential "
               f"deadlock; see scripts/lockgraph.py for the full graph")
        findings.append(Finding(
            code="C002", path=ms.rel_path,
            line=int(anchor.get("line", 0)), col=0,
            context=anchor.get("context", "<module>"), message=msg))
    return findings


@register
class LockOrderPass(LintPass):
    code = "C002"
    name = "lock-order-graph"
    description = ("cycles in the whole-program lock-acquisition-order "
                   "graph (potential deadlocks)")
    TARGETS = CONCURRENCY_TARGETS

    def run(self, ms: ModuleSource) -> List[Finding]:
        targets = self.target_files()
        if ms.rel_path in targets:
            prog = program_for_targets(targets)
            return _cycle_findings(ms, prog, only_this_file=True)
        # standalone file (fixture corpus): self-contained graph
        prog = build_program([ms])
        return _cycle_findings(ms, prog, only_this_file=False)
