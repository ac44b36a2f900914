"""Compiled-plan cache: plan fingerprint -> jitted executable.

Reference surface: the reference keeps compiled PageProcessor /
operator-factory artifacts cached per plan (ExpressionCompiler's
CacheLoader in sql/gen/ExpressionCompiler.java, and the native worker
reuses compiled Velox plan translations across identical fragments).
This engine's analog sits one level higher: the WHOLE fragment lowers
to one XLA program, and recompiling it per query submission costs
seconds of trace+compile for a plan the process has already built.
Repeat submissions (CLI sessions, the statement protocol, dashboards
re-running a query) hit the cache and pay only staging + execution.

The key is a *structural* fingerprint of the plan tree: node types and
parameters in traversal order with shared-subtree back-references
(so a CTE DAG and its tree-shaped twin fingerprint differently), node
ids EXCLUDED (the global id counter makes two plannings of the same SQL
differ only in ids). Two plans with equal fingerprints lower to the
same traced program, so batches -- supplied positionally in scan
traversal order -- execute identically under either plan object.

Thread-safety: a per-entry lock serializes dispatch through one cached
executable (tracing mutates the closure's overflow bookkeeping; XLA
execution itself is async and runs outside the lock via the returned
futures). Different plans never contend.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
from collections import OrderedDict
from typing import Optional, Tuple

import jax
import numpy as np

from ..plan import nodes as N
from ..utils.locks import OrderedLock
from .planner import CompiledPlan, compile_plan
from .stats import note

__all__ = ["plan_fingerprint", "cached_compile", "cache_stats",
           "clear_plan_cache", "KERNEL_MODE_ENVS"]

_MAX_ENTRIES = 64

_lock = OrderedLock("plan_cache._lock")
_cache: "OrderedDict[tuple, _Entry]" = OrderedDict()
_hits = 0
_misses = 0


@dataclasses.dataclass
class _Entry:
    plan: CompiledPlan
    fn: object            # jax.jit-wrapped plan.fn
    call_lock: threading.Lock


def plan_fingerprint(root: N.PlanNode) -> str:
    """Deterministic structural hash of a plan tree (ids excluded,
    object-identity sharing preserved via back-references)."""
    seen: dict = {}
    parts: list = []

    def emit(v):
        if isinstance(v, N.PlanNode):
            walk(v)
        elif isinstance(v, (list, tuple)):
            parts.append("[")
            for x in v:
                emit(x)
            parts.append("]")
        elif isinstance(v, np.ndarray):
            # repr truncates large arrays -- hash the raw bytes instead
            parts.append(f"nd:{v.dtype}:{v.shape}:"
                         f"{hashlib.sha256(v.tobytes()).hexdigest()}")
        else:
            parts.append(repr(v))

    def walk(n):
        if id(n) in seen:
            parts.append(f"@{seen[id(n)]}")
            return
        seen[id(n)] = len(seen)
        parts.append(type(n).__name__)
        for f in dataclasses.fields(n):
            if f.name == "id":
                continue
            parts.append(f.name)
            emit(getattr(n, f.name))

    walk(root)
    return hashlib.sha256("|".join(parts).encode()).hexdigest()


def _mesh_key(mesh) -> Optional[tuple]:
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), mesh.devices.shape,
            tuple(d.id for d in mesh.devices.flat))


# Trace-time env knobs that change the lowered program WITHOUT changing
# the plan fingerprint (kernel form A/Bs: small-G scatter vs einsum,
# Pallas on/off, narrow bf16 forms, large-G sort vs hash). Every entry
# is part of the cache key; tpulint's R001 pass rejects any OTHER env
# read in ops/ or exec/ (an unregistered knob would serve stale
# executables compiled under the other mode).
KERNEL_MODE_ENVS = (("PRESTO_TPU_SMALLG", "auto"),
                    ("PRESTO_TPU_SMALLG_PALLAS", "1"),
                    ("PRESTO_TPU_NARROW", "1"),
                    ("PRESTO_TPU_BF16", "auto"),
                    ("PRESTO_TPU_GROUPBY", "sort"),
                    # pipeline-region fusion (exec/regions.py): =0 runs
                    # every operator as its own materialized program;
                    # partitioning changes WHICH programs compile, so
                    # the mode is part of every cached key
                    ("PRESTO_TPU_FUSION", "1"),
                    # staging-time kernel auditing (audit/staged.py):
                    # doesn't change the lowered program, but keying it
                    # keeps audit-memo and executable lifecycles aligned
                    # and satisfies R001's registered-env contract
                    ("PRESTO_TPU_KERNEL_AUDIT", "0"),
                    # concurrent-query batching (exec/batching.py): the
                    # batched dispatch traces a vmapped program over the
                    # parameter axis, so the mode is part of every batch
                    # key (and rides the one R001-checked env list)
                    ("PRESTO_TPU_BATCHING", "1"),
                    # proven-safe buffer donation (exec/donation.py):
                    # the donating dispatch compiles a separate wrapper
                    # program (donate_argnums over the dead leaves), so
                    # the mode is part of every cached key (and the env
                    # read rides the one R001-checked list)
                    ("PRESTO_TPU_DONATION", "0"))


def _kernel_mode() -> str:
    """The cache-key component built from KERNEL_MODE_ENVS."""
    import os
    # this IS the cache key: the one sanctioned ambient read
    return "|".join(os.environ.get(name, default)  # tpulint: disable=R001
                    for name, default in KERNEL_MODE_ENVS)


def _capacity_sensitive(root: N.PlanNode) -> bool:
    """Whether `default_join_capacity` can change this plan's lowered
    program. The ONLY lowering site that reads it is a JoinNode without
    an explicit out_capacity (exec/planner.py), so join-free plans --
    and plans whose joins all carry planned capacities -- compile
    identically under every default. Keying those on the default would
    fragment the cache across callers that merely configure different
    join defaults (the fragment tier passes the session's
    default_join_capacity on every submission)."""
    seen: set = set()

    def walk(n) -> bool:
        if id(n) in seen:  # shared CTE subtrees visit once (a DAG
            return False   # walked as a tree is exponential)
        seen.add(id(n))
        if isinstance(n, N.JoinNode) and n.out_capacity is None:
            return True
        return any(walk(s) for s in n.sources)
    return walk(root)


def cached_compile(root: N.PlanNode, mesh, default_join_capacity: int,
                   exchange_slot_scale: int = 1
                   ) -> Tuple[CompiledPlan, object, threading.Lock]:
    """(CompiledPlan, jitted fn, per-entry dispatch lock) for this plan,
    compiling at most once per (structure, mesh, capacities, scale).
    Join-free plans are capacity-insensitive: their key ignores
    `default_join_capacity`, so fused scan/agg regions never fragment
    the cache across join-capacity configurations. A hit or a miss is
    counted on the statement that asked (``QueryStats.counters``) as
    well as on the process."""
    global _hits, _misses
    cap_key = default_join_capacity if _capacity_sensitive(root) else None
    key = (plan_fingerprint(root), _mesh_key(mesh), cap_key,
           exchange_slot_scale, _kernel_mode())
    with _lock:
        entry = _cache.get(key)
        if entry is not None:
            _cache.move_to_end(key)
            _hits += 1
        else:
            _misses += 1
    note("plan_cache_hits" if entry is not None else "plan_cache_misses")
    if entry is not None:
        return entry.plan, entry.fn, entry.call_lock
    # compile outside the cache lock (pure python closure-building, fast;
    # the expensive XLA work happens lazily at first dispatch)
    plan = compile_plan(root, mesh, default_join_capacity,
                        exchange_slot_scale=exchange_slot_scale)
    entry = _Entry(plan, jax.jit(plan.fn), OrderedLock("plan_cache._Entry.call_lock"))
    with _lock:
        have = _cache.get(key)
        if have is not None:     # lost a race: keep the first
            return have.plan, have.fn, have.call_lock
        _cache[key] = entry
        while len(_cache) > _MAX_ENTRIES:
            _cache.popitem(last=False)
    return entry.plan, entry.fn, entry.call_lock


def cache_stats() -> dict:
    with _lock:
        return {"entries": len(_cache), "hits": _hits, "misses": _misses}


def clear_plan_cache() -> None:
    global _hits, _misses
    with _lock:
        _cache.clear()
        _hits = 0
        _misses = 0
    # the kernel-audit memo is keyed by the same (fingerprint, mesh,
    # kernel-mode) identity as cache entries: clearing one without the
    # other would serve stale audit reports for freshly traced programs
    from ..audit.staged import clear_audit_memo
    clear_audit_memo()
