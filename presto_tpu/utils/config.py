"""Config + session-property system.

Reference surface: airlift @Config beans (TaskManagerConfig,
QueryManagerConfig, MemoryManagerConfig, FeaturesConfig:72 -- 3.7k LoC
of flags) parsed from etc/config.properties, plus
SystemSessionProperties.java:96 (311 typed per-query session
properties, where the north star's `tpu_execution_enabled` gate
lives) and the native worker's SystemConfig (Configs.h:162).

A ConfigSpec declares typed properties with defaults; Config binds a
property file / dict against a spec with type coercion and unknown-key
errors; Session resolves per-query overrides against SESSION_PROPERTIES
the way SystemSessionProperties resolves them at query start.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

__all__ = ["ConfigSpec", "Config", "SESSION_PROPERTIES", "Session", "parse_size",
           "SessionProperty"]


def _parse_bool(v):
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("true", "1", "yes", "on")


def _parse_size(v):
    """'512MB' / '16GB' / plain int bytes."""
    if isinstance(v, (int, float)):
        return int(v)
    s = str(v).strip().upper()
    for suffix, mult in (("TB", 1 << 40), ("GB", 1 << 30), ("MB", 1 << 20),
                         ("KB", 1 << 10), ("B", 1)):
        if s.endswith(suffix):
            return int(float(s[: -len(suffix)]) * mult)
    return int(s)


_COERCE: Dict[str, Callable[[Any], Any]] = {
    "bool": _parse_bool, "int": int, "float": float, "str": str,
    "size": _parse_size,
}


@dataclasses.dataclass(frozen=True)
class Property:
    name: str
    kind: str
    default: Any
    description: str = ""


class ConfigSpec:
    def __init__(self, name: str):
        self.name = name
        self.properties: Dict[str, Property] = {}

    def add(self, name: str, kind: str, default: Any, description: str = ""):
        assert kind in _COERCE, kind
        self.properties[name] = Property(name, kind, default, description)
        return self


class Config:
    """Bound configuration: spec + overrides, with coercion."""

    def __init__(self, spec: ConfigSpec, values: Optional[Dict[str, Any]] = None):
        self.spec = spec
        self._values: Dict[str, Any] = {}
        for k, v in (values or {}).items():
            self.set(k, v)

    def set(self, key: str, value: Any):
        prop = self.spec.properties.get(key)
        if prop is None:
            raise KeyError(f"unknown config property {key!r} for {self.spec.name}")
        self._values[key] = _COERCE[prop.kind](value)

    def get(self, key: str) -> Any:
        prop = self.spec.properties.get(key)
        if prop is None:
            raise KeyError(f"unknown config property {key!r} for {self.spec.name}")
        if key in self._values:
            return self._values[key]
        return _COERCE[prop.kind](prop.default)  # defaults coerce too ("12GB")

    def get_explicit(self, key: str) -> Any:
        """The EXPLICITLY-set value, or None when the key rides its
        spec default -- for layered precedence chains (session value >
        constructor > env) where the spec default must not shadow the
        lower layers the way get()'s coerced default would."""
        return self._values.get(key)

    @classmethod
    def from_properties_file(cls, spec: ConfigSpec, path: str) -> "Config":
        values = {}
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                k, _, v = line.partition("=")
                values[k.strip()] = v.strip()
        return cls(spec, values)


# ---------------------------------------------------------------------------
# Engine configs (TaskManagerConfig / MemoryManagerConfig analog subset)
# ---------------------------------------------------------------------------

WORKER_CONFIG = (
    ConfigSpec("worker")
    .add("task.batch-capacity", "int", 1 << 20,
         "rows per on-device batch bucket (PageProcessor batch-size analog)")
    .add("task.max-groups", "int", 1 << 20,
         "default dense group-table capacity per aggregation")
    .add("memory.max-query-memory", "size", "12GB",
         "per-query HBM reservation ceiling (query_max_memory analog)")
    .add("exchange.slot-capacity", "int", 1 << 17,
         "per-destination rows in all_to_all exchange buckets")
    .add("join.out-capacity-factor", "float", 1.5,
         "join output bucket = probe rows * factor")
)


# ---------------------------------------------------------------------------
# Session properties (SystemSessionProperties analog)
# ---------------------------------------------------------------------------

SESSION_PROPERTIES = (
    ConfigSpec("session")
    .add("tpu_execution_enabled", "bool", True,
         "offload plan fragments to the TPU engine (north-star gate; "
         "pattern: SystemSessionProperties.java:398 native_execution_enabled)")
    .add("query_max_memory", "size", "12GB", "per-query memory cap")
    .add("join_distribution_type", "str", "AUTOMATIC",
         "PARTITIONED | BROADCAST | AUTOMATIC "
         "(DetermineJoinDistributionType analog)")
    .add("join_reordering_strategy", "str", "AUTOMATIC",
         "NONE | AUTOMATIC: statistics-driven left-deep reorder of "
         "inner-join chains (ReorderJoins analog, plan/reorder.py)")
    .add("hash_partition_count", "int", 8,
         "workers per partitioned exchange (FIXED_HASH distribution width)")
    .add("task_concurrency", "int", 1,
         "local drivers per pipeline; on TPU, batches in flight per chip")
    .add("exchange_compression", "str", "none",
         "none | zstd | zlib for cross-slice SerializedPage exchanges")
    .add("stats_capacity_refinement", "bool", True,
         "let connector NDV statistics SHRINK group-table capacities "
         "(plan.stats.refine_capacities); disable when a hand-set "
         "max_groups must stay authoritative")
    .add("iterative_optimizer", "bool", True,
         "run the rule-based simplification + channel-pruning passes "
         "(plan.rules; IterativeOptimizer/PruneUnreferencedOutputs "
         "analog) before capacity refinement and distribution")
    .add("scan_predicate_pushdown", "bool", True,
         "push filter range conjuncts into pushdown-capable connectors "
         "(parquet row-group statistics pruning; plan/pushdown.py)")
    .add("dynamic_filtering", "bool", True,
         "run small dimension build sides first and prune fact scans "
         "by their join-key domains at staging time (exec/dynfilter.py)")
    .add("hbm_budget_bytes", "int", 0,
         "cap on per-query device state; aggregations whose planned "
         "group table exceeds it run grouped-execution spill to host "
         "DRAM (exec/spill.py; 0 = uncapped)")
    .add("fragment_result_cache", "bool", True,
         "replay identical leaf fragments' serialized pages from the "
         "worker's data-versioned cache (FileFragmentResultCacheManager "
         "analog); disable when benchmarking raw execution")
    .add("adaptive_capacity", "bool", True,
         "on bucket overflow, re-plan with larger capacities instead "
         "of failing, each node sized from the rows it counted "
         "(exec/runner.py rerun ladder + per-node plan-fingerprint "
         "feedback); false runs the plan as planned: no rerun, no "
         "refit, no feedback")
    .add("spill_path", "str", "",
         "directory for the DISK spill tier: spilled bucket outputs "
         "flush from host DRAM to .npz run files once they exceed "
         "spill_file_threshold_bytes (FileSingleStreamSpiller/"
         "TempStorage analog; empty = host-DRAM only)")
    .add("spill_file_threshold_bytes", "int", 256 << 20,
         "host-DRAM bytes a spill staging area may hold before "
         "flushing a run file to spill_path")
    .add("narrow_width_execution", "bool", True,
         "stage scan columns at the narrowest physical lane the "
         "connector's range statistics prove safe (plan/widths.py; "
         "dates as epoch-day int16/int32, range-proven int64 as "
         "int32/int16/int8) -- bit-exact, every compute site widens "
         "before arithmetic; env PRESTO_TPU_NARROW=0 disables globally "
         "including the bf16/fused kernel forms")
    .add("fusion", "bool", True,
         "pipeline-region fusion (exec/regions.py): stage each plan "
         "fragment's operator chain as ONE XLA program per pipeline "
         "region, with fusion-plan choice (what to fuse vs materialize) "
         "driven by K005 footprint estimates against "
         "kernel_audit_budget_bytes and the fused-against-materialized "
         "device-time samples of exec/regions.FusionMemory (regressing "
         "fused regions demote back to materialized boundaries). "
         "false = one program per operator, the A/B + bisection mode (env PRESTO_TPU_FUSION, "
         "registered in KERNEL_MODE_ENVS)")
    .add("buffer_donation", "bool", False,
         "donate dead region-boundary buffers to XLA on proven-safe "
         "dispatches (exec/donation.py): inputs the kernaudit K006 "
         "proof shows aliasable into an output AND whose last consumer "
         "is this dispatch are passed with donate_argnums, so XLA "
         "reuses their HBM for the region's output -- peak residency "
         "drops by the donated bytes (QueryStats.peak_memory_bytes, "
         "presto_tpu_donated_bytes_total). Only overflow-incapable "
         "regions donate (a rerun would re-read freed buffers); any "
         "donation-path error falls back to the undonated dispatch "
         "(env PRESTO_TPU_DONATION, registered in KERNEL_MODE_ENVS)")
    .add("query_cost_analysis", "bool", False,
         "annotate QueryStats' compile stage with XLA cost_analysis "
         "FLOPs / bytes-accessed (costs one extra program trace per "
         "distinct plan+shape, memoized; EXPLAIN ANALYZE, the CLI "
         "--stats flag and bench.py's telemetry smoke turn it on)")
    .add("kernel_audit", "bool", False,
         "run the kernaudit IR passes (presto_tpu/audit/) over the "
         "staged program at staging time: findings land in QueryStats "
         "counters + presto_tpu_kernel_audit_findings_total{pass} on "
         "/v1/metrics + a flight-recorder event (costs one extra trace "
         "per distinct plan+shape, memoized; env default "
         "PRESTO_TPU_KERNEL_AUDIT)")
    .add("kernel_audit_budget_bytes", "int", 0,
         "K005 intermediate-footprint budget for live-query audits: "
         "kernels whose estimated peak live bytes exceed it are "
         "findings (0 = report the estimate without gating)")
    .add("failpoints", "str", "",
         "fault-injection schedule applied for this query's execution "
         "scope and restored afterwards: 'site=action:trigger,...' "
         "(presto_tpu/failpoints grammar; same as the "
         "PRESTO_TPU_FAILPOINTS env var and POST /v1/failpoint). "
         "Empty = no injection; the subsystem is zero-cost disarmed")
    .add("stuck_query_threshold_ms", "float", 0.0,
         "stuck-progress watchdog threshold: a non-terminal query/task "
         "whose live-progress last-advance age (exec/progress.py) "
         "exceeds this fires presto_tpu_stuck_queries_total, a "
         "flight-recorder stuck_progress event and a reason=stuck "
         "flight dump -- orthogonal to slow_query_threshold_ms, which "
         "fires on TOTAL wall time (env fallback PRESTO_TPU_STUCK_MS; "
         "0 disables)")
    .add("slow_query_threshold_ms", "float", 0.0,
         "slow-query flight-dump threshold: a query whose TOTAL wall "
         "time exceeds this auto-dumps the flight-recorder ring once "
         "on completion (server/statement.py _slow_threshold_ms; env "
         "fallback PRESTO_TPU_SLOW_QUERY_MS; 0 disables) -- orthogonal "
         "to stuck_query_threshold_ms, which fires on live-progress "
         "stall age")
    .add("queue_timeout_s", "float", 60.0,
         "admission-queue patience (server/dispatcher.py submit): how "
         "long a statement waits in the resource-group queue before "
         "QUERY_QUEUE_FULL; the registry default is what statement "
         "submission uses when the session carries no override")
    .add("speculative_execution_threshold_ms", "float", 0.0,
         "straggler mitigation: a remote task whose live-progress "
         "last-advance age (exec/progress.py -- the stuck-watchdog's "
         "signal) exceeds this is speculatively re-submitted to "
         "another worker; first FINISHED attempt wins, the loser is "
         "aborted, and the winner alone feeds consumers (exactly-once "
         "by construction). Orthogonal to stuck_query_threshold_ms, "
         "which only OBSERVES the stall. Resolved by "
         "Coordinator.execute(session=...) -- embeddings that drive a "
         "Coordinator pass their session through; the constructor arg "
         "and the PRESTO_TPU_SPECULATION_MS env cover the rest "
         "(0 disables)")
    .add("drain_timeout_ms", "float", 30000.0,
         "graceful-drain budget (POST /v1/worker/drain): how long a "
         "DRAINING worker waits for running tasks to finish and its "
         "buffered result pages to migrate/be consumed before giving "
         "up on unannouncing; this spec's default is what "
         "begin_drain uses when the request body carries no "
         "timeoutMs (server/worker.py)")
    .add("query_batching", "bool", True,
         "concurrent-query batching (exec/batching.py): queries whose "
         "plans differ only in parameterizable literals share ONE "
         "vmapped dispatch -- grouped by (template plan fingerprint, "
         "kernel-mode envs), literals lifted into a parameter vector, "
         "results fanned back bit-identically to serial execution. "
         "false = the serial A/B control scripts/loadgen.py measures "
         "against (env PRESTO_TPU_BATCHING, registered in "
         "KERNEL_MODE_ENVS)")
    .add("batch_window_ms", "float", 5.0,
         "batch formation window: how long the FIRST arrival of a hot "
         "plan fingerprint waits for co-batchable followers before "
         "dispatching (cold fingerprints never wait; hotness is the "
         "fingerprint's recent frequency, seeded from the query-history "
         "archive)")
    .add("batch_max_size", "int", 64,
         "queries per batched dispatch cap; a forming batch seals "
         "early when it fills")
    .add("batch_hot_min", "int", 2,
         "submissions of a plan fingerprint (recent in-process + "
         "history-archive counts) before it is HOT enough to pay the "
         "formation window; <=1 = every batchable query windows")
    .add("latency_class", "str", "",
         "resource-group latency class for admission-to-SLO "
         "(interactive | dashboard | batch, or an explicit dotted "
         "group path) -- dispatchers built with "
         "Dispatcher.with_latency_classes route on it: interactive "
         "preempts scans at admission (higher priority + weight), "
         "per-class concurrency and queue-depth limits apply "
         "(empty = the dispatcher's default group)")
)


class SessionProperty:
    pass  # reserved for typed accessors


class Session(Config):
    """Per-query session: overrides resolved at query start."""

    def __init__(self, values: Optional[Dict[str, Any]] = None,
                 user: str = "presto_tpu", query_id: Optional[str] = None):
        super().__init__(SESSION_PROPERTIES, values)
        self.user = user
        self.query_id = query_id or "q_0"


def parse_size(v) -> int:
    """Public size parser ("4GB" -> bytes; ints pass through)."""
    return _parse_size(v)


def session_flag(session, name: str, default: bool = True) -> bool:
    """Default-on boolean session property over Session objects OR plain
    dicts: missing/None = `default`; only an explicit value overrides.
    The one shared parser -- hand-rolled copies drifted. Values are
    parsed with the registry's bool coercion, NOT truthiness: the
    statement tier hands the engine raw header/SET SESSION strings, and
    ``bool("false")`` silently leaving a flag ON is exactly the bug
    that once broke loadgen's serial A/B control."""
    if session is None:
        return default
    try:
        v = session.get(name)
    except (KeyError, TypeError):
        return default
    if v is None:
        return default
    return v if isinstance(v, bool) else _parse_bool(v)


def session_value(session, name: str, default=None):
    """Typed session property with a fallback for plain dicts/absent
    keys."""
    if session is None:
        return default
    try:
        v = session.get(name)
    except (KeyError, TypeError):
        return default
    return default if v is None else v
