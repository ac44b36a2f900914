#!/usr/bin/env python
"""Seeded chaos soak: the TPC-H corpus under generated fault schedules.

Drives an in-process multi-worker cluster (workers + coordinator +
statement tier + discovery + prober -- the DistributedQueryRunner
harness pattern) through a DETERMINISTIC schedule of fault injections
(presto_tpu/failpoints), armed round by round over the live admin API
(``POST /v1/failpoint``), and asserts the four soak invariants:

  1. correct-or-clean-failure: every chaos query either matches its
     fault-free oracle result or raises a clean error within its
     deadline;
  2. no hangs: a watchdog bounds every query; no metrics counter
     decreases across the run (monotonicity audited per round from
     real ``/v1/metrics`` scrapes);
  3. full fault accounting: every fired injection shows up in the
     ``presto_tpu_failpoint_hits_total{site,action}`` counters AND as
     a flight-recorder ``failpoint`` event (and a statement-tier
     failure round checks its auto flight DUMP carries them);
  4. lock-order consistency: the runtime witness (utils/locks.py) is
     ARMED for the whole soak -- every OrderedLock acquire on every
     tier is checked against the process's established acquisition
     order, and a single inversion anywhere fails its round.

Determinism contract: with a fixed ``--seed``, two runs produce an
identical fault sequence and identical per-query outcomes -- the
report's ``determinism`` section hashes to the same digest. Schedules
therefore use ``once``-triggered faults (fire counts are invariant to
poll timing); ``prob``/``every`` trigger determinism is pinned by
tests/test_failpoints.py at the registry level.

  python scripts/chaos.py --seed 42 --smoke            # pre-PR gate
  python scripts/chaos.py --seed 7 --queries 1,3,6 --schedule 12
  python scripts/chaos.py --seed 42 --report /tmp/chaos.json

Exit codes: 0 invariants hold, 1 invariant violated, 2 harness error.
"""

import argparse
import hashlib
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

# repo root importable + the shared CPU-forcing armor
sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _cpu  # noqa: E402,F401

from presto_tpu import failpoints  # noqa: E402
from presto_tpu.utils import locks as wlocks  # noqa: E402
from presto_tpu.client import StatementClient, QueryError  # noqa: E402
from presto_tpu.exec import run_query  # noqa: E402
from presto_tpu.plan.distribute import add_exchanges  # noqa: E402
from presto_tpu.queries.tpch_sql import tpch_query  # noqa: E402
from presto_tpu.server import Coordinator, TpuWorkerServer  # noqa: E402
from presto_tpu.server.discovery import (Announcer,  # noqa: E402
                                         DiscoveryServer, HeartbeatProber,
                                         alive_nodes)
from presto_tpu.server.flight_recorder import (FlightRecorder,  # noqa: E402
                                               get_flight_recorder,
                                               set_flight_recorder)
from presto_tpu.server.metrics import parse_prometheus  # noqa: E402
from presto_tpu.server.statement import StatementServer  # noqa: E402
from presto_tpu.sql import plan_sql  # noqa: E402

SMOKE_QUERIES = (1, 6)
FULL_QUERIES = (1, 3, 4, 6, 12, 14, 19)

# The fault palette: (layer, site, spec). All `once`-triggered --
# deterministic fire counts regardless of poll timing -- and all
# verified to leave a recoverable or cleanly-failing cluster. The
# schedule's coverage prefix walks every entry once (so each smoke run
# fires >= 5 distinct sites across exchange/serde/task/memory/
# discovery); extra rounds draw from QUERY_FAULTS with the seeded RNG.
QUERY_FAULTS = [
    ("exchange", "exchange.fetch", "error(ConnectionError):once"),
    ("exchange", "exchange.serve", "drop_conn:once"),
    ("serde", "serde.deserialize", "corrupt_page:once"),
    ("serde", "serde.serialize", "error(ValueError):once"),
    ("task", "worker.run_task", "error(RuntimeError):once"),
    ("task", "task.submit", "error(ConnectionError):once"),
    ("task", "task.status", "error(ConnectionError):once"),
    ("task", "task.result", "error(ConnectionError):once"),
    ("task", "client.request", "drop_conn:once"),
    ("task", "worker.run_task", "delay(250):once"),
    ("memory", "memory.reserve", "oom:once"),
]
# non-query rounds: discovery ops + statement-tier rounds (dispatcher
# stall, failed-query flight dump, hang vs client poll deadline)
OP_ROUNDS = [
    ("discovery", "announce"),
    ("discovery", "probe"),
    ("dispatcher", "admit"),
    ("dispatcher", "batch"),
    ("statement", "fail_dump"),
    ("statement", "hang_deadline"),
    ("task", "stuck"),
    ("fusion", "demote"),
    ("fusion", "donation"),
    ("fleet", "elastic"),
    ("fleet", "speculate"),
]


def canon_rows(cols):
    """Coordinator/local result columns -> canonical sorted row tuples
    (floats rounded so distributed summation order cannot flip a
    match verdict)."""
    rows = []
    n = len(cols[0][0]) if cols else 0
    for i in range(n):
        row = []
        for v, nl in cols:
            if bool(nl[i]):
                row.append(None)
                continue
            x = v[i].item() if hasattr(v[i], "item") else v[i]
            if isinstance(x, float):
                x = round(x, 3)
            row.append(x)
        rows.append(tuple(row))
    return sorted(rows, key=lambda r: tuple((x is None, str(x))
                                            for x in r))


class Watchdog:
    """Run fn() on a thread, bounded by a deadline: the no-hangs
    invariant's enforcement. -> ("ok", value) | ("error", exc) |
    ("hung", None)."""

    def __init__(self, fn, deadline_s: float):
        self.fn = fn
        self.deadline_s = deadline_s
        self.value = None
        self.error = None
        self.done = False

    def run(self):
        def target():
            try:
                self.value = self.fn()
            except BaseException as e:  # noqa: BLE001 - verdict data
                self.error = e
            self.done = True
        t = threading.Thread(target=target, daemon=True)
        t.start()
        t.join(self.deadline_s)
        if not self.done:
            return "hung", None
        if self.error is not None:
            return "error", self.error
        return "ok", self.value


class ChaosCluster:
    """In-process cluster: N workers + coordinator (explicit URLs for
    query traffic), a statement tier, and a discovery server whose
    announcer/prober the driver steps MANUALLY -- discovery faults
    then fire a deterministic number of times."""

    def __init__(self, sf: float, workers: int = 2):
        self.sf = sf
        self.workers = [TpuWorkerServer(sf=sf).start()
                        for _ in range(workers)]
        self.urls = [f"http://127.0.0.1:{w.port}" for w in self.workers]
        self.coordinator = Coordinator(self.urls)
        self.statement = StatementServer(sf=sf).start()
        self.discovery = DiscoveryServer().start()
        # driver-stepped: start() is never called on this announcer
        self.announcer = Announcer(self.discovery.url, "chaos-node",
                                   self.urls[0], interval_s=3600.0)
        self.prober = HeartbeatProber(lambda: self.urls, decay=0.0)

    def stop(self):
        for w in self.workers:
            try:
                w.stop()
            except Exception:  # noqa: BLE001 - already stopped
                pass
        self.statement.stop()
        self.discovery.stop()

    # -- admin API (the live-flip path under test) ---------------------

    def _admin(self, method: str, path: str, body=None) -> dict:
        import urllib.request
        data = json.dumps(body).encode() if body is not None else None
        req = urllib.request.Request(
            f"{self.urls[0]}{path}", data=data, method=method,
            headers={"Content-Type": "application/json"} if data else {})
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.loads(r.read())

    def arm(self, site: str, spec: str) -> None:
        doc = self._admin("POST", "/v1/failpoint",
                          {"site": site, "spec": spec})
        assert site in doc.get("active", {}), doc

    def armed_doc(self) -> dict:
        return self._admin("GET", "/v1/failpoint")

    def disarm_all(self) -> None:
        self._admin("DELETE", "/v1/failpoint")

    # -- metrics -------------------------------------------------------

    def scrapes(self) -> dict:
        """{endpoint: parsed /v1/metrics} over every HTTP tier."""
        import urllib.request
        out = {}
        for name, base in [("worker0", self.urls[0]),
                           ("statement", self.statement.url)]:
            with urllib.request.urlopen(f"{base}/v1/metrics",
                                        timeout=10) as r:
                out[name] = parse_prometheus(r.read().decode())
        return out


def monotonicity_violations(before: dict, after: dict) -> list:
    """Counter samples (plain *_total + histogram _bucket/_count/_sum)
    that DECREASED between two parsed scrapes of one endpoint."""
    bad = []
    for fam, samples in after.items():
        if not (fam.endswith(("_total", "_bucket", "_count", "_sum"))):
            continue
        for key, val in samples.items():
            prev = before.get(fam, {}).get(key)
            if prev is not None and val < prev - 1e-9:
                bad.append(f"{fam}{key}: {prev} -> {val}")
    return bad


def failpoint_counter_totals(parsed: dict) -> dict:
    """{(site, action): value} from a parsed scrape."""
    import re
    out = {}
    for key, val in parsed.get("presto_tpu_failpoint_hits_total",
                               {}).items():
        site = re.search(r'site="([^"]+)"', key)
        action = re.search(r'action="([^"]+)"', key)
        if site and action and site.group(1) != "none":
            out[(site.group(1), action.group(1))] = val
    return out


def build_schedule(seed: int, queries, rounds: int):
    """The deterministic round list: a coverage prefix (every palette
    entry + every op round once) then seeded extra draws up to
    `rounds`. Queries rotate deterministically; the RNG never touches
    the prefix, so coverage is identical at every seed."""
    import random
    rng = random.Random(seed)
    sched = []
    qcycle = list(queries)
    for i, (layer, site, spec) in enumerate(QUERY_FAULTS):
        sched.append({"kind": "query", "query": qcycle[i % len(qcycle)],
                      "layer": layer, "site": site, "spec": spec})
    for layer, op in OP_ROUNDS:
        sched.append({"kind": "op", "op": op, "layer": layer})
    while len(sched) < rounds:
        layer, site, spec = rng.choice(QUERY_FAULTS)
        sched.append({"kind": "query", "query": rng.choice(qcycle),
                      "layer": layer, "site": site, "spec": spec})
    return sched


def ring_fires_since(t0_us: int, site: str) -> int:
    """Flight-recorder `failpoint` events for `site` recorded at or
    after t0_us -- the per-round fault/flight accounting source."""
    return sum(1 for e in get_flight_recorder().events(kind="failpoint")
               if e.get("site") == site and e["tsUs"] >= t0_us)


class ChaosRun:
    def __init__(self, args):
        self.args = args
        self.sf = args.sf
        self.failures: list = []       # invariant violations (exit 1)
        self.rounds: list = []         # determinism section rows
        self.expected_fires: dict = {}  # (site, action) -> total fires
        self.oracles: dict = {}
        self.plans: dict = {}

    def fail(self, message: str):
        print(f"INVARIANT VIOLATION: {message}", file=sys.stderr)
        self.failures.append(message)

    # -- per-round drivers ---------------------------------------------

    def warm(self, cluster: ChaosCluster, queries):
        """Fault-free oracles (and warm plan/fragment caches, so round
        timings -- and cache-dependent fire locations -- are identical
        between same-seed runs)."""
        for n in queries:
            q = tpch_query(n)
            plan = plan_sql(q.text, max_groups=q.max_groups,
                            join_capacity=q.join_capacity)
            local = run_query(plan, sf=self.sf,
                              default_join_capacity=q.join_capacity
                              or 1 << 16)
            cols = [(np.asarray(local.columns[c]),
                     np.asarray(local.nulls[c]))
                    for c in range(len(local.columns))]
            self.oracles[n] = canon_rows(cols)
            self.plans[n] = add_exchanges(plan_sql(
                q.text, max_groups=q.max_groups,
                join_capacity=q.join_capacity))
            got, _ = cluster.coordinator.execute(
                self.plans[n], sf=self.sf, timeout=self.args.timeout)
            if canon_rows(got) != self.oracles[n]:
                raise RuntimeError(
                    f"fault-free distributed q{n} does not match its "
                    f"local oracle -- engine bug, not chaos")

    def query_round(self, cluster: ChaosCluster, step: dict) -> str:
        n = step["query"]
        def go():
            cols, _ = cluster.coordinator.execute(
                self.plans[n], sf=self.sf, timeout=self.args.timeout)
            return canon_rows(cols)
        status, value = Watchdog(go, self.args.timeout + 30).run()
        if status == "hung":
            self.fail(f"q{n} under {step['site']}={step['spec']} HUNG "
                      f"past {self.args.timeout + 30}s")
            return "HUNG"
        if status == "error":
            return f"clean_failure:{type(value).__name__}"
        if value != self.oracles[n]:
            self.fail(f"q{n} under {step['site']}={step['spec']} "
                      f"returned WRONG rows")
            return "WRONG_RESULT"
        return "match"

    def op_round(self, cluster: ChaosCluster, step: dict) -> str:
        op = step["op"]
        if op == "announce":
            step["site"], step["spec"] = \
                "discovery.announce", "error(OSError):once"
            cluster.arm(step["site"], step["spec"])
            try:
                cluster.announcer.announce_once()
                return "UNFIRED"  # the once-error must have raised
            except OSError:
                pass
            cluster.announcer.announce_once()  # recovery announcement
            nodes = alive_nodes(cluster.discovery.url)
            return "recovered" if any(
                x["nodeId"] == "chaos-node" for x in nodes) \
                else "NOT_RECOVERED"
        if op == "probe":
            step["site"], step["spec"] = \
                "discovery.probe", "error(OSError):once"
            cluster.arm(step["site"], step["spec"])
            cluster.prober.probe_all_once()   # one probe eats the fault
            cluster.prober.probe_all_once()   # decay=0: full recovery
            healthy = sorted(cluster.prober.healthy())
            return "recovered" if healthy == sorted(
                u.rstrip("/") for u in cluster.urls) else "NOT_RECOVERED"
        if op == "admit":
            step["site"], step["spec"] = \
                "dispatcher.admit", "delay(100):once"
            cluster.arm(step["site"], step["spec"])
            c = StatementClient(cluster.statement.url,
                                "SELECT 1", deadline_s=60).drain()
            return "match" if c.data == [[1]] else "WRONG_RESULT"
        if op == "batch":
            # a FORMED query batch forced to collapse back to serial
            # dispatch mid-flight (PR 13): co-batchable point lookups
            # form one batch under a long window, the
            # dispatcher.batch_collapse failpoint fires before the
            # vmapped dispatch, and every member must still match its
            # serial oracle while the collapse is fully accounted
            # (reason counter + flight event + the generic fires/ring
            # legs the driver audits for every round)
            from presto_tpu.exec.batching import (batching_totals,
                                                  get_batching_executor)
            from presto_tpu.sql import sql as engine_sql
            step["site"], step["spec"] = \
                "dispatcher.batch_collapse", "error(RuntimeError):once"
            texts = ["SELECT custkey, name, acctbal FROM customer "
                     f"WHERE custkey = {k}" for k in (7, 11, 23, 42)]
            oracles = []
            for t in texts:
                r = engine_sql(t, sf=self.sf,
                               session={"query_batching": "false"})
                oracles.append(canon_rows(
                    [(np.asarray(r.columns[c]), np.asarray(r.nulls[c]))
                     for c in range(len(r.columns))]))
            before = batching_totals()["collapses"].get("failpoint", 0)
            cluster.arm(step["site"], step["spec"])
            sess = {"query_batching": "true", "batch_window_ms": "500",
                    "batch_hot_min": "1"}
            executor = get_batching_executor()
            results = [None] * len(texts)
            errors = [None] * len(texts)

            def member(i, t):
                try:
                    res = executor.try_execute(
                        t, sf=self.sf, session=sess,
                        query_id=f"chaos-batch-{i}")
                    if res is None:  # no batch formed for this member
                        res = engine_sql(t, sf=self.sf, session=sess)
                    results[i] = res
                except BaseException as e:  # noqa: BLE001 - verdict
                    errors[i] = e

            threads = [threading.Thread(target=member, args=(i, t),
                                        daemon=True)
                       for i, t in enumerate(texts)]
            threads[0].start()      # the leader opens the window ...
            time.sleep(0.1)
            for t in threads[1:]:   # ... followers join inside it
                t.start()
            for t in threads:
                t.join(60)
            if any(not r and e is None
                   for r, e in zip(results, errors)):
                self.fail("batch round: a member HUNG past 60s")
                return "HUNG"
            for i, e in enumerate(errors):
                if e is not None:
                    self.fail(f"batch round: member {i} failed under "
                              f"collapse: {type(e).__name__}: {e}")
                    return f"clean_failure:{type(e).__name__}"
            for i, r in enumerate(results):
                got = canon_rows(
                    [(np.asarray(r.columns[c]), np.asarray(r.nulls[c]))
                     for c in range(len(r.columns))])
                if got != oracles[i]:
                    self.fail(f"batch round: member {i} under forced "
                              f"collapse returned WRONG rows")
                    return "WRONG_RESULT"
            delta = batching_totals()["collapses"].get("failpoint", 0) \
                - before
            if delta != 1:
                self.fail(f"batch round: collapse counter moved {delta} "
                          f"(expected exactly 1 collapsed batch)")
                return "UNACCOUNTED_COLLAPSE"
            if not get_flight_recorder().events(kind="batch_collapse"):
                self.fail("batch round: collapse without a "
                          "batch_collapse flight event")
                return "NO_FLIGHT_EVENT"
            return "match+collapsed"
        if op == "fail_dump":
            step["site"], step["spec"] = \
                "statement.execute", "error(RuntimeError):once"
            cluster.arm(step["site"], step["spec"])
            qid = None
            try:
                c = StatementClient(cluster.statement.url,
                                    "SELECT 2", deadline_s=60)
                qid = c.query_id
                c.drain()
                return "UNFIRED"
            except QueryError:
                pass
            # the failed query must auto-dump, and the dump must carry
            # the failpoint event (full fault accounting, dump leg)
            deadline = time.time() + 5
            path = None
            while path is None and time.time() < deadline:
                path = get_flight_recorder().dump_path(qid) \
                    if qid else None
                if path is None:
                    time.sleep(0.05)
            if path is None:
                self.fail("failed statement produced no flight dump")
                return "NO_DUMP"
            with open(path) as f:
                dumped = [json.loads(line) for line in f]
            if not any(e.get("kind") == "failpoint" and
                       e.get("site") == "statement.execute"
                       for e in dumped):
                self.fail(f"flight dump {path} missing the injected "
                          f"failpoint event")
                return "DUMP_MISSING_FAULT"
            return "clean_failure:dumped"
        if op == "stuck":
            # the hang failpoint's DETERMINISTIC detector (PR 10): a
            # bounded worker hang well past the stuck threshold must
            # fire the stuck-progress watchdog -- counter bump +
            # flight-recorder stuck_progress event -- while the query
            # still completes and matches its oracle afterwards
            from presto_tpu.server.watchdog import stuck_totals
            step["site"], step["spec"] = \
                "worker.run_task", "hang(1200):once"
            n = min(self.oracles)  # deterministic query choice
            before = stuck_totals()
            cluster.arm(step["site"], step["spec"])
            os.environ["PRESTO_TPU_STUCK_MS"] = "300"
            try:
                def go():
                    cols, _ = cluster.coordinator.execute(
                        self.plans[n], sf=self.sf,
                        timeout=self.args.timeout)
                    return canon_rows(cols)
                status, value = Watchdog(go, self.args.timeout + 30).run()
            finally:
                os.environ.pop("PRESTO_TPU_STUCK_MS", None)
            if status == "hung":
                self.fail(f"stuck round: q{n} HUNG past the deadline")
                return "HUNG"
            if status == "error":
                return f"clean_failure:{type(value).__name__}"
            if value != self.oracles[n]:
                self.fail(f"stuck round: q{n} returned WRONG rows")
                return "WRONG_RESULT"
            if stuck_totals() <= before:
                self.fail("stuck round: the hang fired but the "
                          "stuck-progress watchdog never did")
                return "UNDETECTED"
            if not get_flight_recorder().events(kind="stuck_progress"):
                self.fail("stuck round: watchdog fired without a "
                          "stuck_progress flight event")
                return "NO_FLIGHT_EVENT"
            return "match+stuck_detected"
        if op == "demote":
            # forced mid-query fusion demotion (PR 11): the
            # fusion.demote failpoint demotes the first fused multi-op
            # span a worker dispatches; that query must STILL match its
            # oracle (the materialized region executor is bit-identical
            # to the fused program), the demotion must land as a
            # fusion_demotion flight event, and the round clears the
            # sticky demotion afterwards so later rounds run fused
            from presto_tpu.exec.regions import fusion_memory
            step["site"], step["spec"] = "fusion.demote", "error:once"
            n = min(self.oracles)  # deterministic query choice
            cluster.arm(step["site"], step["spec"])
            try:
                def go():
                    cols, _ = cluster.coordinator.execute(
                        self.plans[n], sf=self.sf,
                        timeout=self.args.timeout)
                    return canon_rows(cols)
                status, value = Watchdog(go, self.args.timeout + 30).run()
            finally:
                demoted = fusion_memory().snapshot()["demoted"]
                fusion_memory().clear()
            if status == "hung":
                self.fail(f"fusion round: q{n} HUNG past the deadline")
                return "HUNG"
            if status == "error":
                return f"clean_failure:{type(value).__name__}"
            if value != self.oracles[n]:
                self.fail(f"fusion round: q{n} under forced demotion "
                          f"returned WRONG rows")
                return "WRONG_RESULT"
            if not demoted:
                self.fail("fusion round: the demote failpoint fired "
                          "but no span was demoted")
                return "NOT_DEMOTED"
            if not get_flight_recorder().events(kind="fusion_demotion"):
                self.fail("fusion round: demotion without a "
                          "fusion_demotion flight event")
                return "NO_FLIGHT_EVENT"
            return "match+demoted"
        if op == "donation":
            # forced donation-path failure (this PR): with buffer
            # donation on under the materialized region executor, the
            # donation.apply failpoint kills the prepare step for the
            # first donation-eligible region BEFORE any buffer is
            # consumed -- the dispatch must collapse to the undonated
            # form with rows still matching the donation-off oracle,
            # the fallback counted presto_tpu_donation_fallbacks_total,
            # and a donation_fallback flight event on the timeline
            from presto_tpu.exec.donation import donation_totals
            from presto_tpu.queries.tpch_sql import tpch_query
            from presto_tpu.sql import sql as engine_sql
            step["site"], step["spec"] = "donation.apply", "error:once"
            q = tpch_query(6)
            oracle = engine_sql(q.text, sf=self.sf,
                                session={"fusion": False},
                                max_groups=q.max_groups)
            before = donation_totals()["fallbacks"]
            cluster.arm(step["site"], step["spec"])
            sess = {"fusion": False, "buffer_donation": True}
            try:
                res = engine_sql(q.text, sf=self.sf, session=sess,
                                 max_groups=q.max_groups)
            except BaseException as e:  # noqa: BLE001 - verdict
                self.fail(f"donation round: query FAILED under forced "
                          f"fallback: {type(e).__name__}: {e}")
                return f"clean_failure:{type(e).__name__}"
            if res.canonical_rows() != oracle.canonical_rows():
                self.fail("donation round: forced fallback returned "
                          "WRONG rows")
                return "WRONG_RESULT"
            if donation_totals()["fallbacks"] - before < 1:
                self.fail("donation round: the failpoint fired but no "
                          "fallback was counted")
                return "UNACCOUNTED_FALLBACK"
            if not get_flight_recorder().events(
                    kind="donation_fallback"):
                self.fail("donation round: fallback without a "
                          "donation_fallback flight event")
                return "NO_FLIGHT_EVENT"
            return "match+fallback"
        if op == "elastic":
            # the elastic-fleet acceptance round: an 8-worker
            # discovery-backed cluster changes shape MID-QUERY -- kill
            # 2 workers, add 2, gracefully drain 1 (pages migrating to
            # a peer) -- and the query must still match its fault-free
            # oracle, the drained worker must end DRAINED with ZERO
            # unreplayed buffered pages, and the armed drain_stall
            # fault must be fully accounted like every other round
            from presto_tpu.server.client import WorkerClient
            step["site"], step["spec"] = \
                "worker.drain_stall", "delay(100):once"
            n = min(self.oracles)  # deterministic query choice
            cluster.arm(step["site"], step["spec"])
            disc = DiscoveryServer().start()
            fleet = [TpuWorkerServer(sf=self.sf, discovery_url=disc.url,
                                     announce_interval_s=0.2).start()
                     for _ in range(8)]
            try:
                deadline = time.time() + 10
                while time.time() < deadline and \
                        len(alive_nodes(disc.url)) < 8:
                    time.sleep(0.05)
                coord = Coordinator(discovery_url=disc.url)
                drained_w, peer_w = fleet[2], fleet[3]

                def churn():
                    time.sleep(0.15)
                    fleet[0].kill()                       # kill 2
                    fleet[1].kill()  # (ungraceful: no unannounce)
                    for _ in range(2):                    # add 2
                        fleet.append(TpuWorkerServer(
                            sf=self.sf, discovery_url=disc.url,
                            announce_interval_s=0.2).start())
                    WorkerClient(                         # drain 1
                        f"http://127.0.0.1:{drained_w.port}", 10).drain(
                        migrate_to=f"http://127.0.0.1:{peer_w.port}",
                        timeout_ms=20000)
                churner = threading.Thread(target=churn, daemon=True)

                def go():
                    churner.start()
                    cols, _ = coord.execute(self.plans[n], sf=self.sf,
                                            timeout=self.args.timeout)
                    return canon_rows(cols)
                status, value = Watchdog(go, self.args.timeout + 30).run()
                churner.join(30)
                if status == "hung":
                    self.fail(f"elastic round: q{n} HUNG past deadline")
                    return "HUNG"
                if status == "error":
                    self.fail(f"elastic round: q{n} failed under fleet "
                              f"churn: {type(value).__name__}: {value}")
                    return f"clean_failure:{type(value).__name__}"
                if value != self.oracles[n]:
                    self.fail(f"elastic round: q{n} under kill/add/"
                              f"drain returned WRONG rows")
                    return "WRONG_RESULT"
                # the drained worker must settle DRAINED with zero
                # unreplayed pages (the graceful-exit acceptance bar)
                deadline = time.time() + 25
                st = drained_w.drain_status()
                while time.time() < deadline and \
                        st["state"] != "DRAINED":
                    time.sleep(0.1)
                    st = drained_w.drain_status()
                if st["state"] != "DRAINED" or \
                        st["unreplayedPages"] != 0:
                    self.fail(f"elastic round: drained worker ended "
                              f"{st}")
                    return "UNREPLAYED_PAGES"
                return "match+drained"
            finally:
                for w in fleet:
                    try:
                        w.stop()
                    except Exception:  # noqa: BLE001 - already stopped
                        pass
                disc.stop()
        if op == "speculate":
            # straggler rescue: ONE task hangs well past the
            # speculation threshold; the coordinator must re-run it
            # elsewhere, the speculative copy must WIN (counter > 0),
            # and the result must match the oracle -- speculation never
            # duplicates or drops rows (first-result-wins dedup)
            from presto_tpu.server.coordinator import speculation_totals
            step["site"], step["spec"] = \
                "worker.run_task", "hang(1800):once"
            n = min(self.oracles)  # deterministic query choice
            before = speculation_totals()["wins"]
            cluster.arm(step["site"], step["spec"])
            spec_coord = Coordinator(cluster.urls,
                                     speculation_threshold_ms=300)

            def go():
                cols, _ = spec_coord.execute(self.plans[n], sf=self.sf,
                                             timeout=self.args.timeout)
                return canon_rows(cols)
            status, value = Watchdog(go, self.args.timeout + 30).run()
            if status == "hung":
                self.fail(f"speculate round: q{n} HUNG past deadline")
                return "HUNG"
            if status == "error":
                # this round's whole point is that speculation RESCUES
                # the straggler -- a clean failure means it did not
                self.fail(f"speculate round: q{n} failed instead of "
                          f"being rescued: {type(value).__name__}: "
                          f"{value}")
                return "SPEC_FAILURE"
            if value != self.oracles[n]:
                self.fail(f"speculate round: q{n} returned WRONG rows "
                          f"(duplicate/missing under speculation)")
                return "WRONG_RESULT"
            if speculation_totals()["wins"] <= before:
                self.fail("speculate round: the straggler hung but no "
                          "speculative attempt won")
                return "NO_SPEC_WIN"
            time.sleep(2.0)  # let the hung loser wake and self-abort
            return "match+spec_win"
        if op == "hang_deadline":
            step["site"], step["spec"] = \
                "statement.execute", "hang(1500):once"
            cluster.arm(step["site"], step["spec"])
            try:
                StatementClient(cluster.statement.url, "SELECT 3",
                                deadline_s=0.7).drain()
                return "NO_TIMEOUT"
            except QueryError as e:
                outcome = f"clean_failure:{e.error_name}"
            time.sleep(1.2)  # let the hung engine thread drain
            return outcome
        raise ValueError(op)

    # -- the soak ------------------------------------------------------

    def run(self) -> int:
        args = self.args
        queries = [int(x) for x in args.queries.split(",") if x.strip()]
        failpoints.disarm_all()
        totals0 = dict(failpoints.failpoint_totals())
        set_flight_recorder(FlightRecorder(
            dump_dir=tempfile.mkdtemp(prefix="presto_tpu_chaos_")))
        # invariant 4: the lock-order witness rides the whole soak --
        # every OrderedLock acquire on every tier is order-checked, and
        # ONE inversion anywhere fails the round that provoked it
        wlocks.reset_witness()
        wlocks.arm_witness()
        witness0 = wlocks.witness_violations_total()
        witness_seen = 0  # records consumed by per-round reporting
        cluster = ChaosCluster(self.sf, workers=args.workers)
        t_run0 = time.time()
        try:
            print(f"warming oracles for q{queries} at sf={self.sf} ...")
            self.warm(cluster, queries)
            schedule = build_schedule(args.seed, queries, args.schedule)
            prev_scrapes = cluster.scrapes()
            for i, step in enumerate(schedule):
                cluster.disarm_all()
                t0_us = int(time.time() * 1e6)
                if step["kind"] == "query":
                    cluster.arm(step["site"], step["spec"])
                    outcome = self.query_round(cluster, step)
                else:
                    outcome = self.op_round(cluster, step)
                # fault accounting leg 1: admin-API fire counts vs the
                # flight-recorder ring, while this round's arm is live
                doc = cluster.armed_doc()
                fires = doc["armed"].get(step["site"], {}).get("fires", 0)
                action = step["spec"].split(":")[0].split("(")[0]
                self.expected_fires[(step["site"], action)] = \
                    self.expected_fires.get((step["site"], action), 0) \
                    + fires
                ring = ring_fires_since(t0_us, step["site"])
                if ring != fires:
                    self.fail(
                        f"round {i}: {step['site']} fired {fires} but "
                        f"the flight ring recorded {ring}")
                # invariant 2: counters never decrease, audited from
                # real scrapes every round
                scrapes = cluster.scrapes()
                for ep in scrapes:
                    for v in monotonicity_violations(prev_scrapes[ep],
                                                     scrapes[ep]):
                        self.fail(f"round {i}: counter decreased on "
                                  f"{ep}: {v}")
                prev_scrapes = scrapes
                # invariant 4: no lock-order inversion this round (the
                # witness catches the FIRST inconsistent acquisition
                # deterministically; which round provoked it is part
                # of the failure report)
                wnow = wlocks.witness_violations_total()
                if wnow != witness0:
                    # only the records NEW since the last round: each
                    # inversion is attributed to (and fails) exactly
                    # the round that provoked it
                    for v in wlocks.witness_violations()[witness_seen:]:
                        self.fail(
                            f"round {i}: lock-order inversion: "
                            f"acquired {v['acquiring']} while holding "
                            f"{v['held']} at {v['site']} (established "
                            f"order {' -> '.join(v['reversePath'])} "
                            f"from {v['reverseSite']})")
                    witness_seen = len(wlocks.witness_violations())
                    witness0 = wnow
                row = {"round": i, "kind": step["kind"],
                       "layer": step["layer"],
                       "site": step["site"], "spec": step["spec"],
                       "fires": fires, "outcome": outcome}
                if step["kind"] == "query":
                    row["query"] = step["query"]
                else:
                    row["op"] = step["op"]
                self.rounds.append(row)
                print(f"  round {i:2d} [{step['layer']:10s}] "
                      f"{step['site']}={step['spec']} fires={fires} "
                      f"-> {outcome}")
                if outcome in ("UNFIRED", "NOT_RECOVERED", "NO_TIMEOUT"):
                    # op-round regressions (broken recovery, broken
                    # client deadline) must fail the gate, not just
                    # print an odd-looking row
                    self.fail(f"round {i}: {step['site']} outcome "
                              f"{outcome}")
                if fires == 0:
                    self.fail(f"round {i}: {step['site']} never fired "
                              f"(site unreachable in this schedule)")
            cluster.disarm_all()
            # fault accounting leg 2: lifetime registry/metrics totals
            reg_delta = {}
            for key, v in failpoints.failpoint_totals().items():
                d = v - totals0.get(key, 0)
                if d:
                    reg_delta[key] = d
            if reg_delta != self.expected_fires:
                self.fail(f"registry fire totals {reg_delta} != "
                          f"per-round accounting {self.expected_fires}")
            scraped = failpoint_counter_totals(
                cluster.scrapes()["worker0"])
            for key, want in self.expected_fires.items():
                have = scraped.get(key, 0) - totals0.get(key, 0)
                if have != want:
                    self.fail(f"/v1/metrics hit counter for {key} is "
                              f"{have}, expected {want}")
            # coverage: the acceptance floor for a smoke run
            fired_layers = {r["layer"] for r in self.rounds
                            if r["fires"] > 0}
            fired_sites = {r["site"] for r in self.rounds
                           if r["fires"] > 0}
            need = {"exchange", "serde", "task", "memory", "discovery"}
            if len(fired_sites) < 5 or not need <= fired_layers:
                self.fail(f"coverage floor missed: {len(fired_sites)} "
                          f"sites over layers {sorted(fired_layers)}")
        finally:
            failpoints.disarm_all()
            wlocks.disarm_witness()
            cluster.stop()
        return self.report(time.time() - t_run0, queries)

    def report(self, wall_s: float, queries) -> int:
        determinism = {"seed": self.args.seed, "sf": self.sf,
                       "queries": queries, "rounds": self.rounds}
        digest = hashlib.sha256(json.dumps(
            determinism, sort_keys=True).encode()).hexdigest()[:16]
        doc = {"determinism": determinism, "digest": digest,
               "invariants": {
                   "correct_or_clean": not any(
                       "WRONG" in r["outcome"] or r["outcome"] in
                       ("HUNG", "NOT_RECOVERED", "NO_TIMEOUT", "UNFIRED",
                        "UNDETECTED", "NO_FLIGHT_EVENT", "NOT_DEMOTED",
                        "NO_SPEC_WIN", "SPEC_FAILURE",
                        "UNREPLAYED_PAGES", "UNACCOUNTED_COLLAPSE",
                        "UNACCOUNTED_DEGRADATION",
                        "NOT_DEGRADED_TO_TOTALS")
                       for r in self.rounds),
                   "no_counter_decrease": not any(
                       "counter decreased" in f for f in self.failures),
                   "fault_accounting": not any(
                       "accounting" in f or "hit counter" in f
                       or "flight" in f for f in self.failures),
                   "lock_order": not any(
                       "lock-order inversion" in f
                       for f in self.failures)},
               "violations": self.failures,
               "wallSeconds": round(wall_s, 2)}
        path = self.args.report or os.path.join(
            tempfile.gettempdir(),
            f"presto_tpu_chaos_seed{self.args.seed}.json")
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        ok = not self.failures
        print(f"chaos: {len(self.rounds)} rounds, "
              f"{sum(r['fires'] for r in self.rounds)} faults fired, "
              f"digest {digest}, {wall_s:.1f}s -> "
              f"{'OK' if ok else 'INVARIANT VIOLATIONS'}")
        print(f"report: {path}")
        return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chaos")
    ap.add_argument("--seed", type=int, default=42,
                    help="schedule + trigger seed (default 42)")
    ap.add_argument("--queries", default="",
                    help="comma-separated TPC-H numbers (default: "
                         "smoke/full preset)")
    ap.add_argument("--schedule", type=int, default=0,
                    help="total rounds (0 = the coverage prefix only)")
    ap.add_argument("--smoke", action="store_true",
                    help="small committed schedule (<60s): the "
                         "lint_all.sh pre-PR gate")
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=60.0,
                    help="per-query coordinator deadline (watchdog "
                         "adds 30s)")
    ap.add_argument("--report", default="",
                    help="JSON report path (default: under $TMPDIR)")
    args = ap.parse_args(argv)
    if not args.queries:
        args.queries = ",".join(
            str(q) for q in (SMOKE_QUERIES if args.smoke
                             else FULL_QUERIES))
    try:
        return ChaosRun(args).run()
    except KeyboardInterrupt:
        raise
    except Exception as e:  # noqa: BLE001 - harness error, not verdict
        import traceback
        traceback.print_exc()
        print(f"chaos: internal error: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
