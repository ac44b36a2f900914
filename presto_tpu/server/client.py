"""Worker HTTP client: the remote-task / exchange-client consumer side.

Reference surface: HttpRemoteTaskWithEventLoop.java:157 (sendUpdate:981
POSTing TaskUpdateRequests) and ExchangeClient.java:255 / PageBufferClient
(token/ack SerializedPage pull) -- collapsed into one small synchronous
client suitable for tests and cross-slice fetches.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import failpoints
from .. import types as T
from ..plan import nodes as N
from ..serde import PageCodec, deserialize_page
from ..utils.backoff import Backoff

__all__ = ["WorkerClient"]


class _HttpStatusError(urllib.error.HTTPError):
    """Status-code error with urllib's .code surface, so existing
    callers (410-token checks, 401 auth tests) keep one catch type."""

    def __init__(self, status: int, data: bytes, path: str):
        import io
        super().__init__(path, status,
                         data.decode("utf-8", "replace")[:500], None,
                         io.BytesIO(data))


class WorkerClient:
    """Persistent-connection client: one keep-alive HTTP/1.1 connection
    per (client, thread), reused across the token/ack pull loop and task
    polls (the reference's pooled PageBufferClient/Netty channel; the
    round-4 per-request urllib connections cost a TCP handshake per
    page). Stale keep-alive sockets (server-side idle close) retry once
    on a fresh connection."""

    def __init__(self, base_url: str, timeout: float = 30.0,
                 shared_secret: Optional[str] = None):
        from .auth import make_authenticator
        self.base = base_url.rstrip("/")
        self.timeout = timeout
        self._secret = shared_secret  # re-target (moved pages) clients
        self._auth = make_authenticator(shared_secret, "client")
        u = urllib.parse.urlsplit(self.base)
        self._scheme = u.scheme or "http"
        self._host, self._port = u.hostname, u.port
        self._prefix = u.path.rstrip("/")
        self._local = threading.local()

    def _connect(self) -> http.client.HTTPConnection:
        if self._scheme == "https":
            from .tls import client_ssl_context
            return http.client.HTTPSConnection(
                self._host, self._port, timeout=self.timeout,
                context=client_ssl_context())
        return http.client.HTTPConnection(self._host, self._port,
                                          timeout=self.timeout)

    def _request(self, method: str, path: str, body: Optional[bytes] = None):
        from .auth import bearer_headers
        from .tracing import TRACE_HEADER, current_context
        headers = dict(bearer_headers(self._auth))
        if body is not None:
            headers["Content-Type"] = "application/json"
        ctx = current_context()
        if ctx is not None:
            # every hop this thread makes on a query's behalf (task
            # create/status, exchange-buffer fetch) carries the trace
            headers[TRACE_HEADER] = ctx.header()
        last_err = None
        for attempt in (0, 1):
            conn = getattr(self._local, "conn", None)
            if conn is None:
                conn = self._connect()
                self._local.conn = conn
            try:
                if failpoints.ARMED:
                    # drop_conn here is an injected stale keep-alive
                    # socket: a ConnectionError the retry below handles
                    failpoints.hit("client.request")
                conn.request(method, self._prefix + path, body=body,
                             headers=headers)
                resp = conn.getresponse()
                data = resp.read()
                if resp.status >= 400:
                    self._raise_http(resp.status, data, path)
                return data, dict(resp.getheaders())
            except (http.client.HTTPException, ConnectionError,
                    BrokenPipeError, TimeoutError) as e:
                if isinstance(e, _HttpStatusError):
                    raise
                self._local.conn = None
                try:
                    conn.close()
                except Exception as ce:  # noqa: BLE001 - already
                    # failing; `ce` not `e`: an inner `as e` would
                    # delete the outer binding on handler exit
                    from .metrics import record_suppressed
                    record_suppressed("worker_client", "conn_close", ce)
                last_err = e
                if attempt == 1:
                    raise
                # stale keep-alive retry: on the flight-recorder
                # timeline so a post-mortem sees flaky transport
                from .flight_recorder import record_event
                record_event("http_retry", path=path,
                             error=f"{type(e).__name__}: {e}")
                # brief seeded backoff before the fresh-connection
                # retry: a reset usually means the peer is busy or
                # mid-restart, and an instant retry piles on
                Backoff(base_s=0.02, cap_s=0.25, seed=path).sleep()
        raise last_err  # unreachable

    @staticmethod
    def _raise_http(status: int, data: bytes, path: str):
        raise _HttpStatusError(status, data, path)

    def info(self) -> dict:
        data, _ = self._request("GET", "/v1/info")
        return json.loads(data)

    def history(self) -> dict:
        """The worker's completed-query history slice (GET /v1/history)
        -- authenticated/TLS'd like every other internal hop, so
        the statement tier's cluster merge works on secured clusters."""
        data, _ = self._request("GET", "/v1/history")
        return json.loads(data)

    def datapath(self) -> dict:
        """The worker's per-hop data-path slice (GET /v1/datapath),
        pulled over the same authenticated transport as history() so
        the statement tier's cluster merge works on secured clusters."""
        data, _ = self._request("GET", "/v1/datapath")
        return json.loads(data)

    def accuracy(self) -> dict:
        """The worker's estimate-accuracy slice (GET /v1/accuracy),
        pulled over the same authenticated transport as history() so
        the statement tier's cluster merge works on secured clusters."""
        data, _ = self._request("GET", "/v1/accuracy")
        return json.loads(data)

    def status(self) -> dict:
        """The worker's enriched NodeStatus (GET /v1/status): liveness,
        uptime, version, running tasks, memory-pool occupancy -- the
        per-worker row of the statement tier's /v1/cluster overview."""
        data, _ = self._request("GET", "/v1/status")
        return json.loads(data)

    def submit(self, task_id: str, plan: N.PlanNode, sf: float = 0.01,
               session: Optional[dict] = None) -> dict:
        return self.submit_body(task_id, {"plan": N.to_json(plan), "sf": sf,
                                          "session": session or {}})

    def submit_body(self, task_id: str, body: dict) -> dict:
        """Raw TaskUpdateRequest submission (scanRanges / remoteSources
        and other fields pass through verbatim)."""
        data, _ = self._request("POST", f"/v1/task/{task_id}",
                                json.dumps(body).encode())
        return json.loads(data)

    def migrate(self, task_id: str, doc: dict) -> dict:
        """Offer a finished task's buffered pages for adoption
        (graceful-drain migration hop; POST /v1/task/{id}/migrate)."""
        data, _ = self._request("POST", f"/v1/task/{task_id}/migrate",
                                json.dumps(doc).encode())
        return json.loads(data)

    def drain(self, migrate_to: Optional[str] = None,
              timeout_ms: Optional[float] = None) -> dict:
        """Start the worker's graceful drain (POST /v1/worker/drain);
        returns the drain-status document."""
        body = {}
        if migrate_to:
            body["migrateTo"] = migrate_to
        if timeout_ms is not None:
            body["timeoutMs"] = float(timeout_ms)
        data, _ = self._request("POST", "/v1/worker/drain",
                                json.dumps(body).encode())
        return json.loads(data)

    def drain_status(self) -> dict:
        data, _ = self._request("GET", "/v1/worker/drain")
        return json.loads(data)

    def task_info(self, task_id: str) -> dict:
        data, _ = self._request("GET", f"/v1/task/{task_id}")
        return json.loads(data)

    def wait(self, task_id: str, timeout: float = 60.0) -> dict:
        deadline = time.time() + timeout
        info = None
        while time.time() < deadline:
            info = self.task_info(task_id)
            self._note_progress(task_id, info)
            if info["state"] in ("FINISHED", "FAILED", "ABORTED"):
                return info
            time.sleep(0.05)
        state = info["state"] if info else "<never polled>"
        raise TimeoutError(f"task {task_id} still {state}")

    def _note_progress(self, task_id: str, info: dict) -> None:
        """Fold the progress heartbeat riding a TaskInfo poll into the
        local registry (exec/progress.py), tagged with the ambient
        trace id -- how the coordinator/statement process learns what
        every remote task is doing mid-flight. A terminal TaskInfo
        state finishes the entry even when the shipped snapshot lags
        behind it (the worker flips the task terminal a beat before
        its own finish_task runs): wait() stops polling on the
        terminal state, so this poll is the last chance to close the
        entry. Never raises."""
        from .tracing import current_context
        if not isinstance(info, dict):
            return
        from ..exec.progress import finish_task, note_remote
        doc = info.get("progress")
        if doc:
            ctx = current_context()
            note_remote(task_id, doc, worker=self.base,
                        query=ctx.trace_id if ctx is not None else None)
        state = info.get("state")
        if state in ("FINISHED", "FAILED", "ABORTED"):
            finish_task(task_id, state)

    def fetch_results(self, task_id: str, types: Sequence[T.Type],
                      codec: PageCodec = PageCodec(), buffer_id: int = 0,
                      ack: bool = True
                      ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Token/ack pull loop until the buffer reports complete; returns
        concatenated (values, nulls) per column. Raises on deadline or on
        HTTP 410 (pages acked away by a prior consumer attempt). A
        drained-away task (``X-Presto-Task-Moved`` header) re-targets
        the adopting peer and resumes the SAME absolute token, so the
        page stream replays exactly once across the migration."""
        token = 0
        pages = []
        target = self  # re-targeted when the task's pages migrated
        moves = 0
        last_move = None  # last followed move target (normalized url)
        peer_misses = 0  # consecutive 404s after following a move
        deadline = time.time() + self.timeout
        while True:
            if time.time() > deadline:
                raise TimeoutError(
                    f"results of {task_id}/{buffer_id} not complete after "
                    f"{self.timeout}s")
            try:
                data, headers = target._request(
                    "GET",
                    f"/v1/task/{task_id}/results/{buffer_id}/{token}")
            except urllib.error.HTTPError as e:
                if e.code == 404 and target is not self:
                    # the adopt POST may still be in flight on the
                    # peer -- or it FAILED and the origin rolled its
                    # moved_to flip back and still serves the pages:
                    # retry the peer briefly, then fall back to the
                    # origin (which either serves directly or re-issues
                    # the move once the adopt finally landed)
                    peer_misses += 1
                    if peer_misses >= 10:
                        peer_misses = 0
                        target = self
                        continue
                    time.sleep(0.05)
                    continue
                raise
            peer_misses = 0
            moved = headers.get("X-Presto-Task-Moved")
            if moved:
                # count only moves to a NEW target toward the loop cap:
                # re-following the SAME pending migration after an
                # origin fallback is the slow-adopt wait (bounded by
                # the deadline), not a redirect chain
                if moved.rstrip("/") != last_move:
                    moves += 1
                    if moves >= 8:
                        raise RuntimeError(
                            f"task {task_id} pages moved too many "
                            f"times (migration loop?)")
                    last_move = moved.rstrip("/")
                target = WorkerClient(moved, self.timeout,
                                      shared_secret=self._secret)
                continue
            complete = headers.get("X-Presto-Buffer-Complete") == "true"
            next_token = int(headers.get("X-Presto-Page-Next-Token", token))
            if data:
                pages.append(deserialize_page(data, types, codec))
                if ack:
                    target._request(
                        "GET",
                        f"/v1/task/{task_id}/results/{buffer_id}/{next_token}/acknowledge")
                token = next_token
            elif complete:
                break
            else:
                time.sleep(0.02)
        if not pages:
            return [(np.array([]), np.array([], dtype=bool)) for _ in types]
        out = []
        for c in range(len(types)):
            vals = np.concatenate([p[c][0] for p in pages])
            nulls = np.concatenate([p[c][1] for p in pages])
            out.append((vals, nulls))
        return out

    def abort(self, task_id: str) -> dict:
        data, _ = self._request("DELETE", f"/v1/task/{task_id}")
        return json.loads(data)


def pull_worker_docs(worker_urls, timeout: float, fetch,
                     component: str, site: str = "cluster_pull",
                     parallel: bool = False, placeholder=None):
    """The one best-effort cluster pull the merged surfaces
    (/v1/datapath, /v1/history, /v1/cluster) share: fetch one document
    per reachable worker through an authenticated WorkerClient,
    skip-and-count the unreachable ones (never an error).
    ``fetch(client) -> dict``; returns (docs, workers_pulled) with
    docs in input-URL order; workers_pulled counts REACHABLE workers
    only. ``parallel`` fans the pulls out on a small thread pool --
    the live /v1/cluster probe uses it so ONE dead worker costs one
    timeout per frame, not one per dead worker. ``placeholder(url) ->
    dict`` keeps unreachable workers IN the doc list (the fleet view's
    DEAD rows) instead of silently dropping them."""
    from .metrics import record_suppressed

    def pull(url):
        try:
            return fetch(WorkerClient(str(url), timeout))
        except Exception as e:  # noqa: BLE001 - a dead worker must not
            # fail the cluster view; the gap is counted on /v1/metrics
            record_suppressed(component, site, e)
            return None
    urls = list(worker_urls or ())
    if parallel and len(urls) > 1:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=min(8, len(urls))) as pool:
            results = list(pool.map(pull, urls))
    else:
        results = [pull(u) for u in urls]
    alive = sum(1 for d in results if d is not None)
    if placeholder is not None:
        docs = [d if d is not None else placeholder(str(u))
                for u, d in zip(urls, results)]
    else:
        docs = [d for d in results if d is not None]
    return docs, alive
