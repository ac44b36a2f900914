"""ASCII waterfall rendering for stitched distributed traces.

The consumer of server/tracing.py's one-trace-per-query documents
(``GET /v1/trace/{queryId}``): build the span tree from parentId edges,
render a fixed-width waterfall aligned to the trace's own time axis,
and attribute the critical path -- walked BACKWARD from the trace's
last-ending moment, so each interval of wall time is owned by the span
that was actually running latest (children own their windows, gaps
between children belong to the parent). The stage with the most
attributed time is named explicitly: the first question every perf
investigation asks (Flare's compile-vs-execute split and the GPU-Presto
kernel-time attribution are both one glance at this line).

Spans are the exported dicts {traceId, spanId, parentId, name, startUs,
endUs, attributes}. Orphans (a parentId missing from the trace -- a
partial stitch, e.g. a worker whose final status poll was lost) render
as extra roots rather than disappearing: an incomplete trace should
LOOK incomplete, not wrong.

Used by scripts/trace_view.py (CLI) and presto_tpu/cli.py --trace.

The second reader here is of the PROFILER's trace (an ``.xplane.pb``):
``device_time_by_scope`` sums the device's time by the scope the
program gave each op (exec/planner.py: ``<NodeType>.<k>``, the ``ops/``
function) under the region of the ``presto:dispatch`` span it ran in,
and splits the device's idle time inside each statement across the
``presto:<name>`` spans (exec/stats.py) that overlap it. Statements,
regions and spans are all read from the program's own annotations. The
arithmetic is in ``scope_seconds``, ``statement_intervals`` and
``gap_seconds_by_span``, over plain event lists, so that it is checked
without a chip.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["build_tree", "critical_path", "critical_path_summary",
           "fetch_trace", "render_waterfall", "scope_of", "scope_seconds",
           "statement_intervals", "gap_seconds_by_span",
           "device_time_by_scope", "render_scopes"]


def fetch_trace(url: str, query_id: Optional[str] = None,
                timeout: float = 10.0) -> dict:
    """GET a stitched trace document: `url` is the full
    ``/v1/trace/{id}`` URL, or a coordinator/worker base URL with
    `query_id` supplied. The one fetch path every consumer (cli
    --trace, scripts/trace_view.py) shares; raises on HTTP/parse
    errors so each caller decides how a missing trace degrades."""
    import json
    import urllib.request
    if query_id is not None:
        url = f"{url.rstrip('/')}/v1/trace/{query_id}"
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def build_tree(spans: List[dict]) -> Tuple[List[dict], Dict[str, List[dict]]]:
    """(roots, children-by-spanId), both start-ordered. A span whose
    parentId is absent from the trace counts as a root (see module
    docstring: partial stitches stay visible)."""
    ids = {s["spanId"] for s in spans}
    roots: List[dict] = []
    children: Dict[str, List[dict]] = {}
    for s in spans:
        pid = s.get("parentId")
        if pid is not None and pid in ids and pid != s["spanId"]:
            children.setdefault(pid, []).append(s)
        else:
            roots.append(s)
    order = lambda s: (s["startUs"], -s["endUs"])  # noqa: E731

    def reach(from_ids: List[str], seen: set) -> None:
        while from_ids:
            sid = from_ids.pop()
            if sid in seen:
                continue
            seen.add(sid)
            from_ids.extend(k["spanId"] for k in children.get(sid, ()))

    # parentId cycles (a buggy/foreign worker's shipped spans -- stitch
    # validates ids and timestamps, not edges) leave spans reachable
    # from no root; break each cycle by promoting its earliest span,
    # dropping that one edge, so malformed traces render degraded (the
    # module promise) instead of crashing or losing spans
    seen: set = set()
    reach([s["spanId"] for s in roots], seen)
    unreached = [s for s in spans if s["spanId"] not in seen]
    while unreached:
        promote = min(unreached, key=order)
        children[promote["parentId"]].remove(promote)
        roots.append(promote)
        reach([promote["spanId"]], seen)
        unreached = [s for s in unreached if s["spanId"] not in seen]
    roots.sort(key=order)
    for kids in children.values():
        kids.sort(key=order)
    return roots, children


def critical_path(spans: List[dict]) -> List[Tuple[dict, int]]:
    """[(span, attributed_us)] -- the spans on the trace's critical
    path with the wall time each one owns.

    Backward walk from the last-ending root: within a span's window the
    child running latest owns that stretch (recursively), and stretches
    no child covers belong to the span itself. Every microsecond of the
    root's window is attributed exactly once, so the entries sum to the
    trace wall (modulo child intervals leaking outside the parent's,
    which are clipped)."""
    roots, children = build_tree(spans)
    if not roots:
        return []
    # multiple roots (an engine-only trace of bare stage spans, or a
    # partial stitch) walk under one virtual root spanning the whole
    # trace, so attribution still covers every interval
    virtual = {"spanId": "", "name": "",
               "startUs": min(s["startUs"] for s in spans),
               "endUs": max(s["endUs"] for s in spans)}
    children[""] = roots
    attributed: Dict[str, int] = {}
    touched: List[dict] = []

    def touch(s: dict, us: int) -> None:
        if us <= 0 or s is virtual:
            return
        if s["spanId"] not in attributed:
            attributed[s["spanId"]] = 0
            touched.append(s)
        attributed[s["spanId"]] += us

    def walk(span: dict, lo: int, hi: int) -> None:
        cur = hi
        # span.kind=state spans annotate their parent's window (a
        # second decomposition of the same time); letting them compete
        # would shadow the real work tree with e.g. query.running
        kids = sorted((k for k in children.get(span["spanId"], ())
                       if k["startUs"] < cur and k["endUs"] > lo
                       and k.get("attributes", {}).get("span.kind")
                       != "state"),
                      key=lambda k: k["endUs"])
        for kid in reversed(kids):          # latest-ending child first
            k_end = min(kid["endUs"], cur)
            if k_end <= lo:
                break
            touch(span, cur - k_end)        # gap after kid: span's own
            k_lo = max(kid["startUs"], lo)
            walk(kid, k_lo, k_end)
            cur = k_lo
            if cur <= lo:
                break
        touch(span, cur - lo)               # leading stretch, if any

    walk(virtual, virtual["startUs"], virtual["endUs"])
    touched.sort(key=lambda s: s["startUs"])
    return [(s, attributed[s["spanId"]]) for s in touched]


def critical_path_summary(spans: List[dict],
                          path: Optional[List[tuple]] = None) -> str:
    """Two lines: the critical-path chain (start-ordered) and the one
    stage on it owning the most wall time, with its share. `path` takes
    a precomputed `critical_path(spans)` so callers that already walked
    the tree (render_waterfall) don't attribute twice."""
    path = critical_path(spans) if path is None else path
    if not path:
        return "critical path: (empty trace)"
    wall = max(s["endUs"] for s in spans) - min(s["startUs"] for s in spans)
    names = [s["name"] for s, _ in path]
    if len(names) > 8:
        names = names[:8] + [f"... (+{len(names) - 8} more)"]
    hot, hot_us = max(path, key=lambda e: e[1])
    share = (100.0 * hot_us / wall) if wall > 0 else 0.0
    return (f"critical path: {' > '.join(names)}\n"
            f"critical-path stage: {hot['name']} "
            f"({hot_us / 1000.0:.1f}ms attributed, {share:.0f}% of wall)")


def render_waterfall(doc: dict, width: int = 72) -> str:
    """The trace document -> an ASCII waterfall: one row per span in
    tree order, a bar positioned on the trace's time axis, duration,
    and a ``*`` on every critical-path span; the critical-path summary
    closes the rendering."""
    spans = doc.get("spans") or []
    if not spans:
        return f"trace {doc.get('traceId', '?')}: no spans"
    t0 = min(s["startUs"] for s in spans)
    t1 = max(s["endUs"] for s in spans)
    wall = max(1, t1 - t0)
    path = critical_path(spans)
    on_path = {s["spanId"] for s, _ in path}
    roots, children = build_tree(spans)
    depth_of: Dict[str, int] = {}
    stack = [(r, 0) for r in roots]
    while stack:
        s, d = stack.pop()
        depth_of[s["spanId"]] = d
        stack.extend((k, d + 1) for k in children.get(s["spanId"], ()))
    name_w = min(44, max(len(s["name"]) + 2 * depth_of[s["spanId"]]
                         for s in spans) + 2)
    bar_w = max(20, width - name_w)
    lines = [f"trace {doc.get('traceId', '?')} -- {len(spans)} span(s), "
             f"{wall / 1000.0:.1f}ms wall"
             + (f", query {doc['queryId']}" if doc.get("queryId") else "")]

    def emit(s: dict, depth: int) -> None:
        lo = int(bar_w * (s["startUs"] - t0) / wall)
        hi = max(lo + 1, int(round(bar_w * (s["endUs"] - t0) / wall)))
        bar = " " * lo + "#" * (hi - lo)
        label = ("  " * depth + s["name"])[:name_w].ljust(name_w)
        dur = (s["endUs"] - s["startUs"]) / 1000.0
        mark = " *" if s["spanId"] in on_path else ""
        lines.append(f"{label}|{bar.ljust(bar_w)}| {dur:9.1f}ms{mark}")
        for kid in children.get(s["spanId"], ()):
            emit(kid, depth + 1)

    for root in roots:
        emit(root, 0)
    lines.append(critical_path_summary(spans, path=path))
    return "\n".join(lines)


# -- the profiler's trace: device time by scope, idle time by span ------

SPAN = "presto:"          # exec/stats.py's annotation prefix
OPS_LINE = "XLA Ops"      # the device plane's line of executed ops
MODULES_LINE = "XLA Modules"  # ... and of the programs that held them
TOP = 24                  # rows of a printed ranking
# the ``ops/`` and ``parallel/`` functions that open a jax.named_scope
# (tests/test_traceview_xplane.py holds this list to the source): the
# operators' kernels, and a meshed program's exchanges
OPS_SCOPES = frozenset([
    "lex_sort", "hash_join", "_sort_build", "_pack_ranks",
    "_match_ranges", "_slot_rows", "_compact_probe", "semi_join_mask",
    "_group_ids_hash", "_group_ids_sort", "_group_by_sorted", "top_n",
    "limb_partial_sums",
    "exchange_by_hash", "exchange_by_range", "_route_rows",
    "broadcast_build", "gather_to_root"])
_NODE = re.compile(r"[A-Za-z]+Node\.\d+$")


def scope_of(op_name: str) -> str:
    """'jit(run)/OutputNode.0/JoinNode.3/hash_join/while/body/sort' ->
    'JoinNode.3/hash_join': the innermost plan node the op was lowered
    under (its ancestors are the plan's to tell) and the ``ops/``
    functions below it, down to the last component the program named,
    without the primitives and transforms JAX appends; '' for an op
    that carries no scope of the program's."""
    parts, last = [], 0
    for part in op_name.split("/"):
        if _NODE.match(part):
            parts, last = [part], 1
        elif parts:
            parts.append(part)
            if part in OPS_SCOPES:
                last = len(parts)
    return "/".join(parts[:last])


def _op(event_name: str) -> str:
    """'%fusion.60 = (u32[6000000]...) fusion(...)' -> 'fusion.60'."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def scope_seconds(ops: List[tuple]) -> Tuple[Dict[str, float],
                                             Dict[Tuple[str, str], float]]:
    """``ops``: (name, scope, start_ns, end_ns) of one device line.
    ({scope: seconds}, {(op, scope): seconds}), each op's time less that
    of the ops nested in it (a ``while`` holds its body's ops on the
    same line), so the scopes sum to the line's busy time."""
    own: Dict[Tuple[str, str], int] = {}
    stack: List[tuple] = []
    for name, scope, s, e in sorted(ops, key=lambda o: (o[2], -o[3])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        key = (_op(name), scope)
        if stack:
            own[stack[-1][1]] -= e - s
        own[key] = own.get(key, 0) + (e - s)
        stack.append((e, key))
    by_scope: Dict[str, float] = {}
    for (_name, scope), ns in own.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + ns / 1e9
    return by_scope, {k: ns / 1e9 for k, ns in own.items()}


def statement_intervals(threads: List[List[tuple]]) -> List[Tuple[int, int]]:
    """(start_ns, end_ns) of every statement, in time order, from the
    program's own spans. ``threads``: per host thread its ``presto:``
    events (name, start_ns, end_ns). On its engine thread a statement
    is the run of spans up to and including ``presto:render``, the last
    one the server opens; spans after a thread's last render (a library
    call renders nothing) are one more statement."""
    out = []
    for events in threads:
        run: List[tuple] = []
        for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
            run.append((s, e))
            if name == SPAN + "render":
                out.append((run[0][0], max(b for _a, b in run)))
                run = []
        if run:
            out.append((run[0][0], max(b for _a, b in run)))
    return sorted(out)


def gap_seconds_by_span(ops: List[tuple], spans: List[tuple],
                        statements: List[Tuple[int, int]]
                        ) -> List[Dict[str, float]]:
    """Device idle seconds inside each of ``statements`` (start_ns,
    end_ns), split by overlap across the program's spans. ``ops``:
    (start_ns, end_ns) of one device line; ``spans``: (name, start_ns,
    end_ns) host events, of which the ``presto:`` ones count. Every
    idle instant goes to the innermost (shortest) span that covers it,
    or to '(no span)'. Returns one {span: seconds} per statement."""
    busy: List[List[int]] = []
    for s, e in sorted(ops):
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    starts = [b[0] for b in busy]
    before = [0]                    # busy ns before each interval
    for s, e in busy:
        before.append(before[-1] + e - s)

    def busy_until(t: int) -> int:
        i = bisect.bisect_right(starts, t)
        return before[i] - (max(busy[i - 1][1] - t, 0) if i else 0)

    spans = [(s, e, n[len(SPAN):]) for n, s, e in spans
             if n.startswith(SPAN) and e > s]
    out = []
    for s0, s1 in statements:
        mine = [(max(s, s0), min(e, s1), e - s, n) for s, e, n in spans
                if s < s1 and e > s0]
        cuts = sorted({s0, s1} | {t for a, b, _d, _n in mine
                                  for t in (a, b)})
        acc: Dict[str, float] = {}
        for a, b in zip(cuts, cuts[1:]):
            idle = (b - a) - (busy_until(b) - busy_until(a))
            if idle <= 0:
                continue
            cover = [(d, n) for x, y, d, n in mine if x <= a and b <= y]
            name = min(cover)[1] if cover else "(no span)"
            acc[name] = acc.get(name, 0.0) + idle / 1e9
        out.append(acc)
    return out


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) pairs of one protobuf message off the
    wire: an int for a varint, the bytes of a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind in (1, 2, 5):
            size, i = _varint(buf, i) if kind == 2 \
                else (8 if kind == 1 else 4, i)
            v = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {kind} in a trace file")
        yield key >> 3, v


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{program: {instruction name: its HLO metadata op_name}} from a
    serialized XSpace. On the TPU an "XLA Ops" event carries no scope
    (its stats are device_offset_ps, device_duration_ps and a time
    scale; its metadata hlo_category, program_id, flops, bytes): the
    scope is the ``op_name`` of the instruction of the event's name in
    the program's optimized HLO, which the profiler keeps as the ``Hlo
    Proto`` stat of the ``/host:metadata`` plane's entry for the
    program, named like its "XLA Modules" event (``jit_run(<id>)``).
    ``ProfileData`` shows neither (the plane has no line), hence the
    wire reader; the field numbers are xplane.proto's and hlo.proto's:
    XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map value 2);
    XEventMetadata.name 2, .stats 5; XStat.bytes_value 6;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    def first(msg, number, default=b""):
        return next((v for k, v in _fields(msg) if k == number), default)

    out: Dict[str, Dict[str, str]] = {}
    for plane in (v for k, v in _fields(memoryview(xspace)) if k == 1):
        if bytes(first(plane, 2)) != b"/host:metadata":
            continue
        for entry in (v for k, v in _fields(plane) if k == 4):
            meta = first(entry, 2)
            names = out.setdefault(bytes(first(meta, 2)).decode(), {})
            for stat in (v for k, v in _fields(meta) if k == 5):
                module = first(first(stat, 6), 1)
                for comp in (v for k, v in _fields(module) if k == 3):
                    for ins in (v for k, v in _fields(comp) if k == 2):
                        names[bytes(first(ins, 1)).decode()] = bytes(
                            first(first(ins, 7), 2)).decode()
    return out


def device_time_by_scope(xplane_path: str) -> dict:
    """Read a profiler trace (``.xplane.pb``, gzipped or not):
    {'busy_s', 'scopes': {scope: s}, 'ops': {(op, scope): s},
     'statements': [{'start_s', 'wall_s', 'gaps': {span: s}}]}, device
    planes summed. Events and their times come from ``ProfileData``, as
    the benchmark's reducer reads them; each op's scope from
    :func:`hlo_op_names`, through the "XLA Modules" event that holds the
    op, under the ``region`` of the ``presto:dispatch`` and
    ``presto:device_wait`` spans that program ran in (a compiled
    program serves any region with its fingerprint: only the spans
    around its call know which it ran for)."""
    import gzip
    from jax.profiler import ProfileData
    opener = gzip.open if xplane_path.endswith(".gz") else open
    with opener(xplane_path, "rb") as f:
        raw = f.read()
    names = hlo_op_names(raw)
    device, threads, regions = [], [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:"):
            by_line = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in by_line:
                device.append(by_line)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                mine = [e for e in ln.events if e.duration_ns > 0
                        and e.name.startswith(SPAN)]
                threads.append([(e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in mine])
                regions += [(int(e.start_ns),
                             int(e.start_ns + e.duration_ns), region)
                            for e in mine
                            for region in [dict(e.stats).get("region")]
                            if region]
    spans = [ev for events in threads for ev in events]
    statements = statement_intervals(threads)
    scopes: Dict[str, float] = {}
    per_op: Dict[Tuple[str, str], float] = {}
    gaps: List[Dict[str, float]] = [{} for _ in statements]
    for by_line in device or [{}]:  # no device plane: all of it is idle
        modules = sorted(
            (int(m.start_ns), int(m.start_ns + m.duration_ns), m.name)
            for m in (by_line[MODULES_LINE].events
                      if MODULES_LINE in by_line else ()))
        # a program's run belongs to the region whose dispatch and wait
        # overlap it most (the two clocks differ by under a millisecond)
        ran_for = [max(((min(e, b) - max(s, a), region)
                        for a, b, region in regions), default=(0, ""))
                   for s, e, _name in modules]
        starts = [m[0] for m in modules]
        ops = []
        for e in by_line[OPS_LINE].events if by_line else ():
            s = int(e.start_ns)
            at = bisect.bisect_right(starts, s) - 1
            program = region = ""
            if at >= 0 and s < modules[at][1]:
                program = modules[at][2]
                region = ran_for[at][1] if ran_for[at][0] > 0 else ""
            path = scope_of(names.get(program, {}).get(_op(e.name), ""))
            ops.append((e.name, "/".join(p for p in (region, path) if p),
                        s, int(e.start_ns + e.duration_ns)))
        by_scope, by_op = scope_seconds(ops)
        for k, v in by_scope.items():
            scopes[k] = scopes.get(k, 0.0) + v
        for k, v in by_op.items():
            per_op[k] = per_op.get(k, 0.0) + v
        for acc, found in zip(gaps, gap_seconds_by_span(
                [(s, e) for _n, _sc, s, e in ops], spans, statements)):
            for k, v in found.items():
                acc[k] = acc.get(k, 0.0) + v
    t0 = statements[0][0] if statements else 0
    return {"busy_s": sum(scopes.values()), "scopes": scopes,
            "ops": per_op,
            "statements": [{"start_s": (s - t0) / 1e9,
                            "wall_s": (e - s) / 1e9, "gaps": g}
                           for (s, e), g in zip(statements, gaps)]}


def render_scopes(found: dict) -> str:
    """``device_time_by_scope``'s result as the three tables the CLI
    prints: device seconds by scope, the top ops with their scope, and
    per statement the idle seconds by span."""
    def ranked(d):
        return sorted(d.items(), key=lambda kv: -kv[1])
    busy = found["busy_s"] or 1.0
    out = [f"device busy {found['busy_s']:.6f} s", "", "by scope:"]
    out += [f"  {s:10.6f} s {100 * s / busy:5.1f}%  {scope or '(none)'}"
            for scope, s in ranked(found["scopes"])[:TOP]]
    out += ["", "top ops:"]
    out += [f"  {s:10.6f} s  {op:28s} {scope or '(none)'}"
            for (op, scope), s in ranked(found["ops"])[:TOP]]
    for i, st in enumerate(found["statements"], 1):
        out += ["", f"device idle inside statement {i} "
                    f"(at {st['start_s']:.3f} s, {st['wall_s']:.3f} s): "
                    f"{sum(st['gaps'].values()):.6f} s"]
        out += [f"  {s:10.6f} s  {name}" for name, s in ranked(st["gaps"])]
    return "\n".join(out)
