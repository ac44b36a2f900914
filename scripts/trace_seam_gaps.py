#!/usr/bin/env python
"""What lies between a statement's spans, from a dumped profiler trace.

  python scripts/trace_seam_gaps.py DUMP.json [--top 6]

DUMP.json is what `benchmarks/run.py --trace 1 --dump-trace DUMP.json`
keeps: `trace_reduce.read_xplane`'s host events `[name, start_ns,
dur_ns]` of every host thread. For each `bench:stmt:<template>`
annotation of the client (one closed-loop client: statements do not
overlap) the program's top-level `presto:<span>` events inside it are
put in order, and the time of the statement that none of them covers is
split by where it lies: before the first span, between two neighbours
(`staging>execute`), after the last (`fetch>end`: render and finish
where the program has them, then the HTTP answer and the client's
drain). `staging` is also split into what its hops cover (the union of
`presto:connector_read`, `decode`, `prune`, `narrow_cast`, `device_put`
and `scan_count`, of any thread) and what they leave. Prints one JSON
line: mean milliseconds a statement by gap, and for each gap the host
events that fill most of it (summed over statements, clipped to the
gap, any thread).
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness.trace_reduce import STATEMENT, _union  # noqa: E402

PREFIX = "presto:"
TOP_LEVEL = ("batch", "plan", "dynfilter", "staging", "execute", "fetch",
             "finish", "render", "write")
HOPS = ("connector_read", "decode", "prune", "narrow_cast", "device_put",
        "scan_count")


def gaps_of(host, top: int = 6) -> dict:
    host = [(n, s, s + d) for n, s, d in host]
    stmts = sorted((s, e, n[len(STATEMENT):]) for n, s, e in host
                   if n.startswith(STATEMENT))
    spans = sorted((s, e, n[len(PREFIX):]) for n, s, e in host
                   if n.startswith(PREFIX))
    others = [(s, e, n) for n, s, e in host
              if not n.startswith((STATEMENT, PREFIX, "bench:"))]
    o_start = np.array([o[0] for o in others], dtype=np.int64)
    o_end = np.array([o[1] for o in others], dtype=np.int64)
    gap_ns, inside, walls = {}, {}, {}

    def add(label, g0, g1):
        if g1 <= g0:
            return
        gap_ns[label] = gap_ns.get(label, 0) + (g1 - g0)
        held = inside.setdefault(label, {})
        for i in np.flatnonzero((o_start < g1) & (o_end > g0)):
            n = others[i][2]
            held[n] = held.get(n, 0) + int(min(o_end[i], g1)
                                           - max(o_start[i], g0))

    for s0, s1, _template in stmts:
        mine = [(s, e, n) for s, e, n in spans if s0 <= s and e <= s1]
        tops = [(s, e, n) for s, e, n in mine if n in TOP_LEVEL]
        # `plan` opens twice a statement, a `write` once a page: tops
        # are in time order and do not overlap (tests hold that)
        at, last = s0, "start"
        for s, e, n in tops:
            add(f"{last}>{n}", at, s)
            walls[n] = walls.get(n, 0) + (e - s)
            at, last = e, n
        add(f"{last}>end", at, s1)
        for s, e, n in tops:
            if n != "staging":
                continue
            hops = _union((max(a, s), min(b, e)) for a, b, m in mine
                          if m in HOPS and a < e and b > s)
            walls["staging.hops"] = walls.get("staging.hops", 0) \
                + sum(b - a for a, b in hops)
            # what no hop covers, piece by piece
            for g0, g1 in zip([s] + [b for _a, b in hops],
                              [a for a, _b in hops] + [e]):
                add("staging:no_hop", g0, g1)
    n = max(len(stmts), 1)

    def ms(ns):
        return round(ns / n / 1e6, 3)

    return {
        "statements": len(stmts),
        "stmt_ms": ms(sum(e - s for s, e, _ in stmts)),
        "span_ms": {k: ms(v) for k, v in sorted(walls.items())},
        "gap_ms": {k: ms(v) for k, v in
                   sorted(gap_ns.items(), key=lambda kv: -kv[1])},
        "held_by_ms": {
            label: {k: ms(v) for k, v in
                    sorted(held.items(), key=lambda kv: -kv[1])[:top]}
            for label, held in inside.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dump")
    ap.add_argument("--top", type=int, default=6)
    args = ap.parse_args(argv)
    with open(args.dump) as f:
        events = json.load(f)
    print(json.dumps(gaps_of(events["host"], args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
