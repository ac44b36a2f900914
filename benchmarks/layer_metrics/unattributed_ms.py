"""What no span of the program covers: HTTP, the long-poll, the gaps.

Mean per statement of the client's wall less the walls of the spans that
tile the server-side life of a statement (queue, batch, plan, dynfilter,
staging, execute, fetch, render, write). `compile` is carved out of
`execute` and their children (`plan.sql`, `dispatch`, ...) lie inside
them, so neither is subtracted. Where this is large a span is missing.
"""

from benchmarks.harness.layers import stat

TOP_LEVEL = ("queue", "batch", "plan", "dynfilter", "staging", "execute",
             "fetch", "render", "write")


def read(run):
    left = []
    for s in run["statements"]:
        if stat(s["stats"], "queryStats.stages.queue.wall_us") is None:
            continue  # a program without the seam: front_ms is its gauge
        covered = sum(stat(s["stats"], f"queryStats.stages.{k}.wall_us") or 0
                      for k in TOP_LEVEL)
        left.append(s["wall_s"] * 1e3 - covered / 1e3)
    return sum(left) / len(left) if left else None
