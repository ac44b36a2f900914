"""Client protocol, parse, plan, prepare_plan and result drain.

Mean per statement of the client's wall less the walls of the three
stages QueryStats times (staging, execute, fetch): what is left is the
HTTP round trips, parsing, planning and the drain of the result pages.
"""

from benchmarks.harness.layers import stat


def read(run):
    left = []
    for s in run["statements"]:
        stages = [stat(s["stats"], f"queryStats.stages.{k}.wall_us")
                  for k in ("staging", "execute", "fetch")]
        if None in stages:
            continue
        left.append(s["wall_s"] * 1e3 - sum(stages) / 1e3)
    return sum(left) / len(left) if left else None
