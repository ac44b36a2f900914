"""The one traffic generator: a workload file and a seed give a stream.

A workload file (`benchmarks/traffic/<traffic>.json`) lists templates,
each with an integer `weight` and its parameter `sets`. One client's
stream repeats a fixed cycle of templates (each `weight` times, in
listed order: the mix of a stream never depends on the seed) and takes
each template's sets in an order drawn from the seed, again and again.
So every seed sends the same statements, in another order.
"""

from __future__ import annotations

import json
import os
import random

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_json(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, kind, name + ".json")) as f:
        return json.load(f)


def statement_text(template: str, catalog: str, params: dict) -> str:
    with open(os.path.join(ROOT, "queries", template + ".sql")) as f:
        return f.read().format(catalog=catalog, **params)


def every_pair(workload: dict):
    """Each (template, set index) the cell can send: what set-up warms."""
    return [(t["name"], i) for t in workload["templates"]
            for i in range(len(t["sets"]))]


def cycle(workload: dict):
    """The templates of one turn of a stream, each `weight` times."""
    return [t["name"] for t in workload["templates"]
            for _ in range(int(t.get("weight", 1)))]


def stream(workload: dict, seed: int, client: int = 0):
    """Endless (template, set index) pairs of one closed-loop client."""
    rng = random.Random(seed * 1000003 + client)
    order = {}
    for t in workload["templates"]:
        order[t["name"]] = list(range(len(t["sets"])))
        rng.shuffle(order[t["name"]])
    turn = {name: 0 for name in order}
    while True:
        for name in cycle(workload):
            sets = order[name]
            yield name, sets[turn[name] % len(sets)]
            turn[name] += 1


def template_of(workload: dict, name: str) -> dict:
    for t in workload["templates"]:
        if t["name"] == name:
            return t
    raise KeyError(name)
