"""What decides `correct`: every answer against the plain reference.

The references (`benchmarks/references/<template>.py`) answer over the
benchmark's own copy of the population; nothing of the program is
imported. Compared, each with a limit of its own:

  <template>_gap   the worst `gap(got, want)` over every answer of that
                   template, warm-up and window alike (limit: the
                   reference file's LIMIT; 0 where answers are exact)
  rows_loaded_gap  rows the load's CTAS reported and `count(*)` read
                   back, against the population's (limit 0)
  unanswered       statements that failed or never came (limit 0)

With `control`, the reference's lower-precision answer is put in the
program's place: that has to come out as not correct.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os

from benchmarks.harness import population, traffic


class Reference:
    """Reference answers per (template, parameter set), kept on disk
    under the checkout's ignored `.cache/`: the population is fixed, so
    an answer is a pure function of the files hashed into its key."""

    def __init__(self, sf: float, cache_dir: str):
        self.sf = sf
        self.pop = population.Population(sf)
        self.dir = os.path.join(cache_dir, "bench_refs")
        self._answers = {}

    def module(self, template):
        return importlib.import_module(f"benchmarks.references.{template}")

    def answer(self, template: str, params: dict, control: bool = False):
        key = (template, json.dumps(params, sort_keys=True), control)
        if key not in self._answers:
            self._answers[key] = self._kept_or_computed(template, params,
                                                        control)
        return self._answers[key]

    def _kept_or_computed(self, template, params, control):
        module = self.module(template)
        h = hashlib.sha256()
        for path in (module.__file__, population.__file__):
            with open(path, "rb") as f:
                h.update(f.read())
        h.update(json.dumps([self.sf, params, control],
                            sort_keys=True).encode())
        kept = os.path.join(self.dir, f"{template}.{h.hexdigest()[:24]}.json")
        if os.path.exists(kept):
            with open(kept) as f:
                return [tuple(r) for r in json.load(f)]
        fn = module.lower_precision if control else module.answer
        rows = fn(self.pop, params)
        os.makedirs(self.dir, exist_ok=True)
        with open(kept + ".tmp", "w") as f:
            json.dump(rows, f)
        os.replace(kept + ".tmp", kept)
        return rows


def judge(reference: Reference, cell, run: dict, control: bool) -> dict:
    numbers = {}
    window = run["statements"]
    for s in cell.warm + window:
        if s["failed"]:
            continue
        module = reference.module(s["template"])
        params = traffic.template_of(
            cell.traffic, s["template"])["sets"][s["set"]]
        want = reference.answer(s["template"], params)
        got = (reference.answer(s["template"], params, control=True)
               if control else module.from_wire(s["data"]))
        gap = module.gap(got, want)
        name = s["template"] + "_gap"
        if name not in numbers or gap > numbers[name]["value"]:
            numbers[name] = {"value": gap, "limit": module.LIMIT}
    if cell.loaded:
        numbers["rows_loaded_gap"] = {
            "value": sum(abs(n - reference.pop.rows(t))
                         for t, ns in cell.loaded.items() for n in ns),
            "limit": 0}
    failed = sum(1 for s in window if s["failed"])
    numbers["unanswered"] = {
        "value": failed + sum(1 for s in cell.warm if s["failed"]),
        "limit": 0}
    for s in (cell.warm + window):
        if s["failed"]:
            numbers["unanswered"]["first"] = s["failed"][:300]
            break
    return {"correct": bool(window) and all(
                n["value"] <= n["limit"] for n in numbers.values()),
            "attempted": len(window), "failed": failed, "numbers": numbers}
