"""Per-layer metrics, each from a file of its own found by its name.

`benchmarks/layer_metrics/<metric>.json` names a path into the
protocol's `stats` document and a scale: the metric is the mean over the
window's statements. `<metric>.py` has `read(run)` for anything else.
A reader that finds nothing to read returns None and the metric is left
out of the line. `run` holds `statements` (template, set, wall_s, stats,
traced), `trace` (the reduced trace, or None), `device_kind` and
`cache_misses_in_window`.
"""

from __future__ import annotations

import importlib
import json
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "layer_metrics")


def stat(stats: dict, path: str):
    """stats['a']['b'] for 'a.b'; None where the program reports none."""
    at = stats
    for key in path.split("."):
        if not isinstance(at, dict) or key not in at:
            return None
        at = at[key]
    return at


def read_metric(name: str, run: dict):
    spec = os.path.join(_DIR, name + ".json")
    if os.path.exists(spec):
        with open(spec) as f:
            spec = json.load(f)
        found = [stat(s["stats"], spec["stats_path"])
                 for s in run["statements"]]
        found = [v for v in found if v is not None]
        return sum(found) / len(found) * spec["scale"] if found else None
    module = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    return module.read(run)
