"""The end-to-end metrics, each over all the work and time of the window."""

from __future__ import annotations

import math


def _done(run):
    return [s for s in run["statements"] if not s["failed"]]


def stmt_ms(run) -> float:
    """Window opening to last completion, over the statements completed:
    what one stream pays per statement of the mix."""
    done = _done(run)
    elapsed = max(s["t1"] for s in done) - run["opened"]
    return 1e3 * elapsed * run["clients"] / len(done)


def stmt_p95_ms(run) -> float:
    """95th percentile (nearest rank) of all client-side statement walls;
    with under 20 statements it is the maximum."""
    walls = sorted(s["wall_s"] for s in _done(run))
    return 1e3 * walls[math.ceil(0.95 * len(walls)) - 1]


def setup_s(run) -> float:
    """Process start to the window's opening."""
    return run["setup_s"]


END_TO_END = {"stmt_ms": stmt_ms, "stmt_p95_ms": stmt_p95_ms,
              "setup_s": setup_s}
