"""Statements of the window that compiled, plus compile-cache misses.

Set-up warms every (template, parameter set), so this is 0; anything
else means a program was built inside the measured window.
"""


def read(run):
    compiled = sum(1 for s in run["statements"]
                   if s["stats"].get("compileTimeMicros", 0) > 0)
    return compiled + run["cache_misses_in_window"]
