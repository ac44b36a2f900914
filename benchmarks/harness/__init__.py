"""The yardstick: traffic, load, comparison, trace reduction, peaks.

Code of the benchmark that no later non-`benchmark` PR edits. What
belongs to one configuration, cell, template or per-layer metric is a
file of its own elsewhere under `benchmarks/`, found by its name.
"""
