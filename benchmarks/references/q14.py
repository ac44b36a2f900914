"""TPC-H Q14 (promotion effect), plain numpy over the population.

Parameters: DATE_LO, DATE_HI (one month). The answer is a DOUBLE: 100 *
promo revenue / revenue, a ratio of two exact integer sums, so the number
compared is its relative distance from the reference's.
"""

import numpy as np

from benchmarks.harness.population import day

# the program reads 1.02e-14 on the chip (its float64 division is
# emulated there), the float32 control 1.41e-07 (PERF.md, section 4)
LIMIT = 1e-10


def _revenue(pop, p, dtype):
    ship = pop.column("lineitem", "shipdate")
    m = (ship >= day(p["DATE_LO"])) & (ship < day(p["DATE_HI"]))
    price = pop.column("lineitem", "extendedprice")[m].astype(dtype)
    disc = pop.column("lineitem", "discount")[m].astype(dtype)
    promo_type = np.char.startswith(pop.column("part", "type"), "PROMO")
    # part.partkey is 1..N ascending
    promo = promo_type[pop.column("lineitem", "partkey")[m] - 1]
    return price * (dtype(100) - disc), promo


def answer(pop, p):
    rev, promo = _revenue(pop, p, np.int64)
    return [(100.0 * float(rev[promo].sum()) / float(rev.sum()),)]


def lower_precision(pop, p):
    """The control: both sums carried in float32."""
    rev, promo = _revenue(pop, p, np.float32)
    return [(float(np.float32(100) * rev[promo].sum(dtype=np.float32)
                   / rev.sum(dtype=np.float32)),)]


def from_wire(data):
    return [(float(r[0]),) for r in data]


def gap(got, want):
    if len(got) != 1 or got[0][0] is None:
        return float("inf")
    return abs(got[0][0] - want[0][0]) / abs(want[0][0])
