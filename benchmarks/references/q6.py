"""TPC-H Q6 (forecasting revenue change), plain numpy over the population.

Parameters (all literal text, as the template gets them): DATE_LO,
DATE_HI, DISCOUNT_LO, DISCOUNT_HI, QUANTITY. The answer is one scaled
integer: sum(extendedprice * discount) in units of 1e-4.
"""

from decimal import Decimal

import numpy as np

from benchmarks.harness.population import day

LIMIT = 0  # exact: rows that differ from the reference's


def _selected(pop, p):
    ship = pop.column("lineitem", "shipdate")
    disc = pop.column("lineitem", "discount")
    m = ((ship >= day(p["DATE_LO"])) & (ship < day(p["DATE_HI"]))
         & (disc >= int(Decimal(p["DISCOUNT_LO"]) * 100))
         & (disc <= int(Decimal(p["DISCOUNT_HI"]) * 100))
         & (pop.column("lineitem", "quantity")
            < int(Decimal(p["QUANTITY"]) * 100)))
    return pop.column("lineitem", "extendedprice")[m], disc[m]


def answer(pop, p):
    price, disc = _selected(pop, p)
    return [(int((price * disc).sum()),)]


def lower_precision(pop, p):
    """The control: the same sum carried in float32, not exact."""
    price, disc = _selected(pop, p)
    prod = price.astype(np.float32) * disc.astype(np.float32)
    return [(int(prod.sum(dtype=np.float32)),)]


def from_wire(data):
    return [(None if r[0] is None else int(str(r[0]).replace(".", "")),)
            for r in data]


def gap(got, want):
    """Rows of the answer that are not the reference's rows."""
    return sum(a != b for a, b in zip(got, want)) + abs(len(got) - len(want))
