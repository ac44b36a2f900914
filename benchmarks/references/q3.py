"""TPC-H Q3 (shipping priority), plain numpy over the population.

Parameters: SEGMENT, DATE. The answer is the ten orders of largest
revenue: (orderkey, revenue scaled by 1e4, orderdate, shippriority),
by revenue descending, then orderdate.
"""

import numpy as np

from benchmarks.harness.population import day, day_text

LIMIT = 0  # exact: rows that differ from the reference's


def _top10(pop, p, dtype):
    in_segment = pop.column("customer", "custkey")[
        pop.column("customer", "mktsegment") == p["SEGMENT"]]
    odate = pop.column("orders", "orderdate")
    om = (odate < day(p["DATE"])) \
        & np.isin(pop.column("orders", "custkey"), in_segment)
    okeys = pop.column("orders", "orderkey")[om]
    lkey = pop.column("lineitem", "orderkey")
    lm = (pop.column("lineitem", "shipdate") > day(p["DATE"])) \
        & np.isin(lkey, okeys)
    rev = pop.column("lineitem", "extendedprice")[lm].astype(dtype) \
        * (dtype(100) - pop.column("lineitem", "discount")[lm].astype(dtype))
    # lineitem.orderkey ascends, so each order's lines are one run
    uniq, first = np.unique(lkey[lm], return_index=True)
    total = np.add.reduceat(rev, first).astype(np.int64)
    at = np.searchsorted(okeys, uniq)  # orders.orderkey ascends
    date, prio = odate[om][at], pop.column("orders", "shippriority")[om][at]
    order = np.lexsort((date, -total))[:10]
    return [(int(uniq[i]), int(total[i]), day_text(date[i]), int(prio[i]))
            for i in order]


def answer(pop, p):
    return _top10(pop, p, np.int64)


def lower_precision(pop, p):
    """The control: each order's revenue carried in float32."""
    return _top10(pop, p, np.float32)


def from_wire(data):
    return [(int(r[0]), int(str(r[1]).replace(".", "")), str(r[2]),
             int(r[3])) for r in data]


def gap(got, want):
    """Rows of the answer that are not the reference's rows."""
    return sum(a != b for a, b in zip(got, want)) + abs(len(got) - len(want))
