SELECT l.orderkey, sum(l.extendedprice * (1 - l.discount)) AS revenue,
       o.orderdate, o.shippriority
FROM {catalog}customer c
JOIN {catalog}orders o ON c.custkey = o.custkey
JOIN {catalog}lineitem l ON l.orderkey = o.orderkey
WHERE c.mktsegment = '{SEGMENT}'
  AND o.orderdate < date '{DATE}'
  AND l.shipdate > date '{DATE}'
GROUP BY l.orderkey, o.orderdate, o.shippriority
ORDER BY revenue DESC, o.orderdate
LIMIT 10
