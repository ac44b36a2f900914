SELECT sum(extendedprice * discount) AS revenue
FROM {catalog}lineitem
WHERE shipdate >= date '{DATE_LO}'
  AND shipdate < date '{DATE_HI}'
  AND discount BETWEEN {DISCOUNT_LO} AND {DISCOUNT_HI}
  AND quantity < {QUANTITY}
