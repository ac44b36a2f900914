SELECT 100.00 * sum(CASE WHEN p.type LIKE 'PROMO%'
                    THEN l.extendedprice * (1 - l.discount)
                    ELSE 0 END)
       / sum(l.extendedprice * (1 - l.discount)) AS promo_revenue
FROM {catalog}lineitem l JOIN {catalog}part p ON l.partkey = p.partkey
WHERE l.shipdate >= date '{DATE_LO}' AND l.shipdate < date '{DATE_HI}'
