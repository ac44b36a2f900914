#!/usr/bin/env python3
"""chip_smoke.py -- the quickest proof that the engine still starts on the chip.

One process, no CPU fallback. Default run (one chip): start the
statement server in-process, send TPC-H q1, q6, q3, q14 and a
`LIKE '%sleep%'` count as SQL TEXT over `POST /v1/statement` at SF1,
each twice (cold, warm), and compare every answer with a plain numpy
reference computed here from the same generated columns. Decimals are
scaled int64, so the comparison is exact.

  python chip_smoke.py                  # what the driver runs: one chip
  python chip_smoke.py --chips 4        # ONLY mesh q3/q1 vs one device
  python chip_smoke.py --sf 10 --statements q1,q6     # by hand
  JAX_PLATFORMS=cpu python chip_smoke.py --allow-cpu-rehearsal --sf 0.01

The lines before the last are free-form JSON, one per statement: they
are smoke, not measurements. The last line is the contract:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
A rehearsal on the CPU always says "ok": false.
"""

import argparse
import json
import sys
import time

import numpy as np

LIKE_SQL = "SELECT count(*) FROM part WHERE name LIKE '%sleep%'"
_EPOCH = np.datetime64("1970-01-01")


def _day(s: str) -> int:
    return int((np.datetime64(s) - _EPOCH).astype(int))


def _scaled(s) -> int:
    """Wire decimal ('12.3400') -> the scaled integer the engine holds."""
    return int(str(s).replace(".", ""))


class Reference:
    """Plain numpy answers over generate_columns -- independent of the
    engine's planner, kernels and staging."""

    def __init__(self, sf: float):
        from presto_tpu.connectors import tpch
        self.sf = sf
        self._tpch = tpch
        self._cache = {}

    def cols(self, table, names):
        key = (table, tuple(names))
        if key not in self._cache:
            n = self._tpch.table_row_count(table, self.sf)
            self._cache[key] = self._tpch.generate_columns(
                table, self.sf, list(names), 0, n)
        return self._cache[key]

    def _lineitem(self):
        return self.cols("lineitem", ["orderkey", "partkey", "shipdate",
                                      "returnflag", "linestatus",
                                      "quantity", "extendedprice",
                                      "discount"])

    def q1(self):
        c = self._lineitem()
        m = c["shipdate"] <= _day("1998-12-01") - 90
        rf = c["returnflag"][m].astype("U1").view(np.uint32).astype(np.int64)
        ls = c["linestatus"][m].astype("U1").view(np.uint32).astype(np.int64)
        qty, price = c["quantity"][m], c["extendedprice"][m]
        disc_price = price * (100 - c["discount"][m])
        uniq, inv = np.unique(rf * 65536 + ls, return_inverse=True)
        rows = []
        for i, k in enumerate(uniq):
            g = inv == i
            rows.append((chr(k // 65536), chr(k % 65536), int(qty[g].sum()),
                         int(price[g].sum()), int(disc_price[g].sum()),
                         int(g.sum())))
        return rows

    def q6(self):
        c = self._lineitem()
        m = ((c["shipdate"] >= _day("1994-01-01"))
             & (c["shipdate"] < _day("1995-01-01"))
             & (c["discount"] >= 5) & (c["discount"] <= 7)
             & (c["quantity"] < 2400))
        return [(int((c["extendedprice"][m] * c["discount"][m]).sum()),)]

    def q3(self):
        cu = self.cols("customer", ["custkey", "mktsegment"])
        od = self.cols("orders", ["orderkey", "custkey", "orderdate",
                                  "shippriority"])
        li = self._lineitem()
        building = cu["custkey"][cu["mktsegment"] == "BUILDING"]
        om = (od["orderdate"] < _day("1995-03-15")) \
            & np.isin(od["custkey"], building)
        okeys = od["orderkey"][om]
        lm = (li["shipdate"] > _day("1995-03-15")) \
            & np.isin(li["orderkey"], okeys)
        rev = li["extendedprice"][lm] * (100 - li["discount"][lm])
        uniq, inv = np.unique(li["orderkey"][lm], return_inverse=True)
        # per-order sums stay far below 2^53: float64 bincount is exact
        total = np.bincount(inv, weights=rev.astype(np.float64)
                            ).astype(np.int64)
        at = np.searchsorted(okeys, uniq)  # orders.orderkey ascends
        odate, prio = od["orderdate"][om][at], od["shippriority"][om][at]
        order = np.lexsort((odate, -total))[:10]
        return [(int(uniq[i]), int(total[i]),
                 str(_EPOCH + int(odate[i])), int(prio[i])) for i in order]

    def q14(self):
        li = self._lineitem()
        pa = self.cols("part", ["partkey", "name", "type"])
        m = (li["shipdate"] >= _day("1995-09-01")) \
            & (li["shipdate"] < _day("1995-10-01"))
        rev = li["extendedprice"][m] * (100 - li["discount"][m])
        promo_part = np.char.startswith(pa["type"].astype(str), "PROMO")
        # part.partkey is 1..N ascending
        promo = promo_part[li["partkey"][m] - pa["partkey"][0]]
        return [(100.0 * float(rev[promo].sum()) / float(rev.sum()),)]

    def like(self):
        pa = self.cols("part", ["partkey", "name", "type"])
        return [(int((np.char.find(pa["name"].astype(str), "sleep")
                      >= 0).sum()),)]


def _wire_rows(name, data):
    """Protocol rows -> the tuples Reference returns."""
    if name == "q1":
        return [(r[0], r[1], _scaled(r[2]), _scaled(r[3]), _scaled(r[4]),
                 int(r[5])) for r in data]
    if name == "q6":
        return [(_scaled(r[0]),) for r in data]
    if name == "q3":
        return [(int(r[0]), _scaled(r[1]), str(r[2]), int(r[3]))
                for r in data]
    if name == "q14":
        return [(float(r[0]),) for r in data]
    return [(int(r[0]),) for r in data]


def _same(name, got, want) -> bool:
    if name == "q14":  # a DOUBLE ratio of two exact sums
        return len(got) == 1 and abs(got[0][0] - want[0][0]) \
            <= 1e-9 * abs(want[0][0])
    return got == want


def _statements(names):
    from presto_tpu.queries.tpch_sql import tpch_query
    texts = {"q1": tpch_query(1).text, "q6": tpch_query(6).text,
             "q3": tpch_query(3).text, "q14": tpch_query(14).text,
             "like": LIKE_SQL}
    return [(n, texts[n]) for n in names]


class CacheCounter:
    """Compile-cache hits/misses as JAX's own monitoring reports them."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self):
        out = {"hits": self.hits, "misses": self.misses}
        self.hits = self.misses = 0
        return out


def run_one_chip(args, devices, on_tpu: bool) -> bool:
    from presto_tpu.client import execute
    from presto_tpu.ops.aggregation import last_smallg_form
    from presto_tpu.server.statement import StatementServer

    cache = CacheCounter()
    ref = Reference(args.sf)
    ok = True
    with StatementServer(sf=args.sf) as srv:
        for name, text in _statements(args.statements):
            walls, answers = [], []
            for temp in ("cold", "warm"):
                t0 = time.time()
                client = execute(srv.url, text)  # raises on FAILED
                walls.append(time.time() - t0)
                if client.stats.get("state") != "FINISHED":
                    raise RuntimeError(f"{name} ended {client.stats}")
                answers.append(_wire_rows(name, client.data))
                if temp == "cold":
                    cold_stats, cold_cache = client.stats, cache.take()
            want = getattr(ref, name)()
            got = answers[0]
            same = all(_same(name, a, want) for a in answers)
            ok &= same
            qs = cold_stats.get("queryStats", {})
            line = {"statement": name, "sf": args.sf, "rows_out": len(got),
                    "matches_numpy": same,
                    "cold_wall_s": round(walls[0], 3),
                    "warm_wall_s": round(walls[1], 3),
                    "compile_s": cold_stats.get("compileTimeMicros", 0) / 1e6,
                    "staged_mb": qs.get("stages", {}).get(
                        "staging", {}).get("bytes", 0) / 1e6,
                    "compile_cache_cold": cold_cache,
                    "compile_cache_warm": cache.take()}
            if name == "q1":
                form = last_smallg_form()
                peak = (devices[0].memory_stats() or {}).get(
                    "peak_bytes_in_use", 0)
                line["smallg_form"] = form
                line["device_peak_mb"] = peak / 1e6
                # the device did the work, in the Pallas form
                if on_tpu and not (str(form).startswith("pallas")
                                   and peak > 0):
                    raise RuntimeError(f"q1 ran as {form!r} with device "
                                       f"peak {peak} B")
            if name == "like" and want[0][0] <= 0:
                raise RuntimeError("the LIKE count must be > 0")
            if not same:
                line["got"], line["want"] = got[:10], want[:10]
            print(json.dumps(line), flush=True)
    return ok


def run_four_chips(args, devices) -> bool:
    """ONLY the path across chips and what it is compared with: q3 and
    q1 as one SPMD program over a 4-device mesh vs one device."""
    from presto_tpu.parallel import make_mesh
    from presto_tpu.sql import sql
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX has "
                         f"{len(devices)}")
    mesh = make_mesh(4)
    ok = True
    for name, text in _statements(["q3", "q1"]):
        line = {"statement": name, "sf": args.sf, "chips": 4}
        for label, m in (("mesh", mesh), ("one_device", None)):
            for temp in ("cold", "warm"):
                t0 = time.time()
                res = sql(text, sf=args.sf, mesh=m)
                line[f"{label}_{temp}_wall_s"] = round(time.time() - t0, 3)
            line[label] = res.rows()
            if m is not None:
                # scans are staged shard by shard, each on its own chip
                # (exec/runner.stage_scan_split): read before the
                # one-device run adds to device 0
                line["peak_bytes_per_device_after_mesh"] = [
                    (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices[:4]]
        same = line.pop("mesh") == (one := line.pop("one_device"))
        ok &= same
        line["rows_out"] = len(one)
        line["mesh_equals_one_device"] = same
        print(json.dumps(line, default=str), flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--statements", default="q1,q6,q3,q14,like",
                    type=lambda s: s.split(","))
    ap.add_argument("--allow-cpu-rehearsal", action="store_true",
                    help="run without a TPU; the last line says ok: false")
    args = ap.parse_args(argv)

    import presto_tpu  # x64 on, as in production  # noqa: F401
    from presto_tpu.utils.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    import jax
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.allow_cpu_rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); there is no CPU fallback",
              file=sys.stderr)
        return 1
    from presto_tpu.native.kernels import native_available
    print(json.dumps({"jax": jax.__version__,
                      "device_kind": devices[0].device_kind,
                      "count": len(devices), "cache_dir": cache_dir,
                      "native_serde": native_available()}), flush=True)

    if args.chips == 4:
        ok = run_four_chips(args, devices)
    else:
        ok = run_one_chip(args, devices, on_tpu)
    print(json.dumps({"ok": bool(ok and on_tpu),
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
