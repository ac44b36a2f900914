#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, on the chip.

  python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no CPU fallback. In order: the compile cache, an in-process
`StatementServer` at the configuration's scale, the configuration's load
over HTTP, one warm-up of every (template, parameter set) of the cell's
traffic, then the window: closed-loop clients calling
`presto_tpu.client.execute` (POST /v1/statement and the nextUri drain).
Every answer is kept; after the window the plain references answer the
same statements and each number compared is printed beside its limit.
The last line of standard output is the contract's result.

  JAX_PLATFORMS=cpu python benchmarks/run.py --workload <cell> --rehearse

drives the same path on the CPU at sf 0.01 and prints a line that names
itself a rehearsal and holds no metric. `--proof 11,12t,13c` is for the
builder's own proofs: several windows in one process, `t` traced, `c`
with the lower-precision control in the program's place.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import judge, layers, traffic  # noqa: E402
from benchmarks.harness import trace_reduce  # noqa: E402
from benchmarks.harness.end_to_end import END_TO_END  # noqa: E402

REHEARSAL_SF = 0.01
CACHE_DIR = os.path.join(ROOT, ".cache")


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _log(*words):
    print("[bench]", *words, file=sys.stderr, flush=True)


class CacheMisses:
    """Compile-cache misses as JAX's own monitoring reports them."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.count += 1


class Cell:
    """One cell's server, load and windows. `execute` is the client
    call; the tests hand in one that breaks the timed path."""

    def __init__(self, cell: dict, sf=None, execute=None, config=None):
        from presto_tpu.client import execute as client_execute
        self.config = config or traffic.read_json("configs", cell["config"])
        self.traffic = traffic.read_json("traffic", cell["traffic"])
        self.sf = self.config["sf"] if sf is None else sf
        self.execute = execute or client_execute
        self.misses = CacheMisses()
        self.texts = {
            (name, i): traffic.statement_text(
                name, self.config["catalog"],
                traffic.template_of(self.traffic, name)["sets"][i])
            for name, i in traffic.every_pair(self.traffic)}
        self.loaded = {}     # table -> rows the CTAS and the read-back gave
        self.warm = []       # warm-up statements, judged with the window's
        self.server = None

    # -- set-up ---------------------------------------------------------

    def start(self):
        from presto_tpu.server.statement import StatementServer
        self.server = StatementServer(sf=self.sf)
        self.server.__enter__()
        self.url = self.server.url

    def stop(self):
        if self.server is not None:
            self.server.__exit__(None, None, None)
            self.server = None

    def load(self):
        tables = sorted({t for tpl in self.traffic["templates"]
                         for t in tpl["tables"]})
        for table in tables if self.config["load"] else []:
            t0 = time.time()
            self.execute(self.url, f"DROP TABLE IF EXISTS "
                         f"{self.config['catalog']}{table}")
            made = self.execute(self.url, self.config["load"].format(
                table=table,
                columns=", ".join(self.config["columns"][table])),
                timeout=600)
            back = self.execute(self.url, f"SELECT count(*) FROM "
                                f"{self.config['catalog']}{table}")
            self.loaded[table] = (int(made.data[0][0]), int(back.data[0][0]))
            _log(f"loaded {table}: {self.loaded[table]} rows in "
                 f"{time.time() - t0:.1f} s")

    def warm_up(self, tries: int = 4):
        """Every (template, set) until a run of it compiles nothing anew:
        a run that only read programs from the compile cache leaves them
        in the process, so one such run is enough."""
        for pair, text in self.texts.items():
            for attempt in range(tries):
                before, t0 = self.misses.count, time.time()
                rec = self._send(pair, text)
                self.warm.append(rec)
                compiled = rec["stats"].get("compileTimeMicros", 0)
                _log(f"warm {pair} try {attempt}: {time.time() - t0:.2f} s, "
                     f"compile {compiled / 1e6:.2f} s, "
                     f"misses {self.misses.count - before}")
                if rec["failed"] or self.misses.count == before:
                    break

    # -- the window -----------------------------------------------------

    def _send(self, pair, text, traced=False):
        from jax.profiler import TraceAnnotation
        rec = {"template": pair[0], "set": pair[1], "traced": traced,
               "failed": None, "data": None, "stats": {}}
        rec["t0"] = time.time()
        try:
            with TraceAnnotation(trace_reduce.STATEMENT + pair[0]):
                done = self.execute(self.url, text, timeout=600)
            rec["data"], rec["stats"] = done.data, done.stats
            if done.stats.get("state") != "FINISHED":
                rec["failed"] = f"ended {done.stats.get('state')}"
        except Exception as e:  # noqa: BLE001 - a failed statement counts
            rec["failed"] = f"{type(e).__name__}: {e}"
        rec["t1"] = time.time()
        rec["wall_s"] = rec["t1"] - rec["t0"]
        return rec

    def window(self, seed: int, seconds: float, trace: bool) -> dict:
        """Statements start while the window is open; the one in flight
        finishes and counts."""
        tracer = _Tracer(self, trace, seed)
        records = [[] for _ in range(self.traffic["clients"])]
        misses_before = self.misses.count
        opened = time.time()

        def client(k):
            for n, pair in enumerate(traffic.stream(self.traffic, seed, k)):
                if time.time() - opened >= seconds:
                    break
                if k == 0:
                    tracer.between_statements(n)
                records[k].append(self._send(pair, self.texts[pair],
                                             traced=tracer.on))
            if k == 0:
                tracer.finish()

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(len(records))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        statements = sorted((r for rs in records for r in rs),
                            key=lambda r: r["t1"])
        return {"opened": opened, "statements": statements,
                "clients": len(records),
                "setup_s": opened - T_START,
                "cache_misses_in_window": self.misses.count - misses_before,
                "trace_dir": tracer.dir if tracer.started else None}


class _Tracer:
    """Traces a few seconds of the steady window: whole cycles of the
    traffic, after one cycle of lead-in, from client 0's thread."""

    def __init__(self, cell: Cell, wanted: bool, seed: int):
        self.wanted, self.on, self.started = wanted, False, False
        self.cycle = len(traffic.cycle(cell.traffic))
        self.seconds = cell.traffic["trace_seconds"]
        self.dir = os.path.join(CACHE_DIR, "bench_trace", str(seed))
        self._window = None

    def between_statements(self, n: int):
        import jax
        if not self.wanted or n % self.cycle:
            return
        if not self.started and n >= self.cycle:
            shutil.rmtree(self.dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._window.__enter__()
            self.started, self.on, self.t0 = True, True, time.time()
        elif self.on and time.time() - self.t0 >= self.seconds:
            self.finish()

    def finish(self):
        import jax
        if self.on:
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


def device_block(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def result_line(bench: dict, cell: dict, run: dict, verdict: dict,
                device: dict, trace: bool, rehearse: bool = False,
                dump_trace=None) -> dict:
    """The contract's last line for one window."""
    line = {"correct": verdict["correct"], "attempted": verdict["attempted"],
            "failed": verdict["failed"], "metrics": {}, "device": device}
    if trace:
        run["trace"] = None
        if run["trace_dir"] and rehearse:
            _log("a rehearsal's trace holds no device plane: not reduced")
        elif run["trace_dir"]:
            events = trace_reduce.read_xplane(
                trace_reduce.newest_xplane(run["trace_dir"]))
            if dump_trace:
                with open(dump_trace, "w") as f:
                    json.dump(events, f)
            run["trace"] = trace_reduce.reduce_events(events)
            shutil.rmtree(run["trace_dir"], ignore_errors=True)
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            line["breakdown"] = {k: run["trace"][k]
                                 for k in ("device_ops", "idle_gaps")}
        for m in bench["per_layer"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                value = layers.read_metric(m["name"], run)
                if value is not None:
                    line["metrics"][m["name"]] = {"value": value,
                                                  "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                line["metrics"][m["name"]] = {
                    "value": END_TO_END[m["name"]](run), "unit": m["unit"]}
    line["statements"] = len(run["statements"])
    line["numbers"] = verdict["numbers"]
    return line


def run_cell(cell: dict, windows, seconds: float, rehearse: bool,
             execute=None, config=None, out=sys.stdout,
             dump_trace=None) -> list:
    """Set-up once, then one window per (seed, trace, control) of
    `windows`; returns the result lines, printing each as it is made."""
    import presto_tpu  # x64 on, as in production  # noqa: F401
    from presto_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    import jax
    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        _log(f"needs {cell['chips']} TPU chip(s); JAX found "
             f"{len(devices)} x {devices[0].platform!r}. No CPU fallback.")
        raise SystemExit(3)
    devices = devices[:cell["chips"]]
    bench = manifest()
    c = Cell(cell, sf=REHEARSAL_SF if rehearse else None,
             execute=execute, config=config)
    lines = []
    c.start()
    try:
        c.load()
        c.warm_up()
        _log(f"set-up took {time.time() - T_START:.1f} s")
        runs = []
        for seed, trace, control in windows:
            run = c.window(seed, seconds, trace)
            run["device_kind"] = devices[0].device_kind
            runs.append((run, device_block(devices), trace, control))
    finally:
        c.stop()
    # the window is closed, the peak is read, the server is gone: now
    # the references answer, outside every timed part
    reference = judge.Reference(c.sf, CACHE_DIR)
    for run, device, trace, control in runs:
        verdict = judge.judge(reference, c, run, control)
        line = result_line(bench, cell, run, verdict, device, trace,
                           rehearse, dump_trace)
        _log("walls", json.dumps([
            [round(s["t1"] - run["opened"], 4), round(s["wall_s"], 4),
             s["template"], s["stats"].get("compileTimeMicros", 0)]
            + [layers.stat(s["stats"], f"queryStats.{path}.wall_us")
               for path in ("stages.staging", "datapath.connector_read",
                            "datapath.narrow_cast", "datapath.device_put",
                            "stages.execute")]
            for s in run["statements"]]))
        for name, n in verdict["numbers"].items():
            _log(f"compared {name}: {n['value']} (limit {n['limit']})")
        print(json.dumps(_rehearsal(line) if rehearse else line),
              file=out, flush=True)
        lines.append(line)
    return lines


def _rehearsal(line: dict) -> dict:
    """What a rehearsal prints: no metric, no device, and none of the
    contract's keys, so that it is never mistaken for a result."""
    return {"rehearsal": True, "platform": line["device"]["platform"],
            "answers_agree": line["correct"],
            "statements": line["statements"],
            "metric_names": sorted(line["metrics"]),
            "numbers": line["numbers"]}


def _proof(text: str):
    """'11,12t,13c' -> [(11, False, False), (12, True, False), ...]"""
    return [(int(w.rstrip("tc")), w.endswith("t"), w.endswith("c"))
            for w in text.split(",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--proof", type=_proof, default=None)
    ap.add_argument("--dump-trace", default=None, metavar="OUT.json",
                    help="keep the traced window's events as "
                         "trace_reduce.read_xplane gives them")
    args = ap.parse_args(argv)
    bench = manifest()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        _log(f"no cell {args.workload!r} in BENCHMARK.json: {sorted(cells)}")
        return 2
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds
    windows = args.proof or [(args.seed, bool(args.trace), False)]
    lines = run_cell(cells[args.workload], windows, seconds, args.rehearse,
                     dump_trace=args.dump_trace)
    return 0 if lines else 1


if __name__ == "__main__":
    sys.exit(main())
