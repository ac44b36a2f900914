"""Registry-drift gates: every session-property READ in the engine
resolves against the SESSION_PROPERTIES registry, and every
``PRESTO_TPU_*`` env READ is either registered in the plan cache's
KERNEL_MODE_ENVS (so it keys compiled-program reuse) or sits on the
visible unkeyed allowlist below (knobs that cannot change staged IR).

Both directions rot silently without this pin: a typo'd
``session_flag(session, "buffer_donatoin")`` falls back to its default
forever, and a behavior env read outside the kernel-mode key serves
stale compiled programs across env flips (exactly the R001 bug class,
enforced here at the registry level rather than per call site).
"""

import ast
import os

import pytest

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "presto_tpu")

from presto_tpu.exec.plan_cache import KERNEL_MODE_ENVS  # noqa: E402
from presto_tpu.utils.config import SESSION_PROPERTIES  # noqa: E402

# session keys that are protocol-envelope/identity context, not
# registered properties: `user` rides Session as an attribute; catalog/
# source/clientTags/systemProperties are the Presto wire envelope
# (X-Presto-* headers flowing through statement.py/worker.py); `sf` is
# the benchmark scale-factor hint the test harness threads through
_NON_PROPERTY_KEYS = {"user", "catalog", "source", "clientTags",
                      "systemProperties", "sf"}

# PRESTO_TPU_* envs that deliberately do NOT key the plan cache: they
# cannot change the staged IR of any kernel. Adding an env here is a
# reviewed decision -- if the knob can alter a compiled program's
# behavior it belongs in KERNEL_MODE_ENVS instead.
_UNKEYED_ENVS = {
    "PRESTO_TPU_CLIENT_DEADLINE_S",   # client-side HTTP deadline
    "PRESTO_TPU_FAILPOINTS",          # chaos fault injection (test-only)
    "PRESTO_TPU_FLIGHT_DIR",          # flight-recorder dump directory
    "PRESTO_TPU_FLIGHT_MAX_DUMPS",    # flight-recorder dump cap
    "PRESTO_TPU_INTERNAL_SECRET",     # worker auth token
    "PRESTO_TPU_SLOW_QUERY_MS",       # observability threshold
}


def _py_files():
    for root, _dirs, files in os.walk(PKG):
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(root, name)


def _scan(path):
    """-> (session property names read, PRESTO_TPU env names read)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    props, envs = set(), set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        # session_flag(session, "name", ...)
        if isinstance(fn, ast.Name) and fn.id == "session_flag" \
                and len(node.args) >= 2 \
                and isinstance(node.args[1], ast.Constant) \
                and isinstance(node.args[1].value, str):
            props.add(node.args[1].value)
        # <something session-ish>.get("name", ...)
        elif isinstance(fn, ast.Attribute) and fn.attr == "get" \
                and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str) \
                and "session" in ast.unparse(fn.value).lower():
            props.add(node.args[0].value)
        # os.environ.get("PRESTO_TPU_X") / os.getenv("PRESTO_TPU_X")
        elif isinstance(fn, ast.Attribute) \
                and fn.attr in ("get", "getenv") \
                and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str) \
                and node.args[0].value.startswith("PRESTO_TPU_"):
            recv = ast.unparse(fn.value)
            if "environ" in recv or recv == "os":
                envs.add(node.args[0].value)
    # os.environ["PRESTO_TPU_X"] subscripts
    for node in ast.walk(tree):
        if isinstance(node, ast.Subscript) \
                and "environ" in ast.unparse(node.value) \
                and isinstance(node.slice, ast.Constant) \
                and isinstance(node.slice.value, str) \
                and node.slice.value.startswith("PRESTO_TPU_"):
            envs.add(node.slice.value)
    return props, envs


def _scan_all():
    props, envs = {}, {}
    for path in _py_files():
        rel = os.path.relpath(path, REPO)
        p, e = _scan(path)
        for name in p:
            props.setdefault(name, []).append(rel)
        for name in e:
            envs.setdefault(name, []).append(rel)
    return props, envs


def test_every_session_property_read_is_registered():
    """No session.get / session_flag read of a name the registry does
    not carry: a typo'd key silently returns its fallback forever."""
    props, _ = _scan_all()
    registered = set(SESSION_PROPERTIES.properties) | _NON_PROPERTY_KEYS
    unknown = {name: files for name, files in sorted(props.items())
               if name not in registered}
    assert not unknown, (
        f"session keys read but not in SESSION_PROPERTIES: {unknown}")


def test_every_presto_tpu_env_read_is_classified():
    """Every PRESTO_TPU_* env read is either plan-cache-keyed
    (KERNEL_MODE_ENVS) or on the explicit unkeyed allowlist -- an
    unclassified behavior env serves stale compiled programs."""
    _, envs = _scan_all()
    keyed = {n for n, _ in KERNEL_MODE_ENVS}
    unknown = {name: files for name, files in sorted(envs.items())
               if name not in keyed | _UNKEYED_ENVS}
    assert not unknown, (
        f"PRESTO_TPU_* envs read but neither kernel-mode-keyed nor "
        f"allowlisted unkeyed: {unknown}")
    # the allowlist itself cannot go stale or double-register
    assert not (keyed & _UNKEYED_ENVS)


def test_every_kernel_mode_env_is_actually_consumed():
    """The reverse direction: a KERNEL_MODE_ENVS entry nothing reads is
    dead cache-key surface (it silently fragments plan reuse). Envs may
    be consumed through a module constant (AUDIT_ENV, DONATION_ENV), so
    this scans source text outside the registry and the linter."""
    for name, _default in KERNEL_MODE_ENVS:
        hits = []
        for path in _py_files():
            rel = os.path.relpath(path, REPO).replace(os.sep, "/")
            if rel == "presto_tpu/exec/plan_cache.py" or \
                    rel.startswith("presto_tpu/lint/"):
                continue
            with open(path, encoding="utf-8") as f:
                if f'"{name}"' in f.read():
                    hits.append(rel)
        assert hits, f"{name} is in KERNEL_MODE_ENVS but nothing reads it"


def test_buffer_donation_property_is_registered_and_keyed():
    """The donation knob rides both registries: session property (off
    by default -- donation must be opted into) and kernel-mode env."""
    prop = SESSION_PROPERTIES.properties["buffer_donation"]
    assert prop.default is False
    assert ("PRESTO_TPU_DONATION", "0") in KERNEL_MODE_ENVS


@pytest.mark.parametrize("name", sorted(_UNKEYED_ENVS))
def test_unkeyed_allowlist_entries_are_still_read(name):
    """Allowlist hygiene: each unkeyed env is still read somewhere;
    a vestigial entry must be dropped, not carried."""
    _, envs = _scan_all()
    assert name in envs, f"{name} allowlisted but no longer read"


# -- one ledger of host time --------------------------------------------
#
# The span seam (exec/stats.py) is the only record of where a
# statement's host time went. These keep the surfaces of the two
# ledgers that stood beside it (exec/timeline.py, exec/profiler.py)
# from growing back: routes, system tables, knobs, stats keys, metric
# families.

_GONE_ROUTES = ("/v1/timeline", "/v1/profile")
_GONE_TABLES = ("occupancy", "kernels")
_GONE_PROPERTIES = ("timeline", "continuous_profiling")
_GONE_ENVS = ("PRESTO_TPU_TIMELINE", "PRESTO_TPU_PROFILE")
_GONE_FAMILIES = ("presto_tpu_timeline_", "presto_tpu_overlap_fraction",
                  "presto_tpu_device_idle_us")


@pytest.fixture(scope="module")
def both_tiers():
    from presto_tpu.server import TpuWorkerServer
    from presto_tpu.server.statement import StatementServer
    worker = TpuWorkerServer(sf=0.01).start()
    try:
        with StatementServer(sf=0.01) as srv:
            yield {"statement": srv.url, "worker": worker.url}
    finally:
        worker.stop()


@pytest.mark.parametrize("route", _GONE_ROUTES)
@pytest.mark.parametrize("tier", ["statement", "worker"])
def test_one_ledger_route_is_gone(both_tiers, tier, route):
    import urllib.error
    import urllib.request
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(both_tiers[tier] + route)
    assert e.value.code == 404


@pytest.mark.parametrize("table", _GONE_TABLES)
def test_one_ledger_system_table_does_not_resolve(table):
    from presto_tpu.connectors import system
    from presto_tpu.sql import sql
    assert table not in system.SCHEMA
    with pytest.raises(KeyError):
        sql(f"SELECT * FROM system.{table}", sf=0.01)


@pytest.mark.parametrize("name", _GONE_PROPERTIES)
def test_one_ledger_session_property_is_unknown(name):
    from presto_tpu.utils.config import Session
    assert name not in SESSION_PROPERTIES.properties
    with pytest.raises(KeyError, match="unknown config property"):
        Session({name: False})


@pytest.mark.parametrize("env", _GONE_ENVS)
def test_one_ledger_env_keys_no_plan(env, monkeypatch):
    """Neither env is a kernel mode: setting it moves no cache key, and
    the next identical statement is a plan-cache hit."""
    from presto_tpu.exec.plan_cache import _kernel_mode
    from presto_tpu.sql import sql
    assert env not in dict(KERNEL_MODE_ENVS)
    text = "SELECT count(*) FROM nation WHERE regionkey < 3"
    monkeypatch.delenv(env, raising=False)
    mode = _kernel_mode()
    sql(text, sf=0.01)
    monkeypatch.setenv(env, "0")
    assert _kernel_mode() == mode
    counters = sql(text, sf=0.01).query_stats.counters
    assert counters.get("plan_cache_hits", 0) >= 1
    assert counters.get("plan_cache_misses", 0) == 0


def _parent_era_document():
    """`stats.queryStats` as a worker of the parent commit ships it:
    today's document plus a populated `timeline` slice."""
    from presto_tpu.sql import sql
    doc = sql("SELECT count(*) FROM region", sf=0.01).query_stats.to_json()
    return doc, {**doc, "timeline": {
        "intervals": [["host", "connector_read", 0, 10, 90, 640]],
        "totals": {"connector_read": [1, 80, 640]}, "dropped": 0}}


@pytest.mark.parametrize("direction", ["to_json", "from_json"])
def test_one_ledger_query_stats_has_no_timeline(direction):
    from presto_tpu.exec.stats import QueryStats
    doc, older = _parent_era_document()
    if direction == "to_json":
        assert "timeline" not in doc
        assert {"stages", "datapath", "counters", "operators",
                "accuracy"} <= set(doc)
    else:
        # an older worker's key is ignored, the rest round-trips
        assert QueryStats.from_json(older).to_json() == doc


@pytest.mark.parametrize("tier", ["statement", "worker"])
def test_one_ledger_metrics_export_no_second_clock(both_tiers, tier):
    import urllib.request
    with urllib.request.urlopen(both_tiers[tier] + "/v1/metrics") as r:
        text = r.read().decode()
    assert "presto_tpu_stage_seconds" in text
    families = {line.split()[2] for line in text.splitlines()
                if line.startswith("# TYPE")}
    assert not [f for f in families if f.startswith(_GONE_FAMILIES)]
