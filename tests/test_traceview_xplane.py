"""The by-hand reader of the profiler's trace (presto_tpu/traceview.py):
device time by the program's scopes, device idle time by its spans, on
hand-made event lists and one hand-made trace file."""

import os
import re

import pytest

from presto_tpu import traceview as tv

MS = 1_000_000  # ns


@pytest.mark.parametrize("op_name,scope", [
    ("jit(run)/OutputNode.0/JoinNode.3/hash_join/_sort_build/lex_sort/"
     "while/body/sort", "JoinNode.3/hash_join/_sort_build/lex_sort"),
    ("jit(run)/AggregationNode.0/jit(_where)/select_n",
     "AggregationNode.0"),
    # a trace of a program that still named its region: not read
    ("jit(run)/region.R1/ProjectNode.2/add", "ProjectNode.2"),
    ("jit(run)/add", ""),
    ("jit(other)/mul", ""),
    ("", ""),
])
def test_scope_of_keeps_what_the_program_named(op_name, scope):
    assert tv.scope_of(op_name) == scope


def test_ops_scopes_are_the_named_scopes_of_ops_and_exchanges():
    found = set()
    for package in ("ops", "parallel"):
        there = os.path.join(os.path.dirname(tv.__file__), package)
        for name in os.listdir(there):
            if name.endswith(".py"):
                with open(os.path.join(there, name)) as f:
                    text = f.read()
                found |= set(re.findall(r'named_scope\("([^"]+)"\)', text))
                found |= set(re.findall(r'\bname="([a-z_]+)",\n\s*\)\(',
                                        text))
    assert found == set(tv.OPS_SCOPES)
    assert {"exchange_by_hash", "_route_rows", "broadcast_build",
            "exchange_by_range"} <= found


def test_scope_seconds_gives_an_op_its_own_time():
    join, sort = "R0/JoinNode.1/hash_join", "R0/JoinNode.1/hash_join/lex_sort"
    ops = [
        # a while of 10 ms holding two fusions of 3 ms each, named twice
        ("%while.7 = (s32[]) while(...)", join, 0, 10 * MS),
        ("%fusion.483 = u32[8] fusion(...)", sort, 1 * MS, 4 * MS),
        ("%fusion.483 = u32[8] fusion(...)", sort, 5 * MS, 8 * MS),
        ("%fusion.9 = f32[] fusion(...)", "", 12 * MS, 13 * MS),
    ]
    scopes, per_op = tv.scope_seconds(ops)
    assert scopes == pytest.approx({join: 0.004, sort: 0.006, "": 0.001})
    assert per_op[("fusion.483", sort)] == pytest.approx(0.006)
    assert per_op[("while.7", join)] == pytest.approx(0.004)
    assert sum(scopes.values()) == pytest.approx(0.011)  # the busy time


def test_statements_are_read_from_the_programs_own_spans():
    """No name of a client's: on its engine thread a statement ends
    with `render`; a library call, which renders nothing, is the run of
    spans that is left."""
    engine = [("presto:plan", 5 * MS, 15 * MS),
              ("presto:plan.sql", 6 * MS, 9 * MS),
              ("presto:execute", 15 * MS, 95 * MS),
              ("presto:render", 95 * MS, 99 * MS),
              # the thread's next statement
              ("presto:batch", 120 * MS, 121 * MS),
              ("presto:plan", 121 * MS, 130 * MS),
              ("presto:render", 180 * MS, 181 * MS)]
    library = [("presto:plan", 200 * MS, 210 * MS),
               ("presto:fetch", 240 * MS, 250 * MS)]
    assert tv.statement_intervals([library, engine, []]) == [
        (5 * MS, 99 * MS), (120 * MS, 181 * MS), (200 * MS, 250 * MS)]


def test_gap_seconds_split_by_overlap_not_by_midpoint():
    # one statement of 100 ms; the device runs 40..60 and 90..100
    ops = [(40 * MS, 60 * MS), (90 * MS, 100 * MS)]
    spans = [
        ("presto:plan", 5 * MS, 15 * MS),
        ("presto:staging", 15 * MS, 38 * MS),
        ("presto:connector_read", 16 * MS, 30 * MS),   # child of staging
        ("presto:execute", 38 * MS, 95 * MS),
        ("presto:device_wait", 39 * MS, 94 * MS),
        ("SomeRuntimeEvent", 0, 100 * MS),             # not the program's
    ]
    got, nothing = tv.gap_seconds_by_span(
        ops, spans, [(0, 100 * MS), (200 * MS, 300 * MS)])
    assert got == pytest.approx({
        "(no span)": 0.005,             # 0..5; 95..100 is busy
        "plan": 0.010,
        "connector_read": 0.014,        # the innermost span owns it
        "staging": 0.009,               # 15..16 and 30..38
        "execute": 0.001,               # 38..39 (94..95 is busy)
        "device_wait": 0.001 + 0.030,   # 39..40 and 60..90: ONE gap of
    })                                  # 30 ms that a midpoint rule
    assert sum(got.values()) == pytest.approx(0.070)   # gives one label
    assert nothing == pytest.approx({"(no span)": 0.100})
    assert tv.gap_seconds_by_span(ops, spans, []) == []


def _ld(number, payload):
    """One length-delimited protobuf field, off the cuff."""
    size, head = len(payload), bytes([number << 3 | 2])
    while size >= 0x80:
        head += bytes([size & 0x7F | 0x80])
        size >>= 7
    return head + bytes([size]) + payload


def _hlo_proto(instructions):
    """HloProto{hlo_module{computations{instructions{name, metadata{
    op_name}}}}} for (name, op_name) pairs."""
    comp = b"".join(_ld(2, _ld(1, n.encode()) + _ld(7, _ld(2, o.encode())))
                    for n, o in instructions)
    return _ld(1, _ld(1, b"jit_run") + _ld(3, _ld(1, b"main") + comp))


def test_device_time_by_scope_reads_a_hand_made_xplane(tmp_path):
    """The file a TPU trace is: ops on the device plane's "XLA Ops"
    line with no scope of their own, their program on "XLA Modules",
    the program's HLO (with each instruction's op_name) in the
    /host:metadata plane, the program's spans on the host plane. ONE
    compiled program (a plan-cache hit) runs for region R0 and then for
    R1: each run's ops are named by the dispatch span around it, and
    the program that ran under no dispatch (the dynamic filter's) by
    none."""
    from jax.profiler import ProfileData
    join = "jit(run)/OutputNode.0/JoinNode.2/hash_join/while/body/gather"
    hlo = _hlo_proto([("fusion.483", join), ("fusion.9", "jit(run)/add"),
                      ("sort.1", "jit(other)/sort")])
    octal = "".join("\\%03o" % b for b in hlo)
    ps = 1000 * MS  # picoseconds in a millisecond

    def ev(mid, start_ms, dur_ms, region=None):
        stat = f'stats {{ metadata_id: 1 str_value: "{region}" }}' \
            if region else ""
        return (f"events {{ metadata_id: {mid} offset_ps: {start_ms * ps} "
                f"duration_ps: {dur_ms * ps} {stat} }}")

    def md(mid, name):
        return (f'event_metadata {{ key: {mid} value {{ id: {mid} '
                f'name: "{name}" }} }}')
    text = f"""
    planes {{ name: "/device:TPU:0"
      lines {{ name: "XLA Modules" {ev(1, 10, 30)} {ev(5, 50, 10)}
              {ev(1, 70, 20)} }}
      lines {{ name: "XLA Ops" {ev(2, 10, 20)} {ev(3, 30, 10)}
              {ev(4, 50, 10)} {ev(2, 70, 20)} }}
      {md(1, "jit_run(7)")} {md(5, "jit_other(8)")}
      {md(2, "%fusion.483 = u32[8]{{0}} fusion(u32[8]{{0}} %p)")}
      {md(3, "%fusion.9 = f32[] fusion(f32[] %q)")}
      {md(4, "%sort.1 = f32[8]{{0}} sort(f32[8]{{0}} %r)")} }}
    planes {{ name: "/host:metadata"
      event_metadata {{ key: 1 value {{ id: 1 name: "jit_run(7)"
        stats {{ metadata_id: 1 bytes_value: "{octal}" }} }} }}
      stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }} }}
    planes {{ name: "/host:CPU"
      lines {{ name: "engine" {ev(2, 0, 8)} {ev(3, 11, 1, "R0")}
              {ev(4, 12, 30, "R0")} {ev(5, 44, 16)} {ev(3, 69, 2, "R1")}
              {ev(4, 71, 19, "R1")} {ev(6, 90, 10)} }}
      {md(2, "presto:staging")} {md(3, "presto:dispatch")}
      {md(4, "presto:device_wait")} {md(5, "presto:dynfilter")}
      {md(6, "presto:render")}
      stat_metadata {{ key: 1 value {{ id: 1 name: "region" }} }} }}
    """
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert tv.hlo_op_names(path.read_bytes()) == {"jit_run(7)": {
        "fusion.483": join, "fusion.9": "jit(run)/add",
        "sort.1": "jit(other)/sort"}}
    found = tv.device_time_by_scope(str(path))
    assert found["busy_s"] == pytest.approx(0.060)
    assert found["scopes"] == pytest.approx({
        "R0/JoinNode.2/hash_join": 0.020, "R0": 0.010,
        "R1/JoinNode.2/hash_join": 0.020,
        "": 0.010})     # sort.1: no dispatch around it, no HLO kept
    assert found["ops"][("fusion.483", "R1/JoinNode.2/hash_join")] == \
        pytest.approx(0.020)
    (only,) = found["statements"]
    assert only["start_s"] == 0 and only["wall_s"] == pytest.approx(0.100)
    assert only["gaps"] == pytest.approx({
        "staging": 0.008, "(no span)": 0.002 + 0.002 + 0.009,
        "device_wait": 0.002, "dynfilter": 0.006, "dispatch": 0.001,
        "render": 0.010})      # the 40 ms in which no op ran


def test_render_scopes_prints_the_three_tables():
    text = tv.render_scopes({
        "busy_s": 2.0, "scopes": {"R0/JoinNode.1/hash_join": 1.5, "": 0.5},
        "ops": {("fusion.483", "R0/JoinNode.1/hash_join"): 1.5},
        "statements": [{"start_s": 0.0, "wall_s": 6.25,
                        "gaps": {"connector_read": 0.25}}]})
    assert "75.0%  R0/JoinNode.1/hash_join" in text
    assert "fusion.483" in text and "(none)" in text
    assert "inside statement 1 (at 0.000 s, 6.250 s): 0.250000 s" in text
