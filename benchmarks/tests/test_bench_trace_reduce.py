"""The trace reduction, held to hand-made events and to a recorded trace."""

import json
import os

import pytest

from benchmarks.harness import peaks, trace_reduce as tr

MS = 1_000_000  # ns


def _events():
    return {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 10 * MS, 20 * MS],      # 10..30
            ["fusion.2", 30 * MS, 10 * MS],      # 30..40, adjacent: union 30
            ["copy.3", 70 * MS, 10 * MS],        # 70..80
            ["fusion.1", 95 * MS, 20 * MS],      # 95..115, clipped at 100
            ["early", 0, 5 * MS]]},              # before the window opens
        "host": [
            [tr.WINDOW, 5 * MS, 95 * MS],        # 5..100
            [tr.STATEMENT + "q6", 5 * MS, 55 * MS],
            ["TransferToDevice", 41 * MS, 28 * MS],
            ["inner", 50 * MS, 10 * MS],
            [tr.STATEMENT + "q3", 60 * MS, 40 * MS]]}


def test_busy_is_the_union_clipped_to_the_window():
    r = tr.reduce_events(_events())
    assert r["window_s"] == pytest.approx(0.095)
    assert r["busy_s"] == pytest.approx(0.045)   # 30 + 10 + 5
    assert r["idle_pct"] == pytest.approx(100 * (1 - 45 / 95))


def test_top_ops_sum_their_durations_inside_the_window():
    ops = dict(tr.reduce_events(_events())["device_ops"])
    assert ops == {"fusion.1": pytest.approx(0.025),
                   "fusion.2": pytest.approx(0.010),
                   "copy.3": pytest.approx(0.010)}
    assert tr.reduce_events(_events(), top=1)["device_ops"][0][0] == "fusion.1"


def test_an_op_that_holds_others_keeps_only_its_own_time():
    e = _events()
    e["devices"]["/device:TPU:0"] = [
        ["%while.1 = (u32[]) while(...)", 10 * MS, 50 * MS],
        ["%fusion.7 = u32[8] fusion(...)", 10 * MS, 20 * MS],
        ["%fusion.8 = u32[8] fusion(...)", 30 * MS, 25 * MS],
        ["%fusion.7 = u32[8] fusion(...)", 70 * MS, 5 * MS]]
    r = tr.reduce_events(e)
    assert dict(r["device_ops"]) == {"fusion.7": pytest.approx(0.025),
                                     "fusion.8": pytest.approx(0.025),
                                     "while.1": pytest.approx(0.005)}
    assert r["busy_s"] == pytest.approx(0.055)


def test_gaps_are_named_by_statement_and_innermost_host_event():
    gaps = dict(tr.reduce_events(_events())["idle_gaps"])
    # 5..10 in q6 with no host event; 40..70 midpoint 55 in q6/inner;
    # 80..95 in q3 with no host event
    assert gaps == {"q6/host_python": pytest.approx(0.005),
                    "q6/inner": pytest.approx(0.030),
                    "q3/host_python": pytest.approx(0.015)}
    assert sum(gaps.values()) == pytest.approx(0.095 - 0.045)


def test_busy_is_averaged_over_the_chips_used():
    e = _events()
    e["devices"]["/device:TPU:1"] = [["fusion.1", 10 * MS, 5 * MS]]
    e["devices"]["/device:TPU:2"] = []  # held by the cell, not used
    e["devices"]["/device:TPU:3"] = [["late", 200 * MS, MS]]
    assert tr.reduce_events(e)["busy_s"] == pytest.approx((0.045 + 0.005) / 2)


@pytest.mark.parametrize("broken", ["no_window", "no_device", "no_ops"])
def test_a_trace_with_nothing_to_read_is_an_error(broken):
    e = _events()
    if broken == "no_window":
        e["host"] = e["host"][1:]
    elif broken == "no_device":
        e["devices"] = {}
    else:
        e["devices"] = {"/device:TPU:0": [["late", 200 * MS, MS]]}
    with pytest.raises(ValueError):
        tr.reduce_events(e)


def test_recorded_trace_from_the_chip():
    """A traced window of mem_sf1.scan on the v5e, as read_xplane gave it.
    The busy time is held to a number got another way (every 100 ns of
    the window marked busy or not), the order of names to what this
    reduction gave when the trace was recorded."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "recorded_trace.json")) as f:
        recorded = json.load(f)
    r = tr.reduce_events(recorded["events"])
    assert r["busy_s"] == pytest.approx(recorded["raster_busy_s_at_100ns"],
                                        rel=1e-3)
    assert r["window_s"] == pytest.approx(5.542398706)
    assert r["idle_pct"] == pytest.approx(99.81, abs=0.01)
    for key in ("device_ops", "idle_gaps"):
        assert [k for k, _ in r[key]] == recorded["expected"][key]
        assert len(r[key]) <= 10
    assert r["device_ops"][0][0] == "select_reduce_fusion"
    assert r["idle_gaps"][0][0] == "q6/host_python"


def test_peaks_know_the_v5e_and_nothing_else():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9", "hbm_bytes_per_s")
