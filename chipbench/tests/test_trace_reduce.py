"""The reduction from a profile to the benchmark's numbers, against
values worked out by hand: busy and idle time, device time inside host
spans, the top operations, idle gaps named by the host span they fell
in, and the roofline arithmetic.

Run with ``python -m pytest chipbench/tests`` from the repository root.
"""
from __future__ import annotations

import json
import pathlib

import pytest

from chipbench import harness, readings, roofline, trace_reduce

MS = 1e6  # ns


def hand_trace():
    """A 100 ms window: admit [0, 10] and [40, 60], step [10, 40] and
    [60, 90]; device ops A [12, 20], B [15, 25] (overlapping A),
    C [62, 70], D [95, 105] and E [-5, 2] (both cut by the window)."""
    host = [["window", 0, 100], ["admit", 0, 10], ["step", 10, 30],
            ["admit", 40, 20], ["step", 60, 30], ["other", 0, 100]]
    ops = [["A", 12, 8], ["B", 15, 10], ["C", 62, 8], ["D", 95, 10],
           ["E", -5, 7]]
    return {"device": [[[n, s * MS, d * MS] for n, s, d in ops]],
            "host": [[n, s * MS, d * MS] for n, s, d in host
                     if n in trace_reduce.SPAN_NAMES]}


def test_busy_idle_spans_and_gaps():
    r = trace_reduce.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.100)
    # union: [0, 2] + [12, 25] + [62, 70] + [95, 100] = 28 ms
    assert r["busy_s"] == pytest.approx(0.028)
    # step spans hold [12, 25] and [62, 70]; admit holds [0, 2]
    assert r["busy_in_s"]["step"] == pytest.approx(0.021)
    assert r["busy_in_s"]["admit"] == pytest.approx(0.002)
    assert r["device_ops"] == [["B", pytest.approx(0.010)],
                               ["A", pytest.approx(0.008)],
                               ["C", pytest.approx(0.008)],
                               ["D", pytest.approx(0.005)],
                               ["E", pytest.approx(0.002)]]
    # gaps [25, 62] (admit 20 ms > step 17 ms), [70, 95] (step 20 ms),
    # [2, 12] (admit 8 ms > step 2 ms)
    assert r["idle_gaps"] == [["admit", pytest.approx(0.037)],
                              ["step", pytest.approx(0.025)],
                              ["admit", pytest.approx(0.010)]]


def test_chips_are_averaged():
    t = hand_trace()
    t["device"].append([])
    r = trace_reduce.reduce(t)
    assert r["busy_s"] == pytest.approx(0.014)


def test_one_window_span_is_required():
    t = hand_trace()
    t["host"].append(["window", 0, MS])
    with pytest.raises(ValueError):
        trace_reduce.reduce(t)


def test_roofline_arithmetic():
    mean = {"real_commands": 1000, "answers": 10, "sets": 3,
            "surface": False}
    surface = dict(mean, surface=True)
    # per command 64 + 5*4 + 3*4 (+ 4 with the surface); per answer
    # 5*4 per set (x 64 cells with the surface)
    assert roofline.needed_bytes(mean) == 1000 * 96 + 10 * 60
    assert roofline.needed_bytes(surface) == 1000 * 100 + 10 * 3840
    r = trace_reduce.reduce(hand_trace())
    run = harness.Run(setup_s=1.0,
                      outcome=harness.Outcome(0.1, 10, 0, mean),
                      spans=[], trace=r,
                      peaks={"hbm_bytes_per_s": 819e9})
    assert readings.roofline_pct(run, "step") == pytest.approx(
        100 * 96600 / 819e9 / 0.021)
    assert readings.roofline_pct(run, "dispatch") is None
    assert readings.idle_pct(run) == pytest.approx(72.0)


def test_recorded_v5e_excerpt():
    """100 ms of a real trace (``v5e_trace_excerpt.json``): the end of a
    window's admit span, the start of its step span, and the estimation
    program's first 80 operations.  Values worked out by a sweep over
    the operations' start and end points."""
    path = pathlib.Path(__file__).with_name("v5e_trace_excerpt.json")
    r = trace_reduce.reduce(json.loads(path.read_text()))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.021694455)
    assert r["busy_in_s"]["step"] == pytest.approx(0.021694455)
    assert r["busy_in_s"]["admit"] == 0.0
    # 500.0 -> 578.3 ms idle: 38.2 ms in admit, 40.0 ms in step
    assert r["idle_gaps"][0] == ["step", pytest.approx(0.07830545)]
    assert r["device_ops"][0][0].startswith("jit_call %fusion.5 = ")
