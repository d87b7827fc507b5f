"""Self-check of the trace reduction: hand-made events with hand-computed
numbers, and a small trace recorded on a TPU v5e (``data/``) reduced
against a brute-force count at nanosecond resolution.

    PYTHONPATH=.:src python3 -m pytest -q bench/tests/test_trace.py
"""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_busy_union_leaves_and_gaps():
    host = [("bench:request", 0, 100), ("bench:step", 0, 50),
            ("PjitFunction(f)", 0, 10), ("bench:fetch", 60, 100)]
    devices = {"/device:TPU:0": [
        ("while", 5, 40),          # holds the next two
        ("fusion.1", 5, 15),
        ("fusion.2", 20, 40),
        ("copy", 30, 45),          # overlaps, contained in none
        ("fusion.3", 70, 80),
        ("late", 95, 130),         # clipped at the window's end
    ]}
    s = trace.reduce(devices, host)
    assert s.window_s == pytest.approx(100e-9)
    # busy: [5, 45] + [70, 80] + [95, 100] = 40 + 10 + 5
    assert s.busy_s == pytest.approx(55e-9)
    assert s.idle_share() == pytest.approx(0.45)
    assert s.op_seconds == pytest.approx({
        "fusion.1": 10e-9, "fusion.2": 20e-9, "copy": 15e-9,
        "fusion.3": 10e-9, "late": 5e-9})
    # gaps: [0,5] in step and Pjit, [45,70] (midpoint 57.5) in the
    # request alone, [80,95] in fetch
    assert s.gap_seconds == pytest.approx({
        "step > PjitFunction(f)": 5e-9, "request": 25e-9,
        "fetch": 15e-9})
    bd = s.breakdown(k=2)
    assert bd["device_ops"] == [["fusion.2", pytest.approx(20e-9)],
                                ["copy", pytest.approx(15e-9)]]
    assert bd["idle_gaps"][0] == ["request", pytest.approx(25e-9)]


def test_two_devices_average():
    host = [("bench:request", 0, 10)]
    s = trace.reduce({"/device:TPU:0": [("a", 0, 10)],
                      "/device:TPU:1": [("a", 0, 4)]}, host)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(7e-9)


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({}, [("bench:request", 0, 10)])


def _brute(devices, host):
    marks = [(s, e) for n, s, e in host if n == trace.WINDOW_SPAN]
    w0, w1 = min(s for s, _ in marks), max(e for _, e in marks)
    t0 = int(np.floor(w0))
    busy = []
    for evs in devices.values():
        mask = np.zeros(int(np.ceil(w1)) - t0 + 1, bool)
        for _, s, e in evs:
            lo, hi = max(s, w0), min(e, w1)
            if hi > lo:
                mask[int(round(lo)) - t0:int(round(hi)) - t0] = True
        busy.append(mask.sum())
    return (w1 - w0) * 1e-9, np.mean(busy) * 1e-9


@pytest.mark.skipif(not (DATA / "v5e_small.xplane.pb").is_file(),
                    reason="no recorded trace")
def test_recorded_v5e_trace():
    devices, host = trace.read_xplane(str(DATA / "v5e_small.xplane.pb"))
    assert list(devices) == ["/device:TPU:0"]
    assert sum(n == trace.WINDOW_SPAN for n, _, _ in host) == 3
    s = trace.reduce(devices, host)
    window_s, busy_s = _brute(devices, host)
    assert s.window_s == pytest.approx(window_s)
    assert s.busy_s == pytest.approx(busy_s, rel=1e-3, abs=5e-9)
    assert 0.0 < s.busy_s < s.window_s
    assert set(s.gap_seconds) <= {"request", "step", "fetch",
                                  "no host span"} | {
        k for k in s.gap_seconds if " > " in k}
