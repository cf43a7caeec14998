"""The trace reduction, on hand-made events and on a trace recorded on
the CPU (see record_cpu_trace.py)."""

import os

import pytest

from bench.tests import util  # noqa: F401  (puts src on sys.path)
from bench.lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cpu_trace.xplane.pb")


def test_hand_made_events():
    """Window 0..100 ns.  Device ops: a 10..30, b 20..40 (overlapping: busy
    10..40), a 60..70.  Busy 40 ns, idle 60 ns: 0..10 and 40..60 in span
    bench.host (0..60), 70..100 outside any span but the window."""
    devices = {"/device:TPU:0": [(10, 30, "a"), (20, 40, "b"), (60, 70, "a")]}
    spans = [(0, 100, T.WINDOW_SPAN), (0, 60, "bench.host")]
    r = T.reduce_events(devices, spans)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["op_s"] == pytest.approx({"a": 30e-9, "b": 20e-9})
    assert r["op_calls"] == {"a": 2, "b": 1}
    assert r["idle_s_by_span"] == pytest.approx(
        {"bench.host": 30e-9, T.NO_SPAN: 30e-9})
    b = T.breakdown(r)
    assert b["device_ops"][0] == ["a", pytest.approx(30e-9)]
    assert T.op_seconds(r, lambda n: n == "b") == (pytest.approx(20e-9), 1)
    assert T.op_seconds(r, lambda n: n == "c") is None


def test_two_devices_average():
    """Busy time and op time are averaged over the devices."""
    devices = {"/device:TPU:0": [(0, 50, "a")],
               "/device:TPU:1": [(0, 100, "a")]}
    r = T.reduce_events(devices, [(0, 100, T.WINDOW_SPAN)])
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["op_s"]["a"] == pytest.approx(75e-9)


def test_recorded_cpu_trace():
    """Three matrix products in bench.step spans, 5 ms sleeps between
    them: on the CPU the ops run on the PjRt client's thread, which stands
    in for a device here."""
    devices, spans = T.read_events(
        DATA, device_plane=lambda p: p == "/host:CPU",
        ops_line=lambda p, line: line.startswith("tf_XLAPjRtCpuClient"))
    r = T.reduce_events(devices, spans)
    assert r["op_calls"]["dot_general.1"] == 3
    assert sum(1 for s in spans if s[2] == "bench.step") == 3
    idle = sum(r["idle_s_by_span"].values())
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])
    # the three sleeps are the longest gaps, each about 5 ms
    top = r["longest_gaps"][:3]
    assert all(w == "bench.sleep" and 4.5e-3 < d < 8e-3 for w, d in top)
    assert r["idle_s_by_span"]["bench.sleep"] > 0.8 * idle
