"""The reduction from a trace to busy time, op times and named idle gaps."""
import json
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).parent / "data"


def test_reduce_hand_computed():
    d = json.loads((DATA / "events.json").read_text())
    ev = d["events"]
    ev["host"] = [tuple(e) for e in ev["host"]]
    got = trace_reduce.reduce(ev)
    want = d["expect"]
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["ops"] == pytest.approx(want["ops"])
    assert got["modules"] == pytest.approx(want["modules"])
    assert [g[0] for g in got["idle_gaps"]] == [g[0] for g in
                                                 want["idle_gaps"]]
    assert [g[1] for g in got["idle_gaps"]] == pytest.approx(
        [g[1] for g in want["idle_gaps"]])
    # busy and the idle gaps fill the window exactly
    assert got["busy_s"] + sum(g[1] for g in got["idle_gaps"]) == \
        pytest.approx(got["window_s"])


def test_no_window_or_no_device_reads_nothing():
    ev = {"host": [("bench.solve", 0, 10)],
          "devices": {"/device:TPU:0": {"XLA Ops": [("f", 0, 5)]}}}
    assert trace_reduce.reduce(ev) is None
    ev = {"host": [("bench.window", 0, 10)], "devices": {}}
    assert trace_reduce.reduce(ev) is None


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5e: two rounds of a 512x512 jitted
    ``sin(x) @ x`` under ``bench.solve``, a 2 ms ``bench.wait``, and a jitted
    ``(2 x).sum()``, inside ``bench.window``.  By hand from its events: the
    window runs from 43,856,119 to 52,361,599 ns; of the four op events
    (11,095 ns, 2,446 ns, 11,158 ns, 2,408 ns) the first ends before the
    window opens, so 16,012 ns are busy."""
    got = trace_reduce.reduce(trace_reduce.load(
        str(DATA / "v5e_tiny.xplane.pb")))
    assert got["n_devices"] == 1
    assert got["window_s"] == pytest.approx(8_505_480e-9)
    assert got["busy_s"] == pytest.approx(16_012e-9)
    assert sorted(got["ops"].values()) == pytest.approx([4_854e-9,
                                                         11_158e-9])
    assert sorted(k.split("(")[0] for k in got["modules"]) == \
        ["jit__lambda", "jit__lambda"]
    assert [g[0] for g in got["idle_gaps"]] == ["bench.wait", "bench.solve"]
    assert sum(g[1] for g in got["idle_gaps"]) == pytest.approx(
        (8_505_480 - 16_012) * 1e-9)
