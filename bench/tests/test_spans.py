"""The program's spans, counters and scoped device ops, and their readers."""
from pathlib import Path

import pytest

import counts
import run
import spans
import trace_reduce

DATA = Path(__file__).parent / "data"
UPDATE = "jit(_update)/"
LOOP = UPDATE + "cpapr.epilogue/while/body/cpapr.phi/jit(_run_mu)/"


def test_decoder_reads_tf_op_from_event_metadata():
    """In the recorded v5e trace, ``%fusion`` is ``sin(x) @ x`` and
    ``%multiply_reduce_fusion`` is ``(2 x).sum()``; the times are those
    ``ProfileData`` gives, to the nanoseconds it rounds off."""
    path = str(DATA / "v5e_tiny.xplane.pb")
    (dev,) = spans.parse(path)["devices"].values()
    assert [n for n, _, _ in dev["ops"]] == [
        "jit(<lambda>)/dot_general:", "jit(<lambda>)/reduce_sum:"] * 2
    (old,) = trace_reduce.load(path)["devices"].values()
    assert [n.split(" =")[0] for n, _, _ in old[trace_reduce.OPS]] == [
        "%fusion", "%multiply_reduce_fusion"] * 2
    for new_ev, old_ev in ((dev["ops"], old[trace_reduce.OPS]),
                           (dev["modules"], old[trace_reduce.MODULES])):
        for (_, s, e), (_, s0, e0) in zip(new_ev, old_ev):
            assert abs(s - s0) < 2 and abs(e - e0) < 2


def test_recorded_scoped_v5e_trace():
    """Recorded on a TPU v5e by ``data/scoped_v5e.py``: a jitted
    ``_update`` with a sort under ``cpapr.pi`` and a matmul and sum under
    ``cpapr.phi``, in a span ``cpapr.solve`` opened with ``modes=4`` and
    given ``sweeps=5`` and ``host_syncs=55`` as it ended.  Its device
    ops carry times about 0.7 ms before the host dispatched them (the
    profiler's host and device clocks differ by that much), so they are
    read unclipped; a 40 s window does not notice."""
    events = spans.parse(str(DATA / "scoped_v5e.xplane.pb"))
    (dev,) = events["devices"].values()
    scoped = [n for n, _, _ in dev["ops"] if n.startswith(UPDATE)]
    assert {spans.scope(n) for n in scoped} == {"cpapr.pi", "cpapr.phi"}
    # what XLA adds (copies, an iota) has no tf_op and no scope
    assert {spans.scope(n) for n, _, _ in dev["ops"]} - {None} == \
        {"cpapr.pi", "cpapr.phi"}
    assert [n.split("(")[0] for n, _, _ in dev["modules"]] == ["jit__update"]
    w = spans.window(events)
    (solve,) = spans.named(w, "cpapr.solve")
    assert solve[3] == {"modes": 4, "sweeps": 5, "host_syncs": 55}
    assert run.load_reader("host_syncs.solve")({"spans": w}) == 11.0


@pytest.mark.parametrize("tf_op,want", [
    (LOOP + "cpapr.layout/pad:", "cpapr.layout"),
    (LOOP + "pallas_call:", "cpapr.phi"),
    (UPDATE + "cpapr.epilogue/while:", "cpapr.epilogue"),
    ("jit(guard_ok)/and:", None),
])
def test_scope_is_the_innermost_cpapr_component(tf_op, want):
    assert spans.scope(tf_op) == want


def _window():
    """One solve in a window of 1000 ns, by hand.  Device busy: a sort
    during the sorts (160-190) and the mode update's ops (310-630)."""
    host = [
        ("bench.solve", 100, 900, {}),
        ("cpapr.solve", 110, 890, {"modes": 4, "sweeps": 2, "inner": 10,
                                   "host_syncs": 22}),
        ("cpapr.prepare", 120, 300, {}),
        ("cpapr.validate", 120, 150, {}),
        ("cpapr.sort", 150, 200, {"mode": 0}),
        ("cpapr.policy", 200, 260, {}),
        ("cpapr.build", 260, 290, {}),
        ("cpapr.sweep", 300, 600, {"outer": 1}),
        ("cpapr.mode_update", 300, 320, {"mode": 0, "strategy": "pallas"}),
    ]
    ops = [
        ("jit(argsort)/sort:", 160, 190),
        (UPDATE + "cpapr.pi/mul:", 310, 350),
        (UPDATE + "cpapr.layout/gather:", 350, 400),
        (UPDATE + "cpapr.epilogue/while:", 400, 600),
        (LOOP + "pallas_call:", 410, 500),
        (LOOP + "cpapr.layout/pad:", 500, 540),
        (UPDATE + "cpapr.epilogue/div:", 600, 620),
        ("jit(guard_ok)/and:", 620, 630),
    ]
    modules = [("jit_argsort(3)", 160, 190), ("jit__update(1)", 305, 625),
               ("jit_guard_ok(2)", 620, 631)]
    return {"window": (0, 1000), "host": host,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def _record(w):
    return {"trace": {"window_s": 1e-6}, "spans": w,
            "solves": [{"n_outer": 2, "inner": 10}],
            "work": {"nnz": 1000, "dims": [10, 20, 30], "rank": 4},
            "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def test_self_seconds_by_scope():
    # the loop's own time less its body's: 200 - 90 - 40 = 70 ns
    assert spans.scope_seconds(_window()) == pytest.approx({
        None: 40e-9, "cpapr.pi": 40e-9, "cpapr.layout": 90e-9,
        "cpapr.phi": 90e-9, "cpapr.epilogue": 90e-9})
    assert spans.module_seconds(_window(), "jit__update") == \
        pytest.approx(320e-9)


def test_idle_by_span():
    """650 of the 1000 ns are idle; each stretch goes to the innermost
    span over it."""
    assert spans.idle_by_span(_window()) == pytest.approx({
        "cpapr.validate": 30e-9, "cpapr.sort": 20e-9, "cpapr.policy": 60e-9,
        "cpapr.build": 30e-9, "cpapr.prepare": 10e-9,
        "cpapr.mode_update": 10e-9, "cpapr.sweep": 0.0,
        "cpapr.solve": 270e-9, "bench.solve": 20e-9, "(outside)": 200e-9})


def test_readers_by_hand():
    r = _record(_window())
    f, _ = counts.phi_pass(1000, [10, 20, 30], 4, 0)
    least_b = min(counts.phi_pass(1000, [10, 20, 30], 4, n)[1]
                  for n in range(3))
    passes = 10 + 2 * 3
    least, _ = counts.least_time(passes * f, passes * least_b, r["peaks"])
    want = {
        "prep_s.solve": 180e-9,
        "idle_prep_share.solve": 100 * 150 / 650,
        "host_syncs.solve": 11.0,
        "phi_kernel_roofline.solve": 100 * least / 90e-9,
        "layout_share.solve": 100 * 90 / 320,
    }
    for name, value in want.items():
        assert run.load_reader(name)(r) == pytest.approx(value), name


def _without_program_marks(w):
    """The window as a program without spans or scopes leaves it."""
    w["host"] = [ev for ev in w["host"] if ev[0] == "bench.solve"]
    for d in w["devices"].values():
        d["ops"] = [(n.replace("cpapr.", "x."), s, e) for n, s, e in d["ops"]]
    return w


NEW = ["prep_s.solve", "idle_prep_share.solve", "host_syncs.solve",
       "phi_kernel_roofline.solve", "layout_share.solve"]


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["untraced", "no marks", "no stats",
                                  "no device"])
def test_readers_read_nothing_where_nothing_is(name, case):
    if case == "untraced":
        r = {"trace": None}
    elif case == "no marks":
        r = _record(_without_program_marks(_window()))
    elif case == "no stats":  # spans and scoped ops, but no counters
        w = _window()
        w["host"] = [ev[:3] + ({},) for ev in w["host"]]
        r = _record(w)
    else:
        w = _window()
        w["devices"] = {}
        r = _record(w)
    got = run.load_reader(name)(r)
    if case == "no stats" and name != "host_syncs.solve":
        assert got is not None and got > 0
    elif case == "no device" and name in ("prep_s.solve",
                                          "host_syncs.solve"):
        assert got is not None and got > 0  # host spans alone
    else:
        assert got is None
