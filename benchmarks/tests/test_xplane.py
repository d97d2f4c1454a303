"""The reduction from a trace to numbers: on a hand-made trace whose
answers are worked by hand, and on a recorded one from the v5e
(``data/trace_train_v5e.json``: one train step of pythia-1.4b.train-t2048 at
depth 4, ``xplane.to_json`` of a loaded profile cut to one step's events)."""

import os

import pytest

from benchmarks.lib import xplane as X

E = X.Event
HERE = os.path.dirname(os.path.abspath(__file__))


def hand_made():
    ops = [
        E("%while.1 = (f32[2]{0}) while(", 0, 1000),  # holds the next two
        E("%fusion.2 = f32[4,8]{1,0} fusion(", 100, 300),
        E("%all-reduce-done.3 = f32[8]{0} all-reduce-done(", 500, 200),
        E("%custom-call.7 = ((bf16[64,2048,128]{2,1,0}), f32[2]) custom-call(", 5000, 2000),
        E("%fusion.9 = f32[4,8]{1,0} fusion(", 7001, 999),  # 1 ns after: a tiny gap
        E("%fusion.11 = f32[4,8]{1,0} fusion(", 20000, 500),  # ends past the window
    ]
    device = X.Plane("/device:TPU:0", [
        X.Line("XLA Ops", ops),
        X.Line("XLA Modules", [E("jit_step(1)", 0, 8000), E("jit_other(2)", 20000, 500)]),
        X.Line("Async XLA Ops", [E("%all-reduce-start.3 = ...", 50, 650)]),
    ])
    host = X.Plane("/host:CPU", [X.Line("python3", [
        E("bench_window", 0, 20250), E("bench_fit", 0, 4000),
        E("bench_submit", 9000, 3000),
    ])])
    other = X.Plane("/device:CUSTOM:Megascale Trace", [X.Line("x", [E("y", 0, 5)])])
    return X.Trace([other, device, host])


def test_window_busy_and_idle_by_hand():
    tr = hand_made()
    assert X.window_of(tr) == (0, 20250)
    assert [p.name for p in X.device_planes(tr)] == ["/device:TPU:0"]
    # union: [0,1000] + [5000,7000] + [7001,8000] + [20000,20250 clipped]
    assert X.busy_seconds(tr) == pytest.approx((1000 + 2000 + 999 + 250) / 1e9)
    # the async line is not busy time
    assert X.busy_seconds(tr) < 20250 / 1e9


def test_own_time_does_not_count_nested_operations_twice():
    own = {e.name.split(" ")[0]: t for e, t in X.self_times(X.op_events(
        X.device_planes(hand_made())[0]))}
    assert own["%while.1"] == 500  # 1000 less its two children
    assert own["%fusion.2"] == 300 and own["%all-reduce-done.3"] == 200
    top = dict(X.top_ops(hand_made()))
    assert top["custom-call bf16[64,2048,128]"] == pytest.approx(2000 / 1e9)
    assert top["fusion f32[4,8]"] == pytest.approx((300 + 999 + 500) / 1e9)
    assert top["while f32[2]"] == pytest.approx(500 / 1e9)


def test_matching_operations_kernels_and_programs():
    tr = hand_made()
    assert X.matching_seconds(tr, "^%all-reduce-done") == pytest.approx(200 / 1e9)
    assert X.matching_seconds(tr, "custom-call|custom_call") == pytest.approx(2000 / 1e9)
    assert X.module_seconds(tr, "jit_step") == (pytest.approx(8000 / 1e9), 1)
    assert X.module_seconds(tr, "nothing") == (0.0, 0)


def test_idle_gaps_go_to_the_span_that_covers_their_middle():
    gaps = dict(X.idle_gaps(hand_made()))
    # [1000,5000] middle 3000 in fit; [8000,20000] middle 14000 in no span
    # (submit ended at 12000); [7000,7001] is under 2 us
    assert gaps["fit"] == pytest.approx(4000 / 1e9)
    assert gaps["outside_spans"] == pytest.approx(12000 / 1e9)
    assert gaps["gaps_under_2_us_between_operations"] == pytest.approx(1 / 1e9)


def test_json_round_trip_keeps_every_number():
    tr = hand_made()
    again = X.from_json(X.to_json(tr))
    assert X.busy_seconds(again) == X.busy_seconds(tr)
    assert X.top_ops(again) == X.top_ops(tr)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_train_v5e.json")) as f:
        return X.from_json(f.read())


def test_recorded_v5e_trace_reduces_to_the_numbers_read_by_hand(recorded):
    planes = X.device_planes(recorded)
    assert [p.name for p in planes] == ["/device:TPU:0"]
    ops = X.op_events(planes[0])
    assert len(ops) == RECORDED["ops"]
    window = X.window_of(recorded)
    assert (window[1] - window[0]) / 1e6 == pytest.approx(RECORDED["window_ms"], abs=1e-3)
    assert X.busy_seconds(recorded) * 1e3 == pytest.approx(RECORDED["busy_ms"], abs=1e-3)
    flash = X.matching_seconds(
        recorded, 'custom_call_target="tpu_custom_call"') * 1e3
    assert flash == pytest.approx(RECORDED["flash_ms"], abs=1e-3)
    secs, runs = X.module_seconds(recorded, "device_step")
    assert runs == RECORDED["step_runs"]
    top = X.top_ops(recorded)
    assert len(top) == 10 and top[0][0] == RECORDED["top_group"]
    assert sum(s for _, s in X.idle_gaps(recorded)) * 1e3 == pytest.approx(
        RECORDED["window_ms"] - RECORDED["busy_ms"], abs=1e-3)


# worked from the fixture's JSON by a script that shares nothing with
# lib/xplane.py (interval union, plain sums), when it was recorded: 12 flash
# kernel calls (4 layers x forward, dq, dk/dv) of 15.06 ms in a 125.06 ms
# step.  The reduction has to keep giving them.
RECORDED = {"ops": 1043, "window_ms": 131.0, "busy_ms": 125.056189,
            "flash_ms": 15.056149, "step_runs": 1, "top_group": "fusion f32[2048]"}
