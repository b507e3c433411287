"""The trace reduction, on a hand-made trace whose numbers can be worked
out by eye and on a small trace recorded on the chip."""

import json
import os

import pytest

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
M = "jit_chipbench_marker(1)"


def _hand_made():
    # device 0: window 1,000..11,000 ns; busy 2,000-5,000 (two ops that
    # overlap) and 8,000-9,000; device 1: busy 1,000-6,000
    def dev(ops, programs):
        return {"ops": ops, "programs": [[M, 900, 100]] + programs + [[M, 10900, 100]]}

    return {"devices": {
        "/device:TPU:0": dev(
            [["%fusion.1 = f32[8,128]{1,0} fusion(x)", 2000, 2000],
             ["%fusion.2 = f32[8,128]{1,0} fusion(y)", 3000, 2000],
             ["%copy.7 = bf16[4]{0} copy(z)", 8000, 1000]],
            [["jit_step(7)", 2000, 3000], ["jit_step(7)", 8000, 1000],
             ["jit_late(9)", 10500, 1000]],
        ),
        "/device:TPU:1": dev(
            [["%fusion.1 = f32[8,128]{1,0} fusion(x)", 1000, 5000]],
            [["jit_step(7)", 1000, 5000]],
        ),
    }}


def test_hand_made_trace():
    r = trace.reduce(_hand_made())
    d0, d1 = r["per_device"]["/device:TPU:0"], r["per_device"]["/device:TPU:1"]
    assert d0["busy_s"] == pytest.approx(4000e-9) and d1["busy_s"] == pytest.approx(5000e-9)
    assert d0["idle_share"] == pytest.approx(0.6) and d1["idle_share"] == pytest.approx(0.5)
    assert r["busy_s"] == pytest.approx(4500e-9) and r["window_s"] == pytest.approx(10000e-9)
    # a program counts only if it ran wholly inside the window
    assert r["program_runs"] == {"jit_step": 3}
    assert r["programs"]["jit_step"] == pytest.approx(9000e-9)
    # the same fusion adds up over layers and chips; time is clipped, not unioned
    assert r["ops"]["fusion f32[8,128]"] == pytest.approx(9000e-9)
    assert r["ops"]["copy bf16[4]"] == pytest.approx(1000e-9)


def test_idle_gaps_go_to_what_the_host_was_doing():
    events = {"devices": {"/device:TPU:0": {
        "ops": [["%a.1 = f32[1]{0} a()", 1_000_000, 1_000_000]],
        "programs": [[M, 0, 1000], [M, 3_999_000, 1000]],
    }}}
    # window 1,000..4,000,000 ns; gaps: 1,000-1,000,000 and 2,000,000-4,000,000
    samples = [(10.0005, "tokenize"), (10.0025, "dispatch"), (10.0035, "dispatch")]
    r = trace.reduce(events, samples=samples, host_open_s=10.0)
    assert r["idle_gaps"]["tokenize"] == pytest.approx(999e-6)
    assert r["idle_gaps"]["dispatch"] == pytest.approx(2000e-6)


def test_short_op_name():
    line = ("%fusion.452 = (f32[312,12,256]{2,1,0:T(8,128)S(1)}, f32[312,12,256,256]"
            "{2,3,1,0:T(8,128)}) fusion(pred[312,256,256]{1,2,0} %c), kind=kOutput")
    assert trace.short_op_name(line) == "fusion f32[312,12,256] f32[312,12,256,256]"
    assert trace.short_op_name("%copy.7 = bf16[4]{0} copy(z)") == "copy bf16[4]"
    assert trace.short_op_name("custom-call") == "custom-call"


def test_a_window_needs_both_markers():
    events = _hand_made()
    events["devices"]["/device:TPU:0"]["programs"].pop()
    with pytest.raises(ValueError, match="marker"):
        trace.reduce(events)


def test_recorded_chip_trace():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        fx = json.load(f)
    r = trace.reduce(
        fx["events"], samples=[tuple(s) for s in fx["samples"]],
        host_open_s=fx["host_open_s"],
    )
    assert r["window_s"] == pytest.approx(0.45, abs=1e-6)
    assert r["busy_s"] == pytest.approx(0.072526068, abs=1e-9)
    assert r["program_runs"]["jit__fwd_packed"] == 2
    assert r["programs"]["jit__fwd_packed"] == pytest.approx(0.072270979, abs=1e-9)
    assert trace.top(r["ops"], 1)[0][0] == "fusion f32[312,12,256] f32[312,12,256,256]"
    # every gap over 50 us is attributed: together they are the idle time
    assert sum(r["idle_gaps"].values()) == pytest.approx(0.45 - r["busy_s"], abs=2e-3)
    assert max(r["idle_gaps"], key=r["idle_gaps"].get).startswith("pw-server:")
