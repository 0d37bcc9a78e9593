"""The reduction from a profiler trace to device busy time, program time and
idle time by host span: on a synthetic trace with known answers, and on a
small trace recorded on a TPU v5e (`record_trace.py`)."""

import os
from types import SimpleNamespace as NS

import pytest

import xplane
from conftest import HERE

RECORDED = os.path.join(HERE, "data", "small.xplane.pb")


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def profile(device_lines, host_events):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=host_events)])
    dev = NS(name="/device:TPU:0",
             lines=[NS(name=n, events=e) for n, e in device_lines.items()])
    return NS(planes=[host, dev])


def test_synthetic_trace():
    p = profile(
        {"XLA Modules": [ev("jit__fit_stack(1)", 100, 400),
                         ev("jit__fit_stack(7)", 120, 300),
                         ev("jit__forward(2)", 500, 700),
                         ev("jit_early(3)", 0, 40)],
         "XLA Ops": [ev("not read", 0, 1000)]},
        [ev("bench.window", 50, 1050), ev("bench.search", 60, 1040),
         ev("bench.step.warmup", 60, 450), ev("bench.step.trial", 450, 1040),
         ev("other", 0, 2000)])
    s = xplane.summarize(p)
    # the trace covers the window span [50, 1050) up to its last program
    # run's end, 700
    assert s.span_s == pytest.approx(1000e-9)
    assert s.window_s == pytest.approx(650e-9)
    # program runs, merged and clipped to the window: [100, 400), [500, 700)
    assert s.busy_s == pytest.approx(500e-9)
    assert s.module_runs == {"jit__fit_stack(1)": 1, "jit__fit_stack(7)": 1,
                             "jit__forward(2)": 1}
    assert s.module_s == {"jit__fit_stack(1)": pytest.approx(300e-9),
                          "jit__fit_stack(7)": pytest.approx(180e-9),
                          "jit__forward(2)": pytest.approx(200e-9)}
    # idle [50, 100) and [400, 500), each named by the innermost span
    # around its midpoint
    assert s.idle_by_span == {"bench.step.warmup": pytest.approx(50e-9),
                              "bench.step.trial": pytest.approx(100e-9)}
    b = xplane.breakdown(s)
    assert b["device_ops"] == [["jit__fit_stack", pytest.approx(480e-9)],
                               ["jit__forward", pytest.approx(200e-9)]]
    assert b["idle_gaps"][0][0] == "bench.step.trial"


def test_window_span_must_be_unique():
    p = profile({"XLA Ops": []}, [ev("bench.window", 0, 10),
                                  ev("bench.window", 20, 30)])
    with pytest.raises(ValueError, match="window"):
        xplane.summarize(p)


def test_recorded_tpu_trace():
    s = xplane.summarize(xplane.load(RECORDED))
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s <= s.span_s
    names = " ".join(s.module_s)
    for program in ("jit__forward", "jit__fit_stack", "jit__score_stack"):
        assert program in names
    assert {"bench.step.warmup", "bench.step.trial"} <= set(s.idle_by_span)
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)

