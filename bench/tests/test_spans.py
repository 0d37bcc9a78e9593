"""The reduction of the program's span records to layer self times
(`spans.py`): on synthetic records with known answers, and on the spans of
a small search run here."""

from types import SimpleNamespace as NS

import pytest

import spans


def rec(id, name, start, end, parent=None):
    return NS(id=id, name=name, start_ns=start, end_ns=end, parent=parent)


def test_self_time_nesting_and_waits():
    records = [
        rec(0, "codesign.outer", 0, 1000),
        rec(1, "codesign.inner", 100, 700, parent=0),
        rec(2, "codesign.sample", 100, 200, parent=1),
        rec(3, "codesign.gp", 300, 600, parent=1),
        rec(4, "codesign.wait", 400, 550, parent=3),
        rec(5, "codesign.gp", 320, 380, parent=3),   # a fit inside a fit
        rec(6, "codesign.wait", 800, 850, parent=0),
    ]
    t = spans.reduce(records, 0, 1000)
    assert t.window_s == pytest.approx(1000e-9)
    assert t.self_s == {
        "codesign.outer": pytest.approx(400e-9),    # 1000 - inner's 600
        "codesign.outer_gp": 0.0,
        "codesign.inner": pytest.approx(200e-9),    # 600 - 100 - 300
        "codesign.sample": pytest.approx(100e-9),
        "codesign.gp": pytest.approx(300e-9),       # 240 + 60, wait kept
        "codesign.forward": 0.0,
    }
    assert t.wait_s == pytest.approx(200e-9)
    assert t.unattributed_s == 0.0


def test_window_clipping_and_unattributed_time():
    records = [
        rec(0, "codesign.outer", -500, 300),
        rec(1, "codesign.gp", -400, 100, parent=0),
        rec(2, "codesign.wait", -100, 50, parent=1),
        rec(3, "codesign.outer", 600, 1500),
        rec(4, "codesign.forward", 900, 1200, parent=3),
        rec(5, "bench.other", 0, 1000),
    ]
    t = spans.reduce(records, 0, 1000)
    assert t.self_s["codesign.outer"] == pytest.approx((200 + 300) * 1e-9)
    assert t.self_s["codesign.gp"] == pytest.approx(100e-9)
    assert t.self_s["codesign.forward"] == pytest.approx(100e-9)
    assert t.wait_s == pytest.approx(50e-9)
    assert t.unattributed_s == pytest.approx(300e-9)   # [300, 600)
    assert sum(t.self_s.values()) + t.unattributed_s == pytest.approx(
        t.window_s)
    assert t.share(t.unattributed_s) == pytest.approx(30.0)


def test_a_span_whose_parent_is_missing_counts_as_top_level():
    t = spans.reduce([rec(1, "codesign.inner", 0, 10, parent=99)], 0, 20)
    assert t.self_s["codesign.inner"] == pytest.approx(10e-9)
    assert t.unattributed_s == pytest.approx(10e-9)


def test_empty_window_is_refused():
    with pytest.raises(ValueError, match="empty window"):
        spans.reduce([], 5, 5)


def test_shares_of_a_small_search_add_up():
    """A small jax search traced here: every layer is present, and the
    layers' self times and the unattributed time make up the window."""
    import time

    from repro.core import (CodesignConfig, CodesignEngine, EngineConfig,
                            HWSearchConfig, SWSearchConfig, trace)
    from repro.timeloop import MODEL_LAYERS

    cfg = CodesignConfig(
        sw=SWSearchConfig(n_trials=14, n_warmup=6, pool_size=20),
        hw=HWSearchConfig(n_trials=3, n_warmup=2, pool_size=20, spec_k=3),
        engine=EngineConfig(backend="jax", strategy="speculative"), seed=3)
    session = CodesignEngine(cfg).session(MODEL_LAYERS["dqn"])
    trace.enable()
    try:
        lo = time.perf_counter_ns()
        while session.step():
            pass
        hi = time.perf_counter_ns()
    finally:
        trace.disable()
    t = spans.reduce(trace.spans(), lo, hi)
    assert all(v > 0 for v in t.self_s.values()), t.self_s
    assert 0 < t.wait_s < t.window_s
    assert sum(t.self_s.values()) + t.unattributed_s == pytest.approx(
        t.window_s)
    assert t.unattributed_s < 0.01 * t.window_s
