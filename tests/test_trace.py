"""The in-program tracer (`repro.core.trace`): spans nest and carry their
search, the off state records nothing, counters count whether or not spans
are on, and tracing never changes what a search finds."""

import dataclasses

import numpy as np
import pytest

from repro.core import (CodesignConfig, CodesignEngine, EngineConfig,
                        HWSearchConfig, SWSearchConfig, cache, trace)
from repro.core.gp import GPClassifierStack, GPStack
from repro.timeloop import MODEL_LAYERS, eyeriss_168
from repro.timeloop import batch as tlb
from repro.timeloop import batch_jax as jtlb

LAYER_SPANS = ("codesign.outer", "codesign.outer_gp", "codesign.inner",
               "codesign.sample", "codesign.forward", "codesign.gp")
COUNTERS = ("forward.rows", "forward.slots", "gp.fits", "gp.runs", "gp.rows",
            "gp.slots", "transfer.h2d_bytes", "transfer.d2h_bytes",
            "device.dispatches")


@pytest.fixture
def tracing():
    trace.enable()
    try:
        yield
    finally:
        trace.disable()


def small_config(backend: str) -> CodesignConfig:
    """Two warm-up probes fanned out, one scored trial speculated 3 wide."""
    return CodesignConfig(
        sw=SWSearchConfig(n_trials=14, n_warmup=6, pool_size=20),
        hw=HWSearchConfig(n_trials=3, n_warmup=2, pool_size=20, spec_k=3),
        engine=EngineConfig(backend=backend, strategy="speculative"), seed=3)


def counter_diff(before: dict) -> dict:
    now = trace.counters_snapshot()
    return {k: now.get(k, 0) - before.get(k, 0) for k in now}


def test_spans_nest_with_parent_and_search(tracing):
    with trace.span("a", search=7) as a:
        with trace.span("b") as b:
            with trace.span("c", search=9):
                pass
        with trace.span("d"):
            pass
    with trace.span("e"):
        pass
    recs = {s.name: s for s in trace.spans()}
    assert [s.name for s in trace.spans()] == ["c", "b", "d", "a", "e"]
    assert recs["a"].parent is None and recs["e"].parent is None
    assert recs["b"].parent == recs["d"].parent == a.id == recs["a"].id
    assert recs["c"].parent == b.id
    # the search tag is inherited unless a span sets its own
    assert (recs["a"].search, recs["b"].search, recs["d"].search) == (7, 7, 7)
    assert recs["c"].search == 9 and recs["e"].search is None
    for s in trace.spans():
        assert s.start_ns <= s.end_ns
    assert recs["a"].start_ns <= recs["b"].start_ns <= recs["c"].start_ns
    assert recs["c"].end_ns <= recs["b"].end_ns <= recs["d"].start_ns
    assert recs["d"].end_ns <= recs["a"].end_ns <= recs["e"].start_ns


def test_off_records_nothing_and_returns_the_shared_noop():
    trace.enable()
    trace.disable()
    assert trace.span("a") is trace.span("b", search=1)
    with trace.span("a"):
        with trace.span("b"):
            pass
    assert trace.spans() == []


def test_enable_starts_afresh_and_the_record_list_is_bounded(monkeypatch):
    trace.enable()
    with trace.span("old"):
        pass
    trace.enable()
    assert trace.spans() == []
    monkeypatch.setattr(trace, "MAX_SPANS", 2)
    dropped = trace.COUNTERS["trace.dropped"]
    try:
        for name in ("x", "y", "z"):
            with trace.span(name):
                pass
    finally:
        trace.disable()
    assert [s.name for s in trace.spans()] == ["x", "y"]
    assert trace.COUNTERS["trace.dropped"] - dropped == 1


def test_counters_count_with_tracing_off():
    """Exact readings of one stacked forward and one stacked GP fit: two
    runs of 5 and 3 mapping rows padded to the bucket of 8, a third run
    sitting the round out; GP runs of 3 and 5 observations, bucket 8."""
    trace.enable()
    trace.disable()  # an empty record list, spans off
    rng = np.random.default_rng(0)
    hw, layers = eyeriss_168(), MODEL_LAYERS["dqn"]
    pools = [tlb.sample_valid_pool(rng, hw, layers[0], 5),
             tlb.sample_valid_pool(rng, hw, layers[1], 3),
             tlb.PaddingPool.of(4)]
    before = trace.counters_snapshot()
    out = jtlb.forward_device_stacked(hw, pools, [*layers, layers[0]],
                                      mode="jnp", dtype="float64")
    feats = trace.fetch(out["features"])
    got = counter_diff(before)
    L, b = 3, 8
    # f64 factors, hardware and layer vectors; int32 loop orders
    h2d = L * b * (5 * 6 * 8 + 2 * 6 * 4 + 15 * 8 + 8 * 8)
    assert (got["forward.rows"], got["forward.slots"]) == (8, L * b)
    assert got["transfer.h2d_bytes"] == h2d
    assert got["transfer.d2h_bytes"] == feats.nbytes == L * 5 * 14 * 8
    assert got["device.dispatches"] == 1

    before = trace.counters_snapshot()
    X = [rng.normal(size=(n, 14)) for n in (3, 5)]
    y = [rng.normal(size=n) for n in (3, 5)]
    GPStack(kind="linear").fit(X, y)
    got = counter_diff(before)
    assert (got["gp.rows"], got["gp.slots"]) == (8, 2 * 8)
    assert got["device.dispatches"] == 1
    # X, y and mask, each copied for the fit and for the fitted state, and
    # the per-run mean and noise
    assert got["transfer.h2d_bytes"] == 2 * 2 * 8 * (14 + 1 + 1) * 8 + 2 * 2 * 8
    assert trace.spans() == []


def test_gp_fit_counters_read_the_stack_width():
    """gp.fits counts stacked fits and gp.runs their runs, so gp.runs /
    gp.fits is the mean stack width; a classifier stack's fit is one fit
    (it runs through `GPStack.fit` once)."""
    rng = np.random.default_rng(1)
    X = [rng.normal(size=(n, 6)) for n in (4, 5, 6)]
    before = trace.counters_snapshot()
    GPStack(kind="linear").fit(X, [rng.normal(size=len(x)) for x in X])
    got = counter_diff(before)
    assert (got["gp.fits"], got["gp.runs"]) == (1, 3)
    before = trace.counters_snapshot()
    GPClassifierStack().fit(X[:2], [rng.random(len(x)) < 0.5 for x in X[:2]])
    got = counter_diff(before)
    assert (got["gp.fits"], got["gp.runs"]) == (1, 2)


@pytest.mark.parametrize("kind, rows, lowrank",
                         [("linear", 20, 1), ("linear", 37, 1),
                          ("linear", 13, 0), ("se", 20, 0)],
                         ids=["linear-bucket-24", "linear-bucket-40",
                              "linear-bucket-16", "se-bucket-24"])
def test_gp_lowrank_fits_count_the_woodbury_fits(kind, rows, lowrank):
    """gp.lowrank_fits counts the stacked fits through the Woodbury NLL: a
    linear stack padded to 24 rows or more, not one padded to 16 nor an
    SE-kernel stack; gp.fits counts every one."""
    rng = np.random.default_rng(2)
    X = [rng.normal(size=(n, 6)) for n in (rows - 4, rows)]
    before = trace.counters_snapshot()
    GPStack(kind=kind).fit(X, [rng.normal(size=len(x)) for x in X])
    got = counter_diff(before)
    assert (got["gp.fits"], got.get("gp.lowrank_fits", 0)) == (1, lowrank)


def test_stats_unchanged_by_the_counter_move():
    """The SlotCache tallies live in `trace.COUNTERS` now; a search's stats
    read as they did when `cache.py` held them (values of the tree before
    the move)."""
    assert cache.COUNTERS is trace.COUNTERS
    result = CodesignEngine(small_config("numpy")).run(MODEL_LAYERS["dqn"])
    assert result.stats == {
        "cache_evictions": 0, "cache_hits": 12, "cache_misses": 10,
        "cache_size": 10, "hw_feat_hits": 0, "hw_feat_misses": 2,
        "prior_rows": 0, "probes_gated": 0, "prune_considered": 0,
        "prune_pruned": 0, "pruned_fraction": 0.0, "spec_evaluated": 2,
        "spec_hit_rate": 0.0, "spec_hits": 0, "sw_feat_hits": 0,
        "sw_feat_misses": 81, "sw_fwd_hits": 0, "sw_fwd_misses": 0}
    assert result.best_model_edp == 210635773762.6013


def run_search(on: bool):
    """One small jax search from a fresh engine; its result, its counter
    readings and (when `on`) its spans and session id."""
    engine = CodesignEngine(small_config("jax"))
    session = engine.session(MODEL_LAYERS["dqn"])
    before = trace.counters_snapshot()
    if on:
        trace.enable()
    try:
        while session.step():
            pass
    finally:
        trace.disable()
    got = counter_diff(before)
    return (session.result(), {k: got.get(k, 0) for k in COUNTERS},
            trace.spans() if on else [], session.trace_id)


def test_tracing_leaves_a_search_bit_identical():
    off, counts_off, _, _ = run_search(on=False)
    on, counts_on, spans, search = run_search(on=True)
    assert dataclasses.astuple(on.best_hw) == dataclasses.astuple(off.best_hw)
    assert on.best_mappings == off.best_mappings
    assert on.best_model_edp == off.best_model_edp
    assert on.layer_edps == off.layer_edps
    assert on.hw_result.history == off.hw_result.history
    assert on.hw_result.points == off.hw_result.points
    assert on.stats == off.stats
    assert counts_on == counts_off
    for name in COUNTERS:
        assert counts_on[name] > 0, name
    assert counts_on["forward.rows"] < counts_on["forward.slots"]
    assert counts_on["gp.rows"] < counts_on["gp.slots"]

    names = {s.name for s in spans}
    assert names == set(LAYER_SPANS) | {"codesign.wait"}
    assert {s.search for s in spans} == {search}
    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.name == "codesign.outer":
            assert s.parent is None
        else:
            parent = by_id[s.parent]
            assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
            assert parent.name != "codesign.wait"
