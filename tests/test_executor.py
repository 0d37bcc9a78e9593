"""Executor layer (ISSUE 8): process fan-out of stacked inner searches.

The load-bearing claims:

  * worker-count invariance -- `strategy="speculative"` under
    `ExecutorConfig(kind="process")` is bit-identical to inline/sequential
    on all four golden workloads, for n_workers in {1, 2, 4} (content-derived
    probe seeds make placement a free variable);
  * chunking invariance -- splitting one stacked dispatch into per-worker
    chunks only regroups which runs share a stacked fit, so entries match
    the unsplit dispatch exactly;
  * spawn hygiene -- a fresh worker boots without jax (the fork-inheritance
    regression surface), and a numpy-backend search inside a worker never
    imports the jax evaluation engine nor flips the global x64 flag;
  * worker failures re-raise in the learner with the worker traceback.

n_workers=2 runs in the PR-CI tier ("not slow"); the 1- and 4-worker sweeps
are slow-marked like the other full parity suites.
"""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

from repro.core import (CodesignConfig, CodesignEngine, EngineConfig,
                        ExecutorConfig, FanoutSearchSpec, HWSearchConfig,
                        ServiceConfig, SWSearchConfig)
from repro.parallel.executor import (InlineExecutor, ProcessExecutor,
                                     _chunk_spec, make_executor)
from repro.timeloop import MODEL_LAYERS, eyeriss_168
from test_golden import GOLDEN_PATH, MODELS, _canonical

# --- config plumbing --------------------------------------------------------------


def test_executor_config_validation():
    assert ExecutorConfig() == ExecutorConfig(kind="inline", n_workers=0,
                                              chunk_items=0)
    assert ExecutorConfig().resolve_workers() >= 1
    assert ExecutorConfig(n_workers=3).resolve_workers() == 3
    with pytest.raises(ValueError, match="kind"):
        ExecutorConfig(kind="threads")
    with pytest.raises(ValueError, match="n_workers"):
        ExecutorConfig(n_workers=-1)
    with pytest.raises(ValueError, match="n_workers"):
        ExecutorConfig(n_workers=True)
    with pytest.raises(ValueError, match="chunk_items"):
        ExecutorConfig(chunk_items=-2)


def test_executor_config_json_roundtrip():
    """The executor section rides the existing config JSON surfaces: dicts
    coerce to ExecutorConfig on the way in, round-trip equality holds."""
    eng = EngineConfig(backend="numpy",
                       executor=ExecutorConfig(kind="process", n_workers=2))
    cfg = CodesignConfig(engine=eng)
    assert CodesignConfig.from_json(cfg.to_json()) == cfg
    # plain-dict executor section (the JSON queue path) coerces + validates
    assert EngineConfig(backend="numpy",
                        executor={"kind": "process"}).executor == \
        ExecutorConfig(kind="process")
    with pytest.raises(ValueError, match="executor"):
        EngineConfig(executor={"kind": "process", "bogus": 1})
    with pytest.raises(ValueError, match="executor"):
        EngineConfig(executor=7)
    sc = ServiceConfig(executor=ExecutorConfig(kind="process", n_workers=4))
    assert ServiceConfig.from_dict(sc.to_dict()) == sc


def test_make_executor_kinds():
    assert isinstance(make_executor(), InlineExecutor)
    assert isinstance(make_executor(ExecutorConfig(kind="inline")),
                      InlineExecutor)
    ex = make_executor(ExecutorConfig(kind="process", n_workers=3))
    try:
        assert isinstance(ex, ProcessExecutor)
        assert ex.n_workers == 3  # no processes started until first submit
    finally:
        ex.close()


# --- spec + chunking --------------------------------------------------------------


def _tiny_spec(n_items: int = 3, sw=None) -> FanoutSearchSpec:
    hw = eyeriss_168()
    layers = (list(MODEL_LAYERS["dqn"]) * n_items)[:n_items]
    items = tuple((hw, layer) for layer in layers)
    cfg = CodesignConfig(engine=EngineConfig(backend="numpy"))
    engine = CodesignEngine(cfg)
    seeds = tuple(engine.probe_seed(hw) + i for i in range(n_items))
    return FanoutSearchSpec(
        items=items, seeds=seeds,
        sw=sw or SWSearchConfig(n_trials=6, n_warmup=3, pool_size=10),
        engine=cfg.engine)


def test_chunk_spec_partitions_in_item_order():
    spec = _tiny_spec(5)
    assert _chunk_spec(spec, n_workers=1, chunk_items=0) == [spec]
    chunks = _chunk_spec(spec, n_workers=2, chunk_items=0)
    assert [len(c.items) for c in chunks] == [3, 2]
    chunks = _chunk_spec(spec, n_workers=4, chunk_items=1)
    assert [len(c.items) for c in chunks] == [1] * 5
    # concatenating chunk items/seeds reproduces the original order exactly
    assert sum((list(c.items) for c in chunks), []) == list(spec.items)
    assert sum((list(c.seeds) for c in chunks), []) == list(spec.seeds)
    # chunks drop the bucketing pad (it only helps a whole stack)
    padded = dataclasses.replace(spec, pad_to=6)
    assert _chunk_spec(padded, 1, 0) == [padded]
    assert all(c.pad_to is None for c in _chunk_spec(padded, 2, 2))


def test_process_entries_match_inline_across_chunkings():
    """The same spec returns identical entries inline, split evenly across
    two workers, and split down to one item per chunk."""
    spec = _tiny_spec(4)
    want = InlineExecutor().run(spec)
    for chunk_items in (0, 1):
        ex = ProcessExecutor(n_workers=2, chunk_items=chunk_items)
        try:
            assert ex.run(spec) == want, f"chunk_items={chunk_items}"
        finally:
            ex.close()


def test_worker_error_propagates_with_traceback():
    bad = dataclasses.replace(_tiny_spec(3), seeds=(0,))  # len mismatch
    ex = ProcessExecutor(n_workers=1)
    try:
        with pytest.raises(RuntimeError, match="worker traceback"):
            ex.run(bad)
        # the pool survives a failed task and keeps serving
        assert ex.run(_tiny_spec(2)) == InlineExecutor().run(_tiny_spec(2))
    finally:
        ex.close()


def test_process_executor_refuses_jax_backend(monkeypatch):
    """Workers are pinned to the host CPU, so a jax-backend search there
    would be a hidden CPU fallback for the accelerator path: the config and
    the executor both refuse it."""
    with pytest.raises(ValueError, match="process"):
        EngineConfig(backend="jax", executor=ExecutorConfig(kind="process"))
    monkeypatch.setenv("REPRO_BACKEND", "jax")  # resolved, not just explicit
    with pytest.raises(ValueError, match="process"):
        EngineConfig(executor={"kind": "process"})
    monkeypatch.delenv("REPRO_BACKEND")
    spec = dataclasses.replace(_tiny_spec(1), engine=EngineConfig(backend="jax"))
    ex = ProcessExecutor(n_workers=1)
    try:
        with pytest.raises(ValueError, match="numpy-backend"):
            ex.submit("j", spec)
        assert not ex._procs  # refused before any worker started
    finally:
        ex.close()


def test_spawned_worker_runs_jax_on_cpu(monkeypatch):
    """Workers never reach for the accelerator, even when the parent leaves
    the jax platform unset: a fresh worker boots pinned to the CPU, and after
    a search its initialized jax backend is the CPU."""
    import os

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    ex = ProcessExecutor(n_workers=1)
    try:
        assert ex.probe()["jax_platform"] == "cpu"
        ex.run(_tiny_spec(1))
        assert ex.probe()["jax_platform"] == "cpu"
    finally:
        ex.close()
    assert "JAX_PLATFORMS" not in os.environ  # the parent's env is restored


# --- spawn hygiene (the no-jax satellite) -----------------------------------------


def test_spawned_worker_is_jax_free_and_numpy_path_stays_clean():
    """Regression pin for worker state hygiene: a freshly spawned worker must
    not inherit the parent's jax runtime (fork would copy it wholesale), and
    running a numpy-backend search inside the worker must neither import the
    jax evaluation-engine modules nor flip the process-global x64 flag."""
    import jax  # the parent process HAS jax loaded -- that is the hazard

    assert jax is not None
    ex = ProcessExecutor(n_workers=1)
    try:
        fresh = ex.probe()
        assert fresh["inherited_jax"] == []
        assert fresh["jax_modules"] == []  # no jax at boot, period
        assert fresh["engine_modules"] == []
        assert fresh["x64_enabled"] is False

        ex.run(_tiny_spec(2))  # numpy-backend search in the same worker
        after = ex.probe()
        assert after["inherited_jax"] == []
        # The GP/BO surrogate layer is jax-based on every backend, so jax
        # itself is now loaded -- but the numpy path must not have pulled in
        # the jax evaluation engine or mutated global x64 state.
        assert after["engine_modules"] == []
        assert after["x64_enabled"] is False
    finally:
        ex.close()

    # The fork tripwire itself: a worker that *did* inherit jax modules
    # (only possible fork-started -- spawn re-imports workers.py in-process,
    # so its PID sentinel marks boot-time jax as fresh) refuses to search.
    from repro.parallel import workers
    with pytest.raises(RuntimeError, match="fork-started"):
        workers._run_search(_tiny_spec(1), inherited_jax=["jax"])


# --- golden worker-count invariance -----------------------------------------------


def _golden_config(model: str, n_workers: int) -> CodesignConfig:
    """test_golden's exact budgets, with the speculative strategy routed
    through a process executor (the acceptance-criteria configuration)."""
    return CodesignConfig(
        sw=SWSearchConfig(n_trials=10, n_warmup=5, pool_size=15),
        hw=HWSearchConfig(n_trials=3, n_warmup=2, pool_size=12,
                          num_pes=256 if model == "transformer" else 168),
        engine=EngineConfig(backend="numpy", strategy="speculative",
                            executor=ExecutorConfig(kind="process",
                                                    n_workers=n_workers)),
        seed=0,
    )


@pytest.fixture(scope="module")
def worker_pool():
    """One shared 2-worker pool for the golden runs (spawn + import cost is
    paid once per worker, not once per test)."""
    ex = ProcessExecutor(n_workers=2)
    yield ex
    ex.close()


def _record(result) -> dict:
    return {
        "design_sha256": hashlib.sha256(
            _canonical(result).encode()).hexdigest(),
        "best_log10_edp": round(float(np.log10(result.best_model_edp)), 6),
        "n_trials": len(result.hw_result.history),
    }


@pytest.mark.e2e
@pytest.mark.parametrize("model", MODELS)
def test_process_speculative_matches_golden(model, worker_pool):
    """speculative + process executor reproduces the checked-in goldens --
    the same pins the sequential inline path is held to."""
    goldens = json.loads(GOLDEN_PATH.read_text())
    engine = CodesignEngine(_golden_config(model, n_workers=2),
                            executor=worker_pool)
    assert _record(engine.run(MODEL_LAYERS[model])) == goldens[model]


@pytest.mark.slow
@pytest.mark.e2e
@pytest.mark.parametrize("n_workers", [1, 4])
@pytest.mark.parametrize("model", MODELS)
def test_worker_count_invariance(model, n_workers):
    """n_workers in {1, 4} (2 is pinned above, inline by test_golden itself):
    every pool width reproduces the identical golden record."""
    goldens = json.loads(GOLDEN_PATH.read_text())
    engine = CodesignEngine(_golden_config(model, n_workers))
    try:
        result = engine.run(MODEL_LAYERS[model])
    finally:
        engine.close()
    assert _record(result) == goldens[model], \
        f"{model} at n_workers={n_workers}"
