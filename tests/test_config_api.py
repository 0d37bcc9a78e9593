"""The typed config API: `CodesignConfig` construction/validation/JSON
round-trip, "auto" resolved from the backend, the benchmark configurations
loading through `CodesignConfig.from_dict`, and the warmup fan-out's exact
reproduction of the sequential outer-loop warmup (same seeds -> same probes,
same EDPs, same histories).

Budgets stay inside the stacked GP's Cholesky regime (see
tests/test_layer_batch.py), where both probe paths are bit-identical.
"""

import json
import pathlib

import numpy as np
import pytest

from repro.core import (CodesignConfig, CodesignEngine, EngineConfig,
                        HWSearchConfig, SWSearchConfig, bo_maximize,
                        bo_maximize_many, optimize_software,
                        optimize_software_fanout)
from repro.core.swspace import SoftwareSpace
from repro.timeloop import MODEL_LAYERS, eyeriss_168
from repro.timeloop import batch as tlb
from repro.timeloop import batch_jax as jtlb
from repro.timeloop.arch import sample_hardware_pool


BENCH_CONFIGS = sorted(
    (pathlib.Path(__file__).resolve().parent.parent / "bench" / "configs")
    .glob("*.json"))


def small_config(strategy="auto", backend=None, spec_k=4, **top):
    # 3 warmup probes (the fan-out) + 1 scored trial (the per-probe path).
    return CodesignConfig(
        sw=SWSearchConfig(n_trials=12, n_warmup=6, pool_size=20),
        hw=HWSearchConfig(n_trials=4, n_warmup=3, pool_size=20,
                          spec_k=spec_k),
        engine=EngineConfig(backend=backend, strategy=strategy),
        **top)


# --- construction + serialization -----------------------------------------------


def test_json_round_trip():
    cfg = CodesignConfig(
        sw=SWSearchConfig(n_trials=42, acquisition="ei", lam=0.5),
        hw=HWSearchConfig(n_trials=7, num_pes=256, surrogate="gp_se"),
        engine=EngineConfig(backend="jax", strategy="speculative",
                            gp_refit_every=3, pallas_mode="interpret"),
        seed=11, verbose=True)
    d = json.loads(json.dumps(cfg.to_dict()))  # through real JSON
    assert CodesignConfig.from_dict(d) == cfg
    assert CodesignConfig.from_json(cfg.to_json()) == cfg


def test_from_dict_partial_and_defaults():
    cfg = CodesignConfig.from_dict({"sw": {"n_trials": 9}, "seed": 4})
    assert cfg.sw.n_trials == 9 and cfg.sw.n_warmup == 30
    assert cfg.hw == HWSearchConfig() and cfg.seed == 4
    assert CodesignConfig.from_dict({}) == CodesignConfig()


@pytest.mark.parametrize("bad", [
    lambda: SWSearchConfig(acquisition="ucb"),
    lambda: SWSearchConfig(surrogate="mlp"),
    lambda: SWSearchConfig(n_trials=0),
    lambda: HWSearchConfig(num_pes=-1),
    lambda: EngineConfig(backend="torch"),
    lambda: EngineConfig(strategy="fanout"),
    lambda: EngineConfig(strategy="layer_batched"),  # folded into speculative
    lambda: EngineConfig(strategy="probe_fanout"),   # speculative, spec_k=1
    lambda: EngineConfig(pallas_mode="triton"),
    lambda: EngineConfig(gp_refit_every=0),
    lambda: EngineConfig(strategy="speculative", use_cache=False),
    lambda: CodesignConfig.from_dict({"sw": {"n_trial": 5}}),  # typo'd field
    lambda: CodesignConfig(sw=HWSearchConfig()),  # wrong section type
])
def test_bad_values_raise_at_construction(bad):
    """Every enumerated string / bound is validated at config construction
    (the one ValueError site), not at some deep call site."""
    with pytest.raises(ValueError):
        bad()


def test_space_validation_shares_the_choice_site():
    with pytest.raises(ValueError):
        SoftwareSpace(eyeriss_168(), MODEL_LAYERS["dqn"][0], backend="torch")
    with pytest.raises(ValueError):
        SoftwareSpace(eyeriss_168(), MODEL_LAYERS["dqn"][0],
                      pallas_mode="triton")


def _assert_codesign_parity(a, b):
    assert a.best_hw == b.best_hw
    assert a.best_model_edp == b.best_model_edp
    assert a.best_mappings == b.best_mappings
    assert np.array_equal(a.hw_result.history, b.hw_result.history)
    assert a.hw_result.n_infeasible == b.hw_result.n_infeasible


# --- probe fan-out --------------------------------------------------------------


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_probe_fanout_matches_sequential_warmup(backend):
    """The H*L*B stacked warmup fan-out (`spec_k=1`: no speculation past the
    warmup) reproduces the sequential outer-loop warmup exactly: same probes,
    same per-probe EDPs, same histories."""
    layers = MODEL_LAYERS["dqn"]
    results = {}
    for strategy in ("sequential", "speculative"):
        eng = CodesignEngine(small_config(strategy=strategy, backend=backend,
                                          spec_k=1))
        results[strategy] = eng.run(layers)
        assert eng.strategy_name == strategy
    _assert_codesign_parity(results["speculative"], results["sequential"])
    assert results["speculative"].stats["spec_evaluated"] == 0


def test_probe_fanout_prefills_cache_for_all_warmup_probes(monkeypatch):
    """After the warmup round every (probe, layer) pair the fan-out searched
    is a cache hit -- eval_hw never re-runs an inner search for them."""
    layers = MODEL_LAYERS["mlp"]
    eng = CodesignEngine(small_config(strategy="speculative", spec_k=1))
    seen = []
    orig = CodesignEngine.evaluate_probe

    def spying(self, hw, seed):
        before = set(self.cache)
        orig(self, hw, seed)
        seen.append(set(self.cache) - before)

    monkeypatch.setattr(CodesignEngine, "evaluate_probe", spying)
    eng.run(layers)
    n_warm = eng.config.hw.n_warmup
    assert len(seen) == eng.config.hw.n_trials
    assert all(not new for new in seen[:n_warm])  # warmup: all cache hits


@pytest.mark.parametrize("backend,expected", [("numpy", "sequential"),
                                              ("jax", "speculative")])
def test_auto_strategy_resolves_per_backend(backend, expected):
    """"auto" is the choice made from what the engine observes: the stacked
    speculative path on jax, the per-layer sequential path on numpy, and
    sequential wherever the cache the fan-outs fill is off."""
    assert EngineConfig(backend=backend).resolve_strategy() == expected
    assert EngineConfig(backend=backend,
                        use_cache=False).resolve_strategy() == "sequential"
    assert CodesignEngine(small_config(backend=backend)).strategy_name == expected


@pytest.mark.parametrize("path", BENCH_CONFIGS, ids=lambda p: p.stem)
def test_bench_configs_load_on_the_speculative_path(path):
    """Every benchmark configuration's `codesign` section still loads, and
    runs the speculative path at `spec_k` 4."""
    cfg = CodesignConfig.from_dict(json.loads(path.read_text())["codesign"])
    assert cfg.engine.strategy == "speculative"
    assert cfg.engine.resolve_strategy() == "speculative"
    assert cfg.hw.spec_k == 4


def test_optimize_software_fanout_matches_per_probe():
    """`optimize_software_fanout` over (hw, layer) items spanning different
    hardware probes reproduces the per-probe `optimize_software` runs."""
    rng = np.random.default_rng(0)
    hws = sample_hardware_pool(rng, 2, num_pes=168)
    layers = MODEL_LAYERS["dqn"]
    cfg = SWSearchConfig(n_trials=12, n_warmup=6, pool_size=20)
    items = [(hw, layer) for hw in hws for layer in layers]
    seeds = [11 + i for i, hw in enumerate(hws) for _ in layers]
    fan = optimize_software_fanout(items, cfg, seeds=seeds)
    for (hw, layer), s, r in zip(items, seeds, fan):
        ref = optimize_software(hw, layer, cfg, seed=s)
        assert r.best_point == ref.best_point
        assert np.array_equal(r.history, ref.history)


def test_forward_device_stacked_per_probe_hw():
    """The stacked fused program with per-run hardware vectors computes per
    row exactly what per-(hw, layer) forward_device calls compute."""
    rng = np.random.default_rng(1)
    hws = sample_hardware_pool(rng, 3, num_pes=168)
    layers = [MODEL_LAYERS["dqn"][0], MODEL_LAYERS["resnet"][1],
              MODEL_LAYERS["mlp"][0]]
    pools = [tlb.sample_valid_pool(rng, hw, ly, 10)
             for hw, ly in zip(hws, layers)]
    out = jtlb.forward_device_stacked(hws, pools, layers)
    for k, (hw, p, ly) in enumerate(zip(hws, pools, layers)):
        ref = jtlb.forward_device(hw, p, ly)
        np.testing.assert_array_equal(
            np.asarray(out["valid"][k]), np.asarray(ref["valid"]))
        for key in ("edp", "utility", "features"):
            np.testing.assert_allclose(
                np.asarray(out[key][k]), np.asarray(ref[key]), rtol=1e-12)


def test_bo_maximize_many_per_run_seeds():
    """A seed sequence gives each lockstep run its own stream, matching the
    individually-seeded sequential calls; a wrong-length sequence is loud."""
    hw = eyeriss_168()
    layers = MODEL_LAYERS["dqn"]
    spaces = [SoftwareSpace(hw, ly) for ly in layers]
    cfg = SWSearchConfig(n_trials=12, n_warmup=6, pool_size=20)
    many = bo_maximize_many(spaces, cfg, seed=[5, 9])
    for ly, s, r in zip(layers, (5, 9), many):
        ref = bo_maximize(SoftwareSpace(hw, ly), cfg, seed=s)
        assert r.best_point == ref.best_point
        assert np.array_equal(r.history, ref.history)
    with pytest.raises(ValueError):
        bo_maximize_many(spaces, cfg, seed=[1, 2, 3])


# --- config-vs-kwarg equivalence of the mid-level entry points ------------------


def test_optimize_software_config_equals_kwargs():
    hw = eyeriss_168()
    layer = MODEL_LAYERS["dqn"][1]
    cfg = SWSearchConfig(n_trials=14, n_warmup=6, pool_size=20,
                         acquisition="ei")
    a = optimize_software(hw, layer, cfg, seed=2)
    b = optimize_software(hw, layer, n_trials=14, n_warmup=6, pool_size=20,
                          acquisition="ei", seed=2)
    assert a.best_point == b.best_point and np.array_equal(a.history, b.history)
    with pytest.raises(TypeError):
        optimize_software(hw, layer, pool=20)  # unknown override is loud


def test_positional_legacy_callers_break_loudly():
    """Pre-config POSITIONAL callers (optimize_software(hw, layer, 100),
    bo_maximize(space, 100)) bind to the new config parameter; they get a
    descriptive TypeError at the entry point, not a deep AttributeError."""
    hw = eyeriss_168()
    layer = MODEL_LAYERS["dqn"][0]
    with pytest.raises(TypeError, match="SearchConfig"):
        optimize_software(hw, layer, 100)
    with pytest.raises(TypeError, match="SearchConfig"):
        bo_maximize(SoftwareSpace(hw, layer), 100)


def test_bo_maximize_config_equals_kwargs():
    hw = eyeriss_168()
    space = SoftwareSpace(hw, MODEL_LAYERS["mlp"][0])
    cfg = SWSearchConfig(n_trials=14, n_warmup=6, pool_size=20)
    a = bo_maximize(space, cfg, seed=1)
    b = bo_maximize(SoftwareSpace(hw, MODEL_LAYERS["mlp"][0]),
                    n_trials=14, n_warmup=6, pool_size=20, seed=1)
    assert a.best_point == b.best_point and np.array_equal(a.history, b.history)
