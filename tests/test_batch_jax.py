"""Parity of the JAX evaluation engine (`repro.timeloop.batch_jax`) against the
NumPy engine (itself pinned to the scalar reference at 1e-9), plus the
device-resident BO scoring path.

Acceptance bar: <= 1e-6 relative on EDP/energy/delay/features, *exact* on
validity masks.  The default float64 engine actually lands ~1e-12; the float32
path is checked against the looser bar it is specified to meet.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.bo import bo_maximize
from repro.core.swspace import SoftwareSpace
from repro.timeloop import PAPER_WORKLOADS, evaluate, eyeriss_168
from repro.timeloop import batch as tlb
from repro.timeloop import batch_jax as jtlb
from repro.timeloop.arch import hw_is_valid, sample_hardware
from repro.timeloop.mapping import constrained_random_mapping, random_mapping

RTOL = 1e-6
KEYS = ("energy_pj", "delay_cycles", "edp")
ALL_LAYERS = sorted(PAPER_WORKLOADS)  # every seed workload


def _random_pool(hw, layer, n=120, seed=0):
    """Half naive draws (exercises invalid rows), half constraint-aware."""
    rng = np.random.default_rng(seed)
    ms = [random_mapping(rng, hw, layer) for _ in range(n // 2)]
    ms += [constrained_random_mapping(rng, hw, layer) for _ in range(n - n // 2)]
    return tlb.pack(ms)


def _assert_parity(hw, layer, mb, rtol=RTOL, **kw):
    ref = tlb.evaluate_batch(hw, mb, layer)
    out = jtlb.evaluate_batch(hw, mb, layer, **kw)
    np.testing.assert_array_equal(out["valid"], ref["valid"])  # exact masks
    v = ref["valid"]
    for key in KEYS:
        assert np.isinf(out[key][~v]).all()
        np.testing.assert_allclose(out[key][v], ref[key][v], rtol=rtol)
    feats_ref = tlb.features_batch(mb, hw, layer)
    feats = jtlb.features_batch(mb, hw, layer, **kw)
    np.testing.assert_allclose(feats, feats_ref, rtol=rtol, atol=1e-12)
    return int(v.sum())


@pytest.mark.parametrize("name", ALL_LAYERS)
def test_jax_engine_parity_all_seed_workloads(name):
    layer = PAPER_WORKLOADS[name]
    hw = eyeriss_168()
    n_valid = _assert_parity(hw, layer, _random_pool(hw, layer))
    assert n_valid > 5  # the comparison exercised real valid rows


def test_jax_engine_parity_float32():
    """The accelerator dtype meets the 1e-6 bar too; masks stay exact (every
    quantity entering a validity comparison is < 2^24)."""
    layer = PAPER_WORKLOADS["ResNet-K2"]
    hw = eyeriss_168()
    _assert_parity(hw, layer, _random_pool(hw, layer), dtype="float32")


def test_jax_engine_parity_on_random_hardware():
    """Hardware enters the jitted program as an array, so one compile serves
    every config -- check parity across sampled configs (incl. dataflow pins)."""
    layer = PAPER_WORKLOADS["DQN-K1"]
    rng = np.random.default_rng(7)
    checked = 0
    while checked < 4:
        hw = sample_hardware(rng, num_pes=168)
        if not hw_is_valid(hw)[0]:
            continue
        _assert_parity(hw, layer, _random_pool(hw, layer, n=60, seed=checked))
        checked += 1


def test_jax_engine_parity_pinned_dataflow():
    layer = PAPER_WORKLOADS["DQN-K1"]
    hw = dataclasses.replace(eyeriss_168(), df_fw=2, df_fh=2)
    base = eyeriss_168()
    rng = np.random.default_rng(3)
    ms = [random_mapping(rng, base, layer) for _ in range(60)]
    ms += [constrained_random_mapping(rng, hw, layer) for _ in range(60)]
    _assert_parity(hw, layer, tlb.pack(ms))


def test_pallas_interpret_mode_matches_jnp():
    """The Pallas kernel body (run through the interpreter on CPU) computes
    exactly what the plain-jnp fallback computes."""
    hw = eyeriss_168()
    for name in ("ResNet-K4", "Transformer-K2"):
        layer = PAPER_WORKLOADS[name]
        mb = _random_pool(hw, layer, n=48, seed=11)
        ref = jtlb.evaluate_batch(hw, mb, layer, mode="jnp")
        out = jtlb.evaluate_batch(hw, mb, layer, mode="interpret")
        np.testing.assert_array_equal(out["valid"], ref["valid"])
        v = ref["valid"]
        for key in KEYS:
            np.testing.assert_allclose(out[key][v], ref[key][v], rtol=1e-12)
        np.testing.assert_allclose(
            jtlb.features_batch(mb, hw, layer, mode="interpret"),
            jtlb.features_batch(mb, hw, layer, mode="jnp"),
            rtol=1e-12,
        )


@pytest.mark.parametrize("rows", [48, 1024, 2600])
def test_edp_reduce_kernel_matches_reference(rows):
    """The lane-major kernel (through the interpreter) against its `jnp`
    reference, across one partial lane block, one full grid block, and a
    padded multi-block grid."""
    import jax.numpy as jnp

    from repro.kernels.edp_reduce import edp_reduce, reduce_edp_terms

    rng = np.random.default_rng(rows)
    ops = (rng.integers(1, 9, (rows, 2, 6)),
           rng.integers(0, 2, (rows, 2, 3, 6)),
           rng.integers(1, 5000, (rows, 2, 3)),
           rng.integers(1, 169, (rows, 6)),
           rng.uniform(0.5, 200.0, (rows, 7)))
    with jax.enable_x64(True):
        ops = [jnp.asarray(o, jnp.float64) for o in ops]
        want = reduce_edp_terms(*ops)
        got = edp_reduce(*ops, interpret=True)
        for g, w in zip(got, want):
            assert g.shape == w.shape
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-12)


def test_valid_batch_and_scalar_oracle():
    layer = PAPER_WORKLOADS["MLP-K2"]
    hw = eyeriss_168()
    mb = _random_pool(hw, layer, n=80, seed=5)
    ok = jtlb.valid_batch(mb, hw, layer)
    from repro.timeloop.mapping import mapping_is_valid

    for i in range(len(mb)):
        assert bool(ok[i]) == mapping_is_valid(mb[i], hw, layer)[0]


def test_forward_device_returns_device_arrays():
    import jax

    hw = eyeriss_168()
    layer = PAPER_WORKLOADS["DQN-K2"]
    space = SoftwareSpace(hw, layer, backend="jax")
    pool = space.sample_pool(np.random.default_rng(0), 20)
    feats = space.features_batch_device(pool)
    assert isinstance(feats, jax.Array)
    assert feats.shape == (20, space.feature_dim)
    np.testing.assert_allclose(
        np.asarray(feats), space.features_batch(pool), rtol=1e-12)


def test_bo_jax_backend_matches_numpy_backend_choices():
    """With the f64 engine, features are bitwise-identical to NumPy's, so the
    whole BO trajectory (device-resident scoring included) picks the same
    candidates and lands on the same best value."""
    hw = eyeriss_168()
    layer = PAPER_WORKLOADS["DQN-K2"]
    bests = {}
    for backend in ("numpy", "jax"):
        space = SoftwareSpace(hw, layer, backend=backend)
        r = bo_maximize(space, n_trials=30, n_warmup=12, pool_size=30, seed=0)
        assert len(r.history) == 30 and np.isfinite(r.best_value)
        bests[backend] = r.best_value
    assert bests["jax"] == pytest.approx(bests["numpy"], rel=1e-9)


def test_bo_maximize_backend_override_is_scoped():
    hw = eyeriss_168()
    layer = PAPER_WORKLOADS["DQN-K2"]
    space = SoftwareSpace(hw, layer, backend="numpy")
    seen = []
    r = bo_maximize(space, n_trials=12, n_warmup=6, pool_size=20, seed=1,
                    backend="jax",
                    callback=lambda t, res: seen.append(space.backend))
    assert np.isfinite(r.best_value)
    assert set(seen) == {"jax"}          # the run used the override...
    assert space.backend == "numpy"      # ...and the caller's space came back
    with pytest.raises(ValueError):
        bo_maximize(space, n_trials=2, backend="torch")


def test_acquisition_device_twins_match_host():
    """The jnp acquisitions must compute the same values as the host ones,
    or the device-resident scoring path would pick different candidates."""
    from repro.core.acquisition import make_acquisition, make_acquisition_device

    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    mu = rng.normal(size=50)
    var = rng.uniform(1e-8, 2.0, size=50)
    with jax.enable_x64(True):  # the real device path feeds f64 posterior arrays
        mu_d, var_d = jnp.asarray(mu), jnp.asarray(var)
    for name in ("ei", "lcb"):
        host = make_acquisition(name, lam=1.3)(mu, var, 0.4)
        dev = make_acquisition_device(name, lam=1.3)(mu_d, var_d, 0.4)
        # atol floors the deep-tail EI values (erf implementations differ in
        # the last ulps there); anything below 1e-10 never decides an argmax.
        np.testing.assert_allclose(np.asarray(dev), host, rtol=1e-7, atol=1e-10)


def test_empty_and_tiny_pools():
    hw = eyeriss_168()
    layer = PAPER_WORKLOADS["DQN-K2"]
    ev = jtlb.evaluate_batch(hw, tlb.pack([]), layer)
    assert ev["valid"].shape == (0,)
    mb = _random_pool(hw, layer, n=1, seed=0)
    ev = jtlb.evaluate_batch(hw, mb, layer)
    assert ev["valid"].shape == (1,)
