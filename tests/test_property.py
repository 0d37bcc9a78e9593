"""Property-based tests (hypothesis) over the system's invariants."""

import numpy as np

from hypothesis_support import given, settings, st

from repro.timeloop import HardwareConfig, PAPER_WORKLOADS, evaluate, eyeriss_168
from repro.timeloop.arch import hw_is_valid, sample_hardware
from repro.timeloop.mapping import (LEVELS, constrained_random_mapping,
                                    mapping_is_valid, random_mapping)
from repro.timeloop.workloads import DIMS, divisors, factorize
from repro.kernels.tiled_matmul import block_is_valid, vmem_bytes


@given(st.integers(1, 10_000))
@settings(max_examples=60, deadline=None)
def test_divisors_correct(n):
    ds = divisors(n)
    assert list(ds) == sorted(set(ds))
    assert all(n % d == 0 for d in ds)
    assert 1 in ds and n in ds
    # divisor count cross-check via factorization
    count = 1
    for p in set(factorize(n)):
        count *= factorize(n).count(p) + 1
    assert len(ds) == count


@given(st.integers(0, 2**32 - 1), st.sampled_from(sorted(PAPER_WORKLOADS)))
@settings(max_examples=40, deadline=None)
def test_mapping_factorization_invariant(seed, layer_name):
    """Every sampled mapping factorizes each dim exactly (S1-S6 product rule),
    for both the naive and the constraint-aware sampler."""
    layer = PAPER_WORKLOADS[layer_name]
    hw = eyeriss_168()
    rng = np.random.default_rng(seed)
    for sampler in (random_mapping, constrained_random_mapping):
        m = sampler(rng, hw, layer)
        for di, d in enumerate(DIMS):
            prod = 1
            for li in range(len(LEVELS)):
                prod *= m.factors[li][di]
            assert prod == layer.dim(d)
        assert sorted(m.order_lb) == sorted(DIMS)


@given(st.integers(0, 2**32 - 1))
@settings(max_examples=30, deadline=None)
def test_valid_mapping_has_finite_positive_edp(seed):
    layer = PAPER_WORKLOADS["DQN-K2"]
    hw = eyeriss_168()
    rng = np.random.default_rng(seed)
    m = constrained_random_mapping(rng, hw, layer)
    ok, _ = mapping_is_valid(m, hw, layer)
    ev = evaluate(hw, m, layer)
    assert ev.valid == ok
    if ok:
        assert np.isfinite(ev.edp) and ev.edp > 0
        assert ev.breakdown["used_pes"] <= hw.num_pes
        # energy >= pure compute energy; delay >= perfectly parallel compute
        assert ev.energy_pj >= layer.macs * hw.energy.mac
        assert ev.delay_cycles >= layer.macs / hw.num_pes


@given(st.integers(0, 2**32 - 1))
@settings(max_examples=30, deadline=None)
def test_sampled_hardware_structural_invariants(seed):
    rng = np.random.default_rng(seed)
    hw = sample_hardware(rng, num_pes=168)
    assert hw.pe_mesh_x * hw.pe_mesh_y == 168
    assert hw.gb_mesh_x * hw.gb_mesh_y == hw.gb_instances
    ok, why = hw_is_valid(hw)
    if ok:
        assert hw.lb_input + hw.lb_weight + hw.lb_output <= hw.lb_budget


@given(st.integers(0, 2**32 - 1), st.sampled_from(sorted(PAPER_WORKLOADS)))
@settings(max_examples=15, deadline=None)
def test_batched_engine_matches_scalar(seed, layer_name):
    """The packed-array engine agrees with the scalar reference on random
    (possibly invalid) mappings: validity bit and EDP to 1e-9 relative."""
    from repro.timeloop import batch as tlb

    layer = PAPER_WORKLOADS[layer_name]
    hw = eyeriss_168()
    rng = np.random.default_rng(seed)
    ms = [random_mapping(rng, hw, layer) for _ in range(8)]
    ev = tlb.evaluate_batch(hw, tlb.pack(ms), layer)
    for i, m in enumerate(ms):
        ref = evaluate(hw, m, layer)
        assert bool(ev["valid"][i]) == ref.valid
        if ref.valid:
            assert abs(ev["edp"][i] - ref.edp) <= 1e-9 * ref.edp


@given(st.sampled_from([128, 256, 512, 1024]),
       st.sampled_from([128, 256, 512, 1024]),
       st.sampled_from([128, 256, 512]))
@settings(max_examples=30, deadline=None)
def test_kernel_block_constraints(bm, bk, bn):
    ok, why = block_is_valid(2048, 2048, 2048, bm, bk, bn)
    if ok:
        assert vmem_bytes(bm, bk, bn) <= 96 * 2**20
        assert 2048 % bm == 0 and 2048 % bk == 0 and 2048 % bn == 0


@given(st.integers(0, 1_000_000))
@settings(max_examples=20, deadline=None)
def test_data_pipeline_deterministic(step):
    from repro.configs.base import ShapeConfig, get_smoke_config
    from repro.data.pipeline import DataConfig, SyntheticSource

    cfg = get_smoke_config("smollm-360m")
    shape = ShapeConfig("t", 32, 4, "train")
    s1 = SyntheticSource(cfg, shape, DataConfig(seed=7))
    s2 = SyntheticSource(cfg, shape, DataConfig(seed=7))
    b1, b2 = s1.batch(step), s2.batch(step)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    assert np.array_equal(b1["labels"], b2["labels"])
    # labels are tokens shifted by one
    assert np.array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])

@given(st.integers(0, 2**32 - 1), st.sampled_from(sorted(PAPER_WORKLOADS)),
       st.sampled_from([168, 256]))
@settings(max_examples=40, deadline=None)
def test_edp_lower_bound_sound_and_vectorized_parity(seed, layer_name, num_pes):
    """ISSUE 6 bound-and-prune contract, randomized: on a random valid
    (hardware, mapping) pair the EDP lower bound never exceeds the true
    evaluated EDP, and the vectorized twins (NumPy batch and the jitted JAX
    dispatch) agree with the scalar reference on that same (hw, layer)."""
    from repro.timeloop.batch import edp_lower_bounds_batch
    from repro.timeloop.batch_jax import edp_lower_bounds_device
    from repro.timeloop.bounds import (hw_bound_vecs, layer_bound_vecs,
                                       layer_caps, lower_bound)

    layer = PAPER_WORKLOADS[layer_name]
    rng = np.random.default_rng(seed)
    hw = sample_hardware(rng, num_pes=num_pes)
    if not hw_is_valid(hw)[0]:
        return  # structurally invalid draw: nothing to bound
    lb = lower_bound(hw, layer)
    assert np.isfinite(lb) and lb > 0
    # both vectorized backends reproduce the scalar bound
    vec = edp_lower_bounds_batch(hw_bound_vecs([hw]), layer_bound_vecs([layer]),
                                 layer_caps([layer]))[0, 0]
    dev = edp_lower_bounds_device([hw], [layer])[0, 0]
    assert abs(vec - lb) <= 1e-12 * lb
    assert abs(dev - lb) <= 1e-9 * lb
    # soundness against the scalar evaluator on a random valid mapping
    m = constrained_random_mapping(rng, hw, layer)
    if mapping_is_valid(m, hw, layer)[0]:
        ev = evaluate(hw, m, layer)
        assert lb <= ev.edp * (1 + 1e-12)
