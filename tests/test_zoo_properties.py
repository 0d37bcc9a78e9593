"""Hypothesis properties for the zoo generator and the sampler divisor
guard."""

from hypothesis_support import given, settings, st

from repro.timeloop import SAMPLER_DIVISOR_CAP, divisors, sampler_divisors
from repro.timeloop.workloads import _TOKENS
from repro.workloads import ZOO_NAMES, zoo_workload


@given(st.integers(1, 10_000_000))
@settings(max_examples=200, deadline=None)
def test_sampler_divisors_invariants(n):
    """The sampler ladder is always a sorted, capped, 1-and-n-containing
    subset of the true divisors -- and exactly the divisors below the cap."""
    full = divisors(n)
    ladder = sampler_divisors(n)
    assert list(ladder) == sorted(set(ladder))
    assert set(ladder) <= set(full)
    assert ladder[0] == 1 and ladder[-1] == n
    assert all(n % f == 0 for f in ladder)
    if len(full) <= SAMPLER_DIVISOR_CAP:
        assert ladder == full
    else:
        assert len(ladder) <= SAMPLER_DIVISOR_CAP


@given(st.sampled_from(ZOO_NAMES))
@settings(max_examples=20, deadline=None)
def test_zoo_layer_invariants(name):
    """Stride/extent/divisor sanity for every generated layer: positive dims,
    stride 1, consistent MACs, halo extent >= output extent, and a sampler
    ladder that is never capped (zoo dims sit under SAMPLER_DIVISOR_CAP)."""
    zw = zoo_workload(name)
    assert sum(c * l.macs for c, l in zip(zw.counts, zw.layers)) \
        == zw.total_macs
    for layer in zw.layers:
        dims = [layer.dim(d) for d in ("R", "S", "P", "Q", "C", "K")]
        assert all(d >= 1 for d in dims)
        assert layer.stride == 1
        r, s, p, q, c, k = (layer.R, layer.S, layer.P, layer.Q, layer.C,
                            layer.K)
        assert layer.macs == r * s * p * q * c * k
        assert layer.input_extent(p, r) == (p - 1) * layer.stride + r >= p
        assert layer.input_extent(q, s) >= q
        assert layer.input_size \
            == layer.input_extent(p, r) * layer.input_extent(q, s) * c
        assert layer.weight_size == r * s * c * k
        assert layer.output_size == p * q * k
        assert p <= _TOKENS
        for d in dims:
            assert sampler_divisors(d) == divisors(d)  # under the cap: exact
        assert layer.divisors("K") == list(divisors(k))
