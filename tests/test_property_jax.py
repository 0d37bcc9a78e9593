"""Property-based parity of the JAX engine vs the NumPy engine (hypothesis);
the deterministic parity suite is `test_batch_jax.py`."""

import numpy as np

from hypothesis_support import given, settings, st

from repro.timeloop import PAPER_WORKLOADS, eyeriss_168  # noqa: E402
from repro.timeloop import batch as tlb  # noqa: E402
from repro.timeloop.mapping import (constrained_random_mapping,  # noqa: E402
                                    random_mapping)

from test_batch_jax import _assert_parity  # noqa: E402


@given(st.integers(0, 2**32 - 1), st.sampled_from(sorted(PAPER_WORKLOADS)))
@settings(max_examples=20, deadline=None)
def test_property_jax_matches_numpy_engine(seed, layer_name):
    """batch_jax == batch on randomized constrained pools across all seed
    workloads: values to 1e-6 (observed ~1e-12), validity masks and feature
    matrices exactly aligned."""
    layer = PAPER_WORKLOADS[layer_name]
    hw = eyeriss_168()
    rng = np.random.default_rng(seed)
    ms = [random_mapping(rng, hw, layer) for _ in range(4)]
    ms += [constrained_random_mapping(rng, hw, layer) for _ in range(4)]
    _assert_parity(hw, layer, tlb.pack(ms))
