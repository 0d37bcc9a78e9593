"""Shared hypothesis strategies for the config API property tests.

Import it from the property-test modules:

    from hypothesis_support import given, settings, st

Not named test_*, so pytest never collects it directly.
"""

from hypothesis import given, settings, strategies as st

from repro.core import (ACQUISITIONS, BACKENDS, PALLAS_MODES,
                        PRUNE_MODES, STRATEGIES, SURROGATES)

# --- CodesignConfig strategies ----------------------------------------------------
# Valid-by-construction section dicts (the from_dict surface): every enumerated
# string from its real choice tuple, every bound respected -- so round-trip
# properties never trip construction-time validation.

search_fields = dict(
    n_trials=st.integers(1, 400),
    n_warmup=st.integers(0, 60),
    pool_size=st.integers(1, 200),
    acquisition=st.sampled_from(ACQUISITIONS),
    lam=st.floats(0.0, 8.0, allow_nan=False, allow_infinity=False),
    surrogate=st.sampled_from(SURROGATES),
    elite_k=st.integers(0, 8),
)

sw_sections = st.fixed_dictionaries({}, optional=search_fields)

hw_sections = st.fixed_dictionaries(
    {},
    optional=dict(search_fields,
                  num_pes=st.sampled_from([64, 128, 168, 256]),
                  spec_k=st.integers(1, 8),
                  prune=st.sampled_from(PRUNE_MODES),
                  prune_margin=st.floats(0.125, 4.0, allow_nan=False,
                                         allow_infinity=False)),
)

engine_sections = st.fixed_dictionaries(
    {},
    optional=dict(
        backend=st.sampled_from([None, *BACKENDS]),
        # speculative requires use_cache=True (validated at construction);
        # the valid-config strategy respects that coupling.
        strategy=st.sampled_from([s for s in STRATEGIES
                                  if s != "speculative"]),
        gp_refit_every=st.integers(1, 8),
        hw_gp_refit_every=st.integers(1, 8),
        batched=st.booleans(),
        use_cache=st.booleans(),
        gp_rank1_updates=st.booleans(),
        pallas_mode=st.sampled_from([None, *PALLAS_MODES]),
    ),
)

config_dicts = st.fixed_dictionaries(
    {},
    optional=dict(
        sw=sw_sections,
        hw=hw_sections,
        engine=engine_sections,
        seed=st.integers(0, 2**31 - 1),
        verbose=st.booleans(),
    ),
)

# Strings that are NOT one of the given choices (the rejection property).
def not_in(choices):
    return st.text(min_size=1, max_size=12).filter(lambda s: s not in choices)
