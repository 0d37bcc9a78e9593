"""Property-based tests of the typed config API (hypothesis): JSON round-trip
over randomized valid configs, and loud `ValueError` rejection of invalid
enumerated strings and `spec_k`/`elite_k` bounds."""

import dataclasses
import json

from hypothesis_support import config_dicts, given, not_in, settings, st

from repro.core import (ACQUISITIONS, BACKENDS, PRUNE_MODES, STRATEGIES,
                        SURROGATES, CodesignConfig, EngineConfig,
                        HWSearchConfig, SWSearchConfig)

import pytest


@given(config_dicts)
@settings(max_examples=60, deadline=None)
def test_config_json_round_trip(d):
    """from_dict(to_dict(cfg)) == cfg through real JSON for every valid
    config the strategy can express -- sections and fields freely omitted."""
    cfg = CodesignConfig.from_dict(d)
    assert CodesignConfig.from_dict(json.loads(json.dumps(cfg.to_dict()))) == cfg
    assert CodesignConfig.from_json(cfg.to_json()) == cfg


@given(config_dicts)
@settings(max_examples=30, deadline=None)
def test_from_dict_applies_defaults_consistently(d):
    """Omitted fields take the dataclass defaults -- from_dict(d) equals the
    explicit constructor call with the same sections."""
    cfg = CodesignConfig.from_dict(d)
    explicit = CodesignConfig(
        sw=SWSearchConfig(**d.get("sw") or {}),
        hw=HWSearchConfig(**d.get("hw") or {}),
        engine=EngineConfig(**d.get("engine") or {}),
        **{k: v for k, v in d.items() if k in ("seed", "verbose")})
    assert cfg == explicit


@given(st.sampled_from(["acquisition", "surrogate"]),
       not_in(ACQUISITIONS + SURROGATES))
@settings(max_examples=25, deadline=None)
def test_invalid_search_enums_rejected(field, bad):
    with pytest.raises(ValueError, match=field):
        SWSearchConfig(**{field: bad})


@given(st.sampled_from(["backend", "strategy", "pallas_mode"]),
       not_in(BACKENDS + STRATEGIES + ("jnp", "pallas", "interpret")))
@settings(max_examples=25, deadline=None)
def test_invalid_engine_enums_rejected(field, bad):
    with pytest.raises(ValueError, match=field):
        EngineConfig(**{field: bad})


@given(st.one_of(st.integers(max_value=0), st.booleans(),
                 st.floats(allow_nan=False), st.text(max_size=4)))
@settings(max_examples=30, deadline=None)
def test_invalid_spec_k_rejected(bad):
    """spec_k must be a real int >= 1: zero/negative ints, bools, floats and
    strings all raise at construction."""
    with pytest.raises(ValueError, match="spec_k"):
        HWSearchConfig(spec_k=bad)


@given(st.one_of(st.integers(max_value=-1), st.booleans(),
                 st.floats(allow_nan=False)))
@settings(max_examples=20, deadline=None)
def test_invalid_elite_k_rejected(bad):
    with pytest.raises(ValueError, match="elite_k"):
        SWSearchConfig(elite_k=bad)


@given(not_in(PRUNE_MODES))
@settings(max_examples=25, deadline=None)
def test_invalid_prune_mode_rejected(bad):
    """prune must be one of PRUNE_MODES -- any other string raises loudly."""
    with pytest.raises(ValueError, match="prune"):
        HWSearchConfig(prune=bad)


@given(st.one_of(st.integers(max_value=0), st.booleans(),
                 st.floats(max_value=0.0, allow_nan=False),
                 st.just(float("nan")), st.text(max_size=4)))
@settings(max_examples=30, deadline=None)
def test_invalid_prune_margin_rejected(bad):
    """prune_margin must be a real number > 0: zero/negative, bools, NaN and
    strings all raise at construction."""
    with pytest.raises(ValueError, match="prune_margin"):
        HWSearchConfig(prune_margin=bad)


@given(st.sampled_from(PRUNE_MODES),
       st.floats(0.125, 4.0, allow_nan=False, allow_infinity=False),
       st.booleans())
@settings(max_examples=20, deadline=None)
def test_prune_and_rank1_round_trip(mode, margin, rank1):
    """The pruning + rank-1 toggles survive the JSON round-trip like every
    other field -- `run.py --config` surfaces them via from_dict."""
    cfg = CodesignConfig(hw=HWSearchConfig(prune=mode, prune_margin=margin),
                         engine=EngineConfig(gp_rank1_updates=rank1))
    back = CodesignConfig.from_json(cfg.to_json())
    assert back == cfg
    assert back.hw.prune == mode
    assert back.hw.prune_margin == margin
    assert back.engine.gp_rank1_updates == rank1


@given(st.sampled_from(["speculative"]))
@settings(max_examples=4, deadline=None)
def test_fanout_strategies_require_cache(strategy):
    with pytest.raises(ValueError, match="use_cache"):
        EngineConfig(strategy=strategy, use_cache=False)
    # with the cache on they construct fine and survive replacement round-trips
    eng = EngineConfig(strategy=strategy)
    assert dataclasses.replace(eng) == eng
