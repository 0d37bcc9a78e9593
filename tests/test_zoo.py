"""Zoo workload generation: MACs cross-check vs `models/flops.py`, shape
sanity, registry resolution, the sampler divisor-cap guard, and a seeded
golden pin for the generated shapes (shape drift fails tier-1; regenerate
with `PYTHONPATH=src python tests/test_zoo.py --regen` and commit the diff
ONLY for an intended extractor change)."""

import dataclasses
import hashlib
import json
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.models.flops import forward_flops
from repro.timeloop import (MODEL_LAYERS, SAMPLER_DIVISOR_CAP, divisors,
                            eyeriss_168, sampler_divisors)
from repro.timeloop.mapping import (constrained_random_mapping,
                                    mapping_is_valid,
                                    sample_constrained_batch)
from repro.timeloop.workloads import _TOKENS, ConvLayer, fc
from repro.workloads import (MACS_RTOL, ZOO_NAMES, known_workloads,
                             resolve_workload, workload_set, zoo_workload)
from repro.workloads.zoo import (MOONLIGHT_DECODE, ZOO_SHAPE,
                                 generate_workload, routed_tokens,
                                 step_tokens)

ZOO_GOLDEN_PATH = Path(__file__).parent / "goldens" / "zoo_workloads.json"


# --- MACs cross-check vs models/flops.py ---------------------------------------

@pytest.mark.parametrize("name", ZOO_NAMES)
def test_macs_cross_check(name):
    """2 * sum(count * macs) must equal forward_flops at the zoo tile up to
    the documented non-matmul remainder (scores+PV, elementwise gates)."""
    zw = zoo_workload(name)
    assert zw.total_macs == sum(
        c * l.macs for c, l in zip(zw.counts, zw.layers))
    flops = forward_flops(get_config(zw.arch), ZOO_SHAPE)
    assert flops == zw.model_flops
    coverage = 2.0 * zw.total_macs / flops
    assert coverage == pytest.approx(zw.coverage)
    assert 1.0 - MACS_RTOL <= coverage <= 1.0 + 1e-9, (
        f"{name}: extracted MACs cover {coverage:.4f} of forward_flops")


@pytest.mark.parametrize("name", ZOO_NAMES)
def test_shape_sanity(name):
    zw = zoo_workload(name)
    cfg = get_config(zw.arch)
    routed = ({t for t, _ in routed_tokens(_TOKENS, cfg.top_k,
                                           cfg.num_experts)}
              if cfg.num_experts else set())
    assert len(zw.layers) == len(zw.counts) > 0
    names = [l.name for l in zw.layers]
    assert len(set(names)) == len(names), "duplicate layer names"
    shapes = {(l.R, l.S, l.P, l.Q, l.C, l.K, l.stride) for l in zw.layers}
    assert len(shapes) == len(zw.layers), "duplicate shapes not merged"
    for layer, count in zip(zw.layers, zw.counts):
        assert count >= 1
        assert layer.name.startswith(zw.name + "-")
        for d in ("R", "S", "P", "Q", "C", "K"):
            assert layer.dim(d) >= 1
        assert layer.stride == 1
        assert layer.macs > 0
        # GEMM encoding: token tile on P (the encoder runs a smaller tile,
        # a routed expert its share of the tile's routed tokens)
        assert layer.P in {_TOKENS, max(_TOKENS // 8, 16), *routed}
        assert layer.input_extent(layer.P, layer.R) >= layer.P


# --- deployed shapes: Moonlight's decode step ------------------------------------

# (role, C, K, P, count) of one Moonlight-16B-A3B decode step at batch 128 and
# an 8192-token latent cache: 27 layers of absorbed MLA, one dense layer and
# 26 MoE layers (64 experts top-6, 2 shared), the untied unembed.
MOONLIGHT_DECODE_TABLE = [
    ("attn_q", 2048, 3072, 128, 27),
    ("attn_kv_a", 2048, 576, 128, 27),
    ("attn_absorb_k", 128, 512, 128, 27 * 16),
    ("attn_scores", 576, 8192, 16, 27 * 128),
    ("attn_pv", 8192, 512, 16, 27 * 128),
    ("attn_absorb_v", 512, 128, 128, 27 * 16),
    ("attn_o", 2048, 2048, 128, 27),
    ("dense_up", 2048, 11264, 128, 2),
    ("dense_down", 11264, 2048, 128, 1),
    ("moe_router", 2048, 64, 128, 26),
    ("shared_up", 2048, 2816, 128, 2 * 26),
    ("shared_down", 2816, 2048, 128, 26),
    ("moe_up", 2048, 1408, 12, 2 * 64 * 26),
    ("moe_down", 1408, 2048, 12, 64 * 26),
    ("unembed", 2048, 163840, 128, 1),
]


def test_moonlight_decode_layers_and_counts():
    zw = generate_workload("moonshot-v1-16b-a3b", shape=MOONLIGHT_DECODE)
    got = [(l.name.split("-", 1)[1], l.C, l.K, l.P, c)
           for l, c in zip(zw.layers, zw.counts)]
    assert got == MOONLIGHT_DECODE_TABLE
    assert all((l.R, l.S, l.Q, l.stride) == (1, 1, 1, 1) for l in zw.layers)
    assert zw.shape == MOONLIGHT_DECODE


@pytest.mark.parametrize("shape", [ZOO_SHAPE, MOONLIGHT_DECODE],
                         ids=lambda s: s.name)
def test_moonlight_macs_cross_check(shape):
    """Coverage of forward_flops within [1 - MACS_RTOL, 1] at the training
    tile (kv_b up-projection, scores+PV skipped) and exactly 1 at decode,
    where every product of the absorbed path is a layer."""
    zw = generate_workload("moonshot-v1-16b-a3b", shape=shape)
    flops = forward_flops(get_config("moonshot-v1-16b-a3b"), shape)
    assert 2 * zw.total_macs / flops == pytest.approx(zw.coverage)
    assert 1.0 - MACS_RTOL <= zw.coverage <= 1.0 + 1e-9
    if shape.kind == "decode":
        assert 2 * zw.total_macs == flops


@pytest.mark.parametrize("tokens,top_k,experts,want", [
    (128, 6, 64, [(12, 64)]),     # Moonlight decode: 12 tokens per expert
    (64, 6, 64, [(6, 64)]),       # Moonlight at the zoo tile
    (64, 1, 128, [(1, 64)]),      # llama4: fewer routed slots than experts
    (10, 3, 8, [(4, 6), (3, 2)]),  # uneven: as even as whole tokens allow
])
def test_routed_tokens(tokens, top_k, experts, want):
    got = routed_tokens(tokens, top_k, experts)
    assert got == want
    assert sum(t * n for t, n in got) == tokens * top_k
    assert sum(n for _, n in got) <= experts


@pytest.mark.parametrize("arch,shape", [
    ("moonshot-v1-16b-a3b", MOONLIGHT_DECODE),
    ("moonshot-v1-16b-a3b", ZOO_SHAPE),
    ("llama4-maverick-400b-a17b", ZOO_SHAPE),
])
def test_routed_expert_macs_are_exact(arch, shape):
    """The routed experts' layers carry tokens * top_k * 3 * D * F MACs in
    each MoE layer, each expert at its routed token count."""
    cfg = get_config(arch)
    zw = generate_workload(arch, shape=shape)
    routed = sum(c * l.macs for l, c in zip(zw.layers, zw.counts)
                 if l.name.split("-", 1)[1].startswith(("moe_up", "moe_down")))
    n_moe = cfg.layer_kinds().count("moe")
    T = step_tokens(shape)
    assert routed == n_moe * T * cfg.top_k * 3 * cfg.d_model * cfg.d_ff
    for l in zw.layers:
        if l.name.split("-", 1)[1].startswith(("moe_up", "moe_down")):
            assert l.P < T


def test_plain_attention_decode_fails_the_cross_check():
    """Plain attention's scores+PV are not layers yet: at a 32k decode they
    are most of the step, and generation refuses rather than under-count."""
    with pytest.raises(ValueError, match="cover"):
        generate_workload("qwen3-14b", shape=SHAPES["decode_32k"])


# --- registry / resolution ------------------------------------------------------

def test_workload_registry_resolution():
    assert set(ZOO_NAMES) == {
        a.replace("-", "_").replace(".", "_") for a in ARCH_IDS}
    # paper names resolve to the exact legacy lists
    assert resolve_workload("resnet") == list(MODEL_LAYERS["resnet"])
    # zoo names resolve through the generator; dashed aliases accepted
    assert workload_set("llama4_maverick_400b_a17b") \
        == resolve_workload("llama4-maverick-400b-a17b")
    known = known_workloads()
    assert "resnet" in known and "qwen3_14b" in known
    with pytest.raises(ValueError) as ei:
        resolve_workload("nope")
    msg = str(ei.value)
    assert "resnet" in msg and "qwen3_14b" in msg


def test_zoo_workload_is_cached():
    assert zoo_workload("qwen3_14b") is zoo_workload("qwen3-14b")


# --- sampler divisor-cap guard --------------------------------------------------

def test_sampler_divisors_passthrough_below_cap():
    """Every paper and zoo dim sits under the cap: the sampler ladder is the
    exact divisor tuple (so RNG streams -- and the goldens -- are
    unchanged)."""
    dims = {layer.dim(d)
            for layers in MODEL_LAYERS.values() for layer in layers
            for d in ("R", "S", "P", "Q", "C", "K")}
    for name in ZOO_NAMES:
        for layer in zoo_workload(name).layers:
            dims.update(layer.dim(d) for d in ("R", "S", "P", "Q", "C", "K"))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no cap warning may fire
        for n in sorted(dims):
            assert len(divisors(n)) <= SAMPLER_DIVISOR_CAP
            assert sampler_divisors(n) == divisors(n)


def test_sampler_divisors_caps_pathological_dims():
    n = 720720  # 2^4*3^2*5*7*11*13: 240 divisors
    full = divisors(n)
    assert len(full) > SAMPLER_DIVISOR_CAP
    with pytest.warns(RuntimeWarning, match="SAMPLER_DIVISOR_CAP"):
        sampler_divisors.cache_clear()
        capped = sampler_divisors(n)
    assert len(capped) <= SAMPLER_DIVISOR_CAP
    assert set(capped) <= set(full)
    assert capped[0] == 1 and capped[-1] == n
    assert list(capped) == sorted(capped)


def test_capped_dims_still_sample_valid_mappings():
    """The samplers stay structurally correct when a dim's ladder is capped:
    factor products must still equal the layer dims."""
    layer = fc("pathological", 720720, 64, _TOKENS)
    hw = eyeriss_168()
    rng = np.random.default_rng(0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for _ in range(5):
            m = constrained_random_mapping(rng, hw, layer)
            ok, reason = mapping_is_valid(m, hw, layer)
            assert ok or reason == "gb_capacity", reason
        factors, *_ = sample_constrained_batch(rng, hw, layer, 16)
    prods = factors.prod(axis=1)
    want = [layer.dim(d) for d in ("R", "S", "P", "Q", "C", "K")]
    assert (prods == np.array(want)[None, :]).all()


def test_conv_layer_divisors_method():
    layer = fc("x", 96, 7, _TOKENS)
    assert layer.divisors("C") == [1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 96]
    assert layer.divisors("K") == [1, 7]


# --- seeded golden pin ----------------------------------------------------------

def zoo_golden_record(name: str) -> dict:
    zw = zoo_workload(name)
    canonical = repr([(dataclasses.astuple(l), c)
                      for l, c in zip(zw.layers, zw.counts)])
    return {
        "shapes_sha256": hashlib.sha256(canonical.encode()).hexdigest(),
        "n_layers": len(zw.layers),
        "total_macs": zw.total_macs,
        "coverage": round(zw.coverage, 6),
    }


@pytest.mark.parametrize("name", ZOO_NAMES)
def test_zoo_matches_golden(name):
    goldens = json.loads(ZOO_GOLDEN_PATH.read_text())
    got = zoo_golden_record(name)
    want = goldens[name]
    assert got == want, (
        f"zoo workload drift on {name!r}:\n  got  {got}\n  want {want}\n"
        "If this PR intentionally changes the extractors, regenerate with\n"
        "  PYTHONPATH=src python tests/test_zoo.py --regen\n"
        "and commit the goldens diff; otherwise this is a regression.")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="rewrite tests/goldens/zoo_workloads.json")
    args = ap.parse_args()
    records = {n: zoo_golden_record(n) for n in ZOO_NAMES}
    print(json.dumps(records, indent=2))
    if args.regen:
        ZOO_GOLDEN_PATH.parent.mkdir(parents=True, exist_ok=True)
        ZOO_GOLDEN_PATH.write_text(
            json.dumps(records, indent=2, sort_keys=True) + "\n")
        print(f"wrote {ZOO_GOLDEN_PATH}")
