"""The plain Moonlight reference (`bench/references/moonlight.py`) at small
widths on the CPU: its absorbed MLA against its naive MLA, its grouped
experts against a per-pair loop, and the GEMMs of its decode step against
the workload zoo's decode set and `forward_flops`; and the cell's
configuration against the zoo and the published config, and the cost
model's plain reference (`bench/references/timeloop.py`) against the NumPy
engine on the cell's layers."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, ShapeConfig, get_config
from repro.models.flops import forward_flops
from repro.timeloop import eyeriss_168
from repro.timeloop import batch as tlb
from repro.timeloop.arch import sample_hardware_pool
from repro.timeloop.mapping import sample_constrained_batch
from repro.timeloop.workloads import ConvLayer
from repro.workloads.zoo import MOONLIGHT_DECODE, generate_workload

import checks
from conftest import BENCH

spec = importlib.util.spec_from_file_location(
    "reference_moonlight", os.path.join(BENCH, "references", "moonlight.py"))
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)

spec = importlib.util.spec_from_file_location(
    "reference_timeloop", os.path.join(BENCH, "references", "timeloop.py"))
timeloop_ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(timeloop_ref)

CONFIG = os.path.join(BENCH, "configs", "moonlight16b-decode-eyeriss168.json")

# A small DeepSeek-V3 block: every width its own size, so no two GEMMs of
# different roles share a shape; 3 layers = 1 dense + 2 MoE.
SMALL = dict(ref.MOONLIGHT, num_hidden_layers=3, hidden_size=64,
             vocab_size=512, num_attention_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=8,
             intermediate_size=128, moe_intermediate_size=24,
             n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=2)
SMALL_CFG = ModelConfig(
    name="mla-moe-small", family="moe", num_layers=3, d_model=64,
    num_heads=4, num_kv_heads=4, d_ff=24, vocab_size=512,
    block_pattern=("moe",), kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=8, num_experts=8, top_k=2,
    num_shared_experts=2, dense_layers=1, dense_d_ff=128,
    tie_embeddings=False)
B, S = 8, 16
SMALL_DECODE = ShapeConfig("small_decode", seq_len=S, global_batch=B,
                           kind="decode")

# Absorbed and naive MLA compute the same sums in another association: in
# float32 (eps 1.2e-7) the two differ by a few eps of the output's scale
# (3.1e-7 measured here), after sums of at most S + r = 48 products.  1e-5
# leaves 30x room for that rounding; bfloat16 (eps 7.8e-3) departs by 8.8e-3.
MLA_RTOL = 1e-5


def rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.fixture(scope="module")
def small():
    params = ref.init_params(SMALL, seed=0)
    caches = ref.init_cache(SMALL, B, S, seed=1)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(B, 64)),
                    jnp.float32)
    return params, caches, x


def mla_out(params, cache, x, absorbed, dtype=jnp.float32):
    p = jax.tree.map(lambda t: t.astype(dtype), params["layers"][0])
    with jax.default_matmul_precision("highest"):
        out, _ = ref.mla(p, x.astype(dtype), cache.astype(dtype), S - 1,
                         SMALL, absorbed)
    return out


def test_absorbed_mla_equals_naive(small):
    params, caches, x = small
    naive = mla_out(params, caches[0], x, absorbed=False)
    absorbed = mla_out(params, caches[0], x, absorbed=True)
    assert rel_gap(absorbed, naive) <= MLA_RTOL


def test_absorbed_mla_in_bfloat16_fails_the_tolerance(small):
    """The control: the same absorbed path one precision below float32."""
    params, caches, x = small
    naive = mla_out(params, caches[0], x, absorbed=False)
    low = mla_out(params, caches[0], x, absorbed=True, dtype=jnp.bfloat16)
    assert rel_gap(low, naive) > 10 * MLA_RTOL


def test_decode_step_absorbed_equals_naive(small):
    params, caches, _ = small
    tokens = jnp.arange(B) * 37 % SMALL["vocab_size"]
    a, new_a = ref.decode_step(params, caches, tokens, S - 1, SMALL, True)
    n, new_n = ref.decode_step(params, caches, tokens, S - 1, SMALL, False)
    assert rel_gap(a, n) <= MLA_RTOL
    # the written latents agree (exactly in the first layer, whose input is
    # the same embedding on both paths); the older positions are untouched
    np.testing.assert_array_equal(new_a[0], new_n[0])
    for ca, cn, old in zip(new_a, new_n, caches):
        np.testing.assert_array_equal(ca[:, :S - 1], old[:, :S - 1])
        assert rel_gap(ca, cn) <= MLA_RTOL


def test_grouped_experts_equal_a_per_pair_loop(small):
    """The routed part of `moe` (pairs sorted by expert, one ragged product
    per matrix) against each (token, expert) pair computed on its own."""
    params, _, x = small
    p = params["layers"][1]
    with jax.default_matmul_precision("highest"):
        got = ref.moe(p, x, SMALL) - ref.mlp(p["shared"], x)
        scores = jax.nn.sigmoid(x @ p["router"])
        _, idx = jax.lax.top_k(scores + p["router_bias"], 2)
        want = np.zeros((B, 64))
        for b in range(B):
            w = np.asarray(scores[b, idx[b]])
            w = w / w.sum() * SMALL["routed_scaling_factor"]
            for j, e in enumerate(np.asarray(idx[b])):
                ex = {k: v[e] for k, v in p["experts"].items()}
                want[b] += w[j] * np.asarray(ref.mlp(ex, x[b:b + 1]))[0]
    assert rel_gap(got, want) <= 1e-5


@pytest.fixture(scope="module")
def small_dots(small):
    params, caches, _ = small
    return ref.dot_shapes(
        lambda t: ref.decode_step(params, caches, t, S - 1, SMALL)[0],
        jnp.arange(B))


def zoo_split(zw):
    """The zoo set as (C, K, P) -> count, routed experts as (C, K) -> rows."""
    dots, routed = {}, {}
    for layer, count in zip(zw.layers, zw.counts):
        if layer.name.split("-", 1)[1].startswith(("moe_up", "moe_down")):
            key = (layer.C, layer.K)
            routed[key] = routed.get(key, 0) + layer.P * count
        else:
            key = (layer.C, layer.K, layer.P)
            dots[key] = dots.get(key, 0) + count
    return dots, routed


def test_reference_gemms_equal_the_zoo_decode_set(small_dots):
    """Every dot_general of the reference's step is a zoo layer with its
    count, and back; the routed experts' rows, which the reference groups
    by its router's choice and the zoo spreads uniformly, agree in sum."""
    dots, ragged = small_dots
    zoo_dots, zoo_routed = zoo_split(
        generate_workload("mla-moe-small", SMALL_CFG, shape=SMALL_DECODE))
    assert dict(dots) == zoo_dots
    assert dict(ragged) == zoo_routed


def test_routed_macs_are_exact(small_dots):
    _, ragged = small_dots
    n_moe = SMALL["num_hidden_layers"] - SMALL["first_k_dense_replace"]
    D, F = SMALL["hidden_size"], SMALL["moe_intermediate_size"]
    k = SMALL["num_experts_per_tok"]
    assert sum(C * K * rows for (C, K), rows in ragged.items()) \
        == n_moe * B * k * 3 * D * F


def test_forward_flops_equals_the_reference_macs(small_dots):
    dots, ragged = small_dots
    macs = (sum(C * K * P * n for (C, K, P), n in dots.items())
            + sum(C * K * rows for (C, K), rows in ragged.items()))
    assert 2 * macs == forward_flops(SMALL_CFG, SMALL_DECODE)


def test_small_config_mirrors_the_reference_dict():
    assert SMALL_CFG.mla and SMALL_CFG.layer_kinds() == ("dense", "moe", "moe")
    assert (SMALL_CFG.d_model, SMALL_CFG.kv_lora_rank, SMALL_CFG.d_ff,
            SMALL_CFG.dense_d_ff, SMALL_CFG.num_experts,
            SMALL_CFG.num_shared_experts) == (
        SMALL["hidden_size"], SMALL["kv_lora_rank"],
        SMALL["moe_intermediate_size"], SMALL["intermediate_size"],
        SMALL["n_routed_experts"], SMALL["n_shared_experts"])


# --- the cell's configuration ---------------------------------------------------

def test_cell_layers_are_the_zoo_decode_set():
    with open(CONFIG) as f:
        cfg = json.load(f)
    zw = generate_workload("moonshot-v1-16b-a3b", shape=MOONLIGHT_DECODE)
    assert cfg["layers"] == [dataclasses.asdict(l) for l in zw.layers]
    assert cfg["layer_counts"] == list(zw.counts)
    assert (cfg["decode_batch"], cfg["decode_cache"]) == (
        MOONLIGHT_DECODE.global_batch, MOONLIGHT_DECODE.seq_len)


def test_cell_states_the_published_config():
    """The catalog keys of the configuration file, the reference's
    `MOONLIGHT` and the program's `moonshot-v1-16b-a3b` agree."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    for key, value in ref.MOONLIGHT.items():
        assert cfg[key] == value, key
    m = get_config("moonshot-v1-16b-a3b")
    assert (m.num_layers, m.d_model, m.vocab_size, m.num_heads,
            m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
            m.v_head_dim, m.dense_d_ff, m.dense_layers, m.d_ff,
            m.num_experts, m.top_k, m.num_shared_experts,
            m.tie_embeddings) == (
        cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"],
        cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["intermediate_size"], cfg["first_k_dense_replace"],
        cfg["moe_intermediate_size"], cfg["n_routed_experts"],
        cfg["num_experts_per_tok"], cfg["n_shared_experts"],
        cfg["tie_word_embeddings"])
    assert cfg["q_lora_rank"] is None and cfg["moe_layer_freq"] == 1


def test_cost_reference_matches_numpy_engine_on_the_cell_layers():
    """The comparison that decides `correct` holds on the cell's GEMMs:
    half valid mappings, half raw constrained draws (partly invalid), on
    Eyeriss-168 and five sampled accelerators."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    checker = checks.Checker(cfg, timeloop_ref)
    rng = np.random.default_rng(1)
    hws = [eyeriss_168()] + sample_hardware_pool(rng, 5)
    for ly in cfg["layers"]:
        layer = ConvLayer(**ly)
        for hw in hws:
            raw = tlb.MappingBatch(
                *sample_constrained_batch(rng, hw, layer, 150))
            valid = tlb.sample_valid_pool(rng, hw, layer, 150)
            mb = raw if valid is None else tlb.concat([valid, raw])
            want = tlb.evaluate_batch(hw, mb, layer)
            got = checker._rows([hw], [layer.name], mb.factors, mb.order_gb,
                                mb.order_dram, [len(mb)], np.float64)
            np.testing.assert_array_equal(got["valid"], want["valid"])
            v = want["valid"]
            np.testing.assert_allclose(got["edp"][v], want["edp"][v],
                                       rtol=1e-12)
            assert np.isinf(got["edp"][~v]).all()
