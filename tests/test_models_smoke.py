"""Per-architecture smoke tests (reduced configs, CPU) + consistency checks
between the parallel (train/prefill) and recurrent (decode) code paths."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config, get_smoke_config, SHAPES, cell_is_applicable
from repro.models.model import build_model, input_specs
from repro.models import layers as L
from repro.models import xlstm as XL

KEY = jax.random.key(0)
B, S = 2, 32


def _batch(cfg, rng, with_labels=True, S=S):
    batch = {}
    if cfg.family == "encdec":
        batch["src_embeddings"] = jnp.asarray(rng.normal(size=(B, 8, cfg.d_model)), jnp.float32)
        batch["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)))
    elif cfg.input_mode == "embeddings":
        batch["embeddings"] = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
    else:
        batch["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)))
    if with_labels:
        batch["labels"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    rng = np.random.default_rng(0)
    batch = _batch(cfg, rng)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert jnp.isfinite(loss), arch
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    assert jnp.isfinite(gnorm) and gnorm > 0, arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode_shapes(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    rng = np.random.default_rng(1)
    batch = _batch(cfg, rng, with_labels=False)
    logits, cache = jax.jit(model.prefill)(params, batch)
    assert logits.shape[:2] == (B, 1)
    assert bool(jnp.isfinite(logits).all()), arch
    if cfg.family == "encdec" or cfg.input_mode != "embeddings":
        step = {"tokens": jnp.ones((B, 1), jnp.int32)}
    else:
        step = {"embeddings": jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)}
    logits2, _ = jax.jit(model.decode_step)(params, cache, step,
                                            jnp.asarray(S - 1, jnp.int32))
    assert bool(jnp.isfinite(logits2).all()), arch


@pytest.mark.parametrize("arch", ["phi3-medium-14b", "qwen3-14b", "moonshot-v1-16b-a3b"])
def test_prefill_decode_consistency(arch):
    """Decode logits at position S from the prefill cache must match a full
    forward over S+1 tokens (cache correctness end-to-end)."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, (B, S + 1))
    S_max = S + 8
    padded = np.zeros((B, S_max), np.int64)
    padded[:, :S] = toks[:, :S]
    _, cache = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(padded)})
    dec_logits, _ = jax.jit(model.decode_step)(
        params, cache, {"tokens": jnp.asarray(toks[:, S:S + 1])},
        jnp.asarray(S, jnp.int32))
    ref_logits, _ = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(toks)})
    np.testing.assert_allclose(np.asarray(dec_logits[:, 0]),
                               np.asarray(ref_logits[:, -1]),
                               rtol=2e-2, atol=2e-2)


def test_mlstm_chunkwise_matches_recurrent():
    """The chunkwise-parallel mLSTM must equal the naive per-step recurrence."""
    rng = np.random.default_rng(3)
    Bh, Sh, H, dh = 2, 24, 2, 8
    q = jnp.asarray(rng.normal(size=(Bh, Sh, H, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(Bh, Sh, H, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(Bh, Sh, H, dh)), jnp.float32)
    ig = jnp.asarray(rng.normal(size=(Bh, Sh, H)), jnp.float32)
    fg = jnp.asarray(rng.normal(size=(Bh, Sh, H)) + 2.0, jnp.float32)

    for chunk in (1, 4, 8, 24):
        out, _ = XL.mlstm_chunkwise(q, k, v, ig, fg, chunk)
        # reference: strict per-timestep recurrence
        state = (jnp.zeros((Bh, H, dh, dh)), jnp.zeros((Bh, H, dh)),
                 jnp.full((Bh, H), -1e30))
        refs = []
        for t in range(Sh):
            o, state = XL.mlstm_recurrent_step(
                q[:, t], k[:, t], v[:, t], ig[:, t], fg[:, t], state)
            refs.append(o)
        ref = jnp.stack(refs, axis=1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_rglru_scan_matches_stepwise():
    from repro.models import rglru as RG
    cfg = get_smoke_config("recurrentgemma-9b")
    p = RG.init_rglru_block(KEY, cfg, jnp.float32)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(B, 12, cfg.d_model)), jnp.float32)
    full, state_full = RG.rglru_block(p, cfg, x, return_state=True)
    state = RG.init_rglru_state(cfg, B)
    outs = []
    for t in range(12):
        o, state = RG.rglru_block_decode(p, cfg, x[:, t:t + 1], state)
        outs.append(o)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(step), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state_full["h"]), np.asarray(state["h"]),
                               rtol=1e-4, atol=1e-4)


def test_flash_matches_naive_sdpa():
    rng = np.random.default_rng(5)
    for (Bf, Sf, H, KV, hd, win) in [(2, 64, 4, 2, 16, 0), (1, 96, 6, 2, 8, 24)]:
        g = H // KV
        q = jnp.asarray(rng.normal(size=(Bf, Sf, H, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(Bf, Sf, KV, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(Bf, Sf, KV, hd)), jnp.float32)
        ref = L._sdpa(q, k, v, L.causal_mask(Sf, win), g)
        got = L.flash_sdpa(q, k, v, g, win, 32, 32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_int8_kv_cache_roundtrip():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(2, 1, 4, 16)) * 3, jnp.float32)
    q8, scale = L._quant(x)
    back = L._dequant(q8, scale, jnp.float32)
    assert float(jnp.max(jnp.abs(back - x))) < float(jnp.max(jnp.abs(x))) / 127 * 1.01


@pytest.mark.parametrize("change,what", [
    ({"kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
      "v_head_dim": 16}, "multi-head latent attention"),
    ({"num_shared_experts": 1}, "shared experts"),
    ({"dense_layers": 1, "dense_d_ff": 128}, "leading dense layers"),
    ({"tie_embeddings": False}, "untied embeddings"),
])
def test_build_model_refuses_what_the_lm_stack_lacks(change, what):
    """A config the LM stack cannot build is refused, not silently built as
    plain MHA / plain MoE under its name; the smoke config still builds."""
    cfg = dataclasses.replace(get_smoke_config("moonshot-v1-16b-a3b"), **change)
    with pytest.raises(NotImplementedError, match=what):
        build_model(cfg)
    build_model(get_smoke_config("moonshot-v1-16b-a3b"))


def test_build_model_refuses_the_published_moonlight_config():
    with pytest.raises(NotImplementedError, match="latent attention"):
        build_model(get_config("moonshot-v1-16b-a3b"))


def test_long_500k_skip_rules():
    shape = SHAPES["long_500k"]
    runs = {a: cell_is_applicable(get_config(a), shape)[0] for a in ARCH_IDS}
    assert runs["xlstm-1.3b"] and runs["recurrentgemma-9b"]
    assert sum(runs.values()) == 2  # everyone else is full-attention -> skip