"""Model facade: build any assigned architecture and produce step functions +
ShapeDtypeStruct input specs for every (shape x kind) cell."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.encdec import EncDecLM
from repro.models.lm import LM


def build_model(cfg: ModelConfig):
    """The LM (or encoder-decoder) of `cfg`.  Raises NotImplementedError for
    a config that needs what the LM stack does not build (latent attention,
    shared experts, leading dense layers, untied embeddings), rather than
    building a different model under its name."""
    missing = [what for what, needed in (
        ("multi-head latent attention", cfg.mla),
        ("shared experts", cfg.num_shared_experts),
        ("leading dense layers", cfg.dense_layers),
        ("untied embeddings", not cfg.tie_embeddings)) if needed]
    if missing:
        raise NotImplementedError(
            f"{cfg.name}: the LM stack does not implement "
            f"{', '.join(missing)}")
    return EncDecLM(cfg) if cfg.family == "encdec" else LM(cfg)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for the step function's *data* arguments.
    Modality frontends are stubs: embeddings arrive precomputed (assignment)."""
    B, S = shape.global_batch, shape.seq_len
    specs: dict = {}
    if cfg.family == "encdec":
        S_src = max(S // 8, 16)
        specs["src_embeddings"] = _sds((B, S_src, cfg.d_model), cfg.compute_dtype)
        if shape.kind == "decode":
            specs["tokens"] = _sds((B, 1), "int32")
        else:
            specs["tokens"] = _sds((B, S), "int32")
            if shape.kind == "train":
                specs["labels"] = _sds((B, S), "int32")
        return specs

    if shape.kind == "decode":
        if cfg.input_mode == "embeddings":
            specs["embeddings"] = _sds((B, 1, cfg.d_model), cfg.compute_dtype)
        else:
            specs["tokens"] = _sds((B, 1), "int32")
        return specs

    if cfg.input_mode == "embeddings":
        specs["embeddings"] = _sds((B, S, cfg.d_model), cfg.compute_dtype)
    else:
        specs["tokens"] = _sds((B, S), "int32")
    if cfg.mrope:
        specs["positions"] = _sds((3, B, S), "int32")
    if shape.kind == "train":
        specs["labels"] = _sds((B, S), "int32")
    return specs


def cache_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStructs for the decode cache (incl. enc-dec encoder output)."""
    model = build_model(cfg)
    B, S = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        S_src = max(S // 8, 16)

        def mk():
            cache = model.init_cache(B, S)
            enc = jnp.zeros((B, S_src, cfg.d_model), jnp.dtype(cfg.compute_dtype))
            return (cache, enc)

        return jax.eval_shape(mk)
    return jax.eval_shape(lambda: model.init_cache(B, S))
