"""Plain reference of one decode step of Moonlight-16B-A3B, the DeepSeek-V3
block (https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json),
in straightforward `jax.numpy`, float32, with random weights from a seed.

It imports nothing of the program.  The configuration is a dict with the
published config's own keys (`MOONLIGHT` holds them at their published
values); the tests build small ones.  One step takes one new token per
sequence at position `pos` and a latent cache of `pos + 1` positions per
layer, writes the token's latent at `pos`, and attends over all of them:

    layer i:  h = x + MLA(norm(x));  x = h + FFN(norm(h))
    FFN:      the dense MLP (intermediate_size) in the first
              first_k_dense_replace layers, else the MoE layer
    logits:   norm(x) @ lm_head (untied from the embedding)

MLA (q_lora_rank null): q = x W_q, split per head into q_nope (dn) and
q_pe (dr); [c ; k_pe] = x W_kv_a with c normalized and k_pe rotated, one
(r + dr)-wide latent per position shared by all heads, and that is what the
cache holds.  W_kv_b maps the latent to each head's key (W_UK, r -> dn) and
value (W_UV, r -> dv).  Computed two ways:

- naive: up-project every cached latent to per-head keys and values, then
  softmax((q_nope k_nope + q_pe k_pe) / sqrt(dn + dr)) v, per head;
- absorbed (the decode path): q_lat = q_nope W_UK^T per head, scores are
  [q_lat ; q_pe] against the whole (r + dr)-wide cache in one product, PV
  is the probabilities against the cache's latent part, and the (r-wide)
  result is mapped to v by W_UV per head.

MoE (DeepSeek-V3, n_group = topk_group = 1): scores = sigmoid(x W_router);
the top num_experts_per_tok of scores + e_score_correction_bias are chosen;
their weights are the chosen scores, normalized to sum 1 (norm_topk_prob)
and scaled by routed_scaling_factor.  Each (token, expert) pair runs its
expert's SiLU-gated MLP (moe_intermediate_size) as one grouped product over
the pairs sorted by expert (`lax.ragged_dot`), and n_shared_experts shared
experts run as one MLP n_shared_experts * moe_intermediate_size wide.

Departures: RoPE uses the rotate-half convention on the rotary part (the
published code permutes interleaved pairs first; both sides of every
comparison here use the same rotation); nothing is cached beyond the latent.

Every product of the absorbed step is a `lax.dot_general` with the
activation on the left (a ragged one for the routed experts), so
`dot_shapes` reads each GEMM of a step from its jaxpr as (C, K, P) and a
count: C the contracted size, K the right operand's free size, P the left's
free size, the count the batch size.
"""

from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np

MOONLIGHT = {
    "num_hidden_layers": 27, "hidden_size": 2048, "vocab_size": 163840,
    "num_attention_heads": 16, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "intermediate_size": 11264,
    "first_k_dense_replace": 1, "moe_intermediate_size": 1408,
    "n_routed_experts": 64, "num_experts_per_tok": 6, "n_shared_experts": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "rms_norm_eps": 1e-5, "rope_theta": 50000.0,
}


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def init_params(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """Random weights: each matrix N(0, 1/fan_in), norms 1 + N(0, 0.1)."""
    D, H, r, dn, dr, dv = _dims(cfg)
    V, E = cfg["vocab_size"], cfg["n_routed_experts"]
    F, Fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    Fs = cfg["n_shared_experts"] * F
    rng = np.random.default_rng(seed)

    def w(*shape, fan_in=None):
        fan_in = shape[-2] if fan_in is None else fan_in
        return jnp.asarray(rng.normal(size=shape) / math.sqrt(fan_in), dtype)

    def norm(n):
        return jnp.asarray(1.0 + 0.1 * rng.normal(size=n), dtype)

    def mlp(width):
        return {"gate": w(D, width), "up": w(D, width), "down": w(width, D)}

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        layer = {"attn_norm": norm(D), "ffn_norm": norm(D),
                 "q": w(D, H * (dn + dr)), "kv_a": w(D, r + dr),
                 "kv_a_norm": norm(r), "kv_b": w(r, H * (dn + dv)),
                 "o": w(H * dv, D)}
        if i < cfg["first_k_dense_replace"]:
            layer["mlp"] = mlp(Fd)
        else:
            layer["router"] = w(D, E)
            layer["router_bias"] = jnp.asarray(0.1 * rng.normal(size=E), dtype)
            layer["experts"] = {"gate": w(E, D, F), "up": w(E, D, F),
                                "down": w(E, F, D)}
            layer["shared"] = mlp(Fs)
        layers.append(layer)
    return {"embed": w(V, D, fan_in=1), "layers": layers,
            "final_norm": norm(D), "lm_head": w(D, V)}


def init_cache(cfg: dict, batch: int, length: int, seed: int,
               dtype=jnp.float32) -> list:
    """One random (batch, length, r + dr) latent cache per layer: the
    latent and the rotary key of each position."""
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=(batch, length, width)), dtype)
            for _ in range(cfg["num_hidden_layers"])]


def _dot(x, w, contract, batch=((), ())):
    return jax.lax.dot_general(x, w, (contract, batch))


def _mm(x, w):
    """x (..., C) @ w (C, K)."""
    return _dot(x, w, ((x.ndim - 1,), (0,)))


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, pos, theta):
    """Rotate-half RoPE of x (..., d) at position `pos`."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos * freq
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, x, cache, pos, cfg, absorbed: bool = True):
    """MLA of x (B, D) at position `pos` over `cache` (B, S, r + dr), S =
    pos + 1; returns the output (B, D) and the cache with pos written."""
    D, H, r, dn, dr, dv = _dims(cfg)
    B = x.shape[0]
    q = _mm(x, p["q"]).reshape(B, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])
    kv = _mm(x, p["kv_a"])
    latent = jnp.concatenate(
        [rms_norm(kv[:, :r], p["kv_a_norm"], cfg["rms_norm_eps"]),
         rope(kv[:, r:], pos, cfg["rope_theta"])], -1)
    cache = cache.at[:, pos].set(latent)
    kv_b = p["kv_b"].reshape(r, H, dn + dv)
    w_uk, w_uv = kv_b[..., :dn], kv_b[..., dn:]          # (r, H, dn|dv)
    scale = 1.0 / math.sqrt(dn + dr)
    if absorbed:
        # (H, B, r): each head's q_nope mapped into the latent
        q_lat = _dot(q_nope, w_uk, ((2,), (2,)), ((1,), (1,)))
        q_cat = jnp.concatenate([q_lat.transpose(1, 0, 2), q_pe], -1)
        s = _dot(q_cat, cache, ((2,), (2,)), ((0,), (0,))) * scale  # (B,H,S)
        prob = jax.nn.softmax(s, axis=-1)
        o_lat = _dot(prob, cache[..., :r], ((2,), (1,)), ((0,), (0,)))
        o = _dot(o_lat, w_uv, ((2,), (0,)), ((1,), (1,)))  # (H, B, dv)
        o = o.transpose(1, 0, 2)
    else:
        kvs = _mm(cache[..., :r], p["kv_b"]).reshape(B, -1, H, dn + dv)
        k_nope, v = kvs[..., :dn], kvs[..., dn:]
        s = (jnp.einsum("bhd,bshd->bhs", q_nope, k_nope)
             + jnp.einsum("bhd,bsd->bhs", q_pe, cache[..., r:])) * scale
        prob = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhs,bshd->bhd", prob, v)
    return _mm(o.reshape(B, H * dv), p["o"]), cache


def mlp(p, x):
    return _mm(jax.nn.silu(_mm(x, p["gate"])) * _mm(x, p["up"]), p["down"])


def moe(p, x, cfg):
    """DeepSeek-V3 MoE layer of x (B, D): routed experts plus shared."""
    B = x.shape[0]
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm(x, p["router"]))
    _, idx = jax.lax.top_k(scores + p["router_bias"], k)        # (B, k)
    weight = jnp.take_along_axis(scores, idx, axis=1)
    if cfg["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * cfg["routed_scaling_factor"]
    expert = idx.reshape(-1)
    order = jnp.argsort(expert, stable=True)
    token = jnp.repeat(jnp.arange(B), k)[order]
    sizes = jnp.bincount(expert, length=E).astype(jnp.int32)
    xs, ex = x[token], p["experts"]
    h = (jax.nn.silu(jax.lax.ragged_dot(xs, ex["gate"], sizes))
         * jax.lax.ragged_dot(xs, ex["up"], sizes))
    y = jax.lax.ragged_dot(h, ex["down"], sizes)
    y = y * weight.reshape(-1)[order][:, None].astype(y.dtype)
    routed = jnp.zeros_like(x).at[token].add(y)
    return routed + mlp(p["shared"], x)


def decode_step(params, caches, tokens, pos, cfg, absorbed: bool = True):
    """One decode step: tokens (B,) at position `pos`; returns the logits
    (B, vocab) and the updated caches."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        new = []
        for i, p in enumerate(params["layers"]):
            a, c = mla(p, rms_norm(x, p["attn_norm"], eps), caches[i], pos,
                       cfg, absorbed)
            new.append(c)
            h = x + a
            f = rms_norm(h, p["ffn_norm"], eps)
            x = h + (mlp(p["mlp"], f) if "mlp" in p else moe(p, f, cfg))
        x = rms_norm(x, params["final_norm"], eps)
        return _mm(x, params["lm_head"]), new


def dot_shapes(fn, *args) -> tuple[collections.Counter, collections.Counter]:
    """The GEMMs of `fn(*args)`'s jaxpr: a Counter of (C, K, P) -> count
    over its `dot_general`s, and one of (C, K) -> rows over its ragged
    (grouped) products, whose rows are split over the groups at run time."""
    dots, ragged = collections.Counter(), collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in ("dot_general", "ragged_dot_general"):
                lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
                if name == "dot_general":
                    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
                else:
                    (lc, rc), (lb, rb) = eqn.params[
                        "ragged_dot_dimension_numbers"].dot_dimension_numbers
                    rb = (*rb, *eqn.params[
                        "ragged_dot_dimension_numbers"].rhs_group_dimensions)
                C = math.prod(lhs[d] for d in lc)
                P = math.prod(s for d, s in enumerate(lhs)
                              if d not in lc and d not in lb)
                K = math.prod(s for d, s in enumerate(rhs)
                              if d not in rc and d not in rb)
                if name == "dot_general":
                    dots[(C, K, P)] += math.prod(lhs[d] for d in lb)
                else:
                    ragged[(C, K)] += P
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return dots, ragged
