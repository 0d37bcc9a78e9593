"""Transformer primitives: norms, RoPE / M-RoPE, GQA attention (train + cached
decode, causal or local-window), SwiGLU MLP, embeddings, quantized KV cache."""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel import sharding

# ---------------------------------------------------------------- init helpers

def dense_init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------- norms

def rmsnorm(x, scale, eps=1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def head_rmsnorm(x, scale, eps=1e-6):
    """qk-norm: rmsnorm over the head_dim axis."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


# ----------------------------------------------------------------------- rope

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return theta ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32) / (head_dim // 2))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                       # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float = 10000.0):
    """M-RoPE (Qwen2-VL): rotary pairs split into 3 sections (t/h/w), each
    rotated by its own position stream.  positions3: (3, ..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    sect = [half - 2 * (half // 3), half // 3, half // 3]  # t gets the remainder
    freqs = rope_freqs(hd, theta)
    pieces = []
    start = 0
    for comp in range(3):
        f = freqs[start : start + sect[comp]]
        ang = positions3[comp][..., None].astype(jnp.float32) * f
        pieces.append(ang)
        start += sect[comp]
    angles = jnp.concatenate(pieces, axis=-1)[..., None, :]  # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------- attention

def init_attention(key, cfg: ModelConfig, dtype):
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = split_keys(key, 4)
    p = {
        "ln": jnp.zeros((D,), dtype),
        "wq": dense_init(ks[0], (D, H * hd), dtype),
        "wk": dense_init(ks[1], (D, KV * hd), dtype),
        "wv": dense_init(ks[2], (D, KV * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, D), dtype, scale=(H * hd) ** -0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), dtype)
    return p


def _qkv(p, cfg: ModelConfig, x, positions):
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = rmsnorm(x, p["ln"])
    q = (h @ p["wq"]).reshape(B, S, H, hd)
    k = (h @ p["wk"]).reshape(B, S, KV, hd)
    v = (h @ p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = head_rmsnorm(q, p["q_norm"])
        k = head_rmsnorm(k, p["k_norm"])
    if cfg.mrope:
        q = apply_mrope(q, positions)
        k = apply_mrope(k, positions)
    elif cfg.rope:
        q = apply_rope(q, positions)
        k = apply_rope(k, positions)
    q = sharding.act(q, "batch", "seq", "heads", None)
    k = sharding.act(k, "batch", "seq", None, None)
    return q, k, v


def _sdpa(q, k, v, mask, q_per_kv: int):
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd), mask: (B,1,Sq,Sk) or broadcastable."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    q = q.reshape(B, Sq, KV, q_per_kv, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H * hd)


# When True (set by the dry-run's cost-extrapolation variants), chunk loops are
# unrolled so XLA's cost analysis -- which counts while-loop bodies once -- sees
# every iteration.  Never enabled for real execution.
ANALYSIS_UNROLL = False


# Cap on unrolled copies: bounds depth-variant compile time on 1 CPU core.
# Inner loops longer than the cap stay partially rolled; their residual
# undercount is covered by the analytic FLOPs model (models/flops.py).
ANALYSIS_UNROLL_CAP = 4


def analysis_unroll(n: int) -> int:
    """lax.scan unroll factor: (capped) full length in analysis mode so loop
    iterations appear in the HLO (cost analysis counts loop bodies once)."""
    import repro.models.layers as _self
    return min(max(int(n), 1), ANALYSIS_UNROLL_CAP) if _self.ANALYSIS_UNROLL else 1


def _chunk_map(fn, xs, n):
    """lax.map with a partially-unrolled variant for analysis mode."""
    if ANALYSIS_UNROLL and n <= ANALYSIS_UNROLL_CAP:
        outs = [fn(jax.tree.map(lambda a: a[i], xs)) for i in range(n)]
        return jnp.stack(outs)
    return jax.lax.map(fn, xs)


def flash_sdpa(q, k, v, q_per_kv: int, window: int = 0,
               bq: int = 1024, bk: int = 1024):
    """Flash-style causal attention in pure JAX: online softmax over K/V chunks,
    scan over Q chunks.  Peak memory O(bq*bk) per (batch, head) instead of
    O(S^2).  For local windows, each Q chunk gathers only its K window.

    q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd) -> (B,Sq,H*hd)
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    KV = k.shape[2]
    g = q_per_kv
    bq = min(bq, Sq)
    while Sq % bq:
        bq //= 2
    nq = Sq // bq
    scale = hd ** -0.5

    qc = q.reshape(B, nq, bq, KV, g, hd).swapaxes(0, 1)   # (nq,B,bq,KV,g,hd)

    if window > 0:
        span = window + bq                                 # static K slice per Q chunk
        span = min(span, Sk)

        def one_chunk(i, qb):
            start = jnp.clip(i * bq + bq - span, 0, Sk - span)
            kb = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            qpos = i * bq + jnp.arange(bq)
            kpos = start + jnp.arange(span)
            m = (kpos[None] <= qpos[:, None]) & (kpos[None] > qpos[:, None] - window)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qb, kb).astype(jnp.float32) * scale
            s = jnp.where(m[None, None, None], s, -1e30)
            w = jax.nn.softmax(s, axis=-1).astype(vb.dtype)
            return jnp.einsum("bkgqs,bskh->bqkgh", w, vb)

        one_chunk = jax.checkpoint(one_chunk)
        outs = _chunk_map(lambda args: one_chunk(*args), (jnp.arange(nq), qc), nq)
        return outs.swapaxes(0, 1).reshape(B, Sq, H * hd).astype(q.dtype)

    bk = min(bk, Sk)
    while Sk % bk:
        bk //= 2
    nk = Sk // bk
    kc = k.reshape(B, nk, bk, KV, hd).swapaxes(0, 1)
    vc = v.reshape(B, nk, bk, KV, hd).swapaxes(0, 1)

    def q_chunk(i, qb):
        # online softmax across K chunks
        m0 = jnp.full((B, KV, g, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, g, bq), jnp.float32)
        acc0 = jnp.zeros((B, bq, KV, g, hd), jnp.float32)
        qpos = i * bq + jnp.arange(bq)

        def kv_step(carry, xs):
            m_prev, l_prev, acc = carry
            j, kb, vb = xs
            kpos = j * bk + jnp.arange(bk)
            valid = kpos[None] <= qpos[:, None]               # (bq,bk) causal
            s = jnp.einsum("bqkgh,bskh->bkgqs", qb, kb).astype(jnp.float32) * scale
            s = jnp.where(valid[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(valid[None, None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(vb.dtype), vb).astype(jnp.float32)
            acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
            return (m_new, l_new, acc), None

        kv_step_ck = jax.checkpoint(kv_step)  # recompute p in backward (flash)
        if ANALYSIS_UNROLL:
            carry = (m0, l0, acc0)
            for j in range(nk):
                carry, _ = kv_step_ck(carry, (jnp.asarray(j), kc[j], vc[j]))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(
                kv_step_ck, (m0, l0, acc0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return out

    q_chunk = jax.checkpoint(q_chunk)
    outs = _chunk_map(lambda args: q_chunk(*args), (jnp.arange(nq), qc), nq)
    out = outs.swapaxes(0, 1).reshape(B, Sq, H, hd)
    return out.reshape(B, Sq, H * hd).astype(q.dtype)


def causal_mask(S: int, window: int = 0, dtype=jnp.bool_):
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    m = j <= i
    if window > 0:
        m = m & (j > i - window)
    return m[None, None]  # (1,1,S,S)


def full_seq_sdpa(cfg: ModelConfig, q, k, v, window: int, causal: bool = True):
    if cfg.attn_impl == "flash" and causal:
        return flash_sdpa(q, k, v, cfg.q_per_kv, window,
                          cfg.flash_block_q, cfg.flash_block_k)
    S, Sk = q.shape[1], k.shape[1]
    mask = causal_mask(S, window) if causal else jnp.ones((1, 1, S, Sk), bool)
    return _sdpa(q, k, v, mask, cfg.q_per_kv)


def attention(p, cfg: ModelConfig, x, positions, window: int = 0):
    """Full-sequence attention (train / prefill)."""
    B, S, D = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    out = full_seq_sdpa(cfg, q, k, v, window)
    out = out @ p["wo"]
    return sharding.act(out, "batch", "seq", "dmodel")


# --------------------------------------------------------- KV cache (+ int8)

@dataclasses.dataclass(frozen=True)
class CacheSpec:
    seq_len: int
    dtype: str  # "bfloat16" | "float32" | "int8"


def init_kv_cache(cfg: ModelConfig, batch: int, spec: CacheSpec):
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    S = spec.seq_len
    if spec.dtype == "int8":
        z8 = jnp.zeros((batch, S, KV, hd), jnp.int8)
        zs = jnp.zeros((batch, S, KV, 1), jnp.float32)
        return {"k": z8, "v": z8, "k_scale": zs, "v_scale": zs}
    z = jnp.zeros((batch, S, KV, hd), jnp.dtype(spec.dtype))
    return {"k": z, "v": z}


def _quant(x):
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-8
    return jnp.round(x / scale).astype(jnp.int8), scale.astype(jnp.float32)


def _dequant(x8, scale, dtype):
    return (x8.astype(jnp.float32) * scale).astype(dtype)


def update_kv_cache(cache, k_new, v_new, pos):
    """k_new/v_new: (B,1,KV,hd); pos: scalar int32 write index."""
    quantized = "k_scale" in cache
    if quantized:
        k8, ks = _quant(k_new)
        v8, vs = _quant(v_new)
        cache = dict(cache)
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], k8, pos, axis=1)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], v8, pos, axis=1)
        cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], ks, pos, axis=1)
        cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], vs, pos, axis=1)
        return cache
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
    return cache


def read_kv_cache(cache, dtype):
    if "k_scale" in cache:
        return (_dequant(cache["k"], cache["k_scale"], dtype),
                _dequant(cache["v"], cache["v_scale"], dtype))
    return cache["k"].astype(dtype), cache["v"].astype(dtype)


def attention_decode(p, cfg: ModelConfig, x, cache, pos, window: int = 0):
    """One-token decode: x (B,1,D); attends to cache[0..pos] inclusive."""
    B = x.shape[0]
    if cfg.mrope:
        positions = jnp.broadcast_to(pos, (3, B, 1))
    else:
        positions = jnp.broadcast_to(pos, (B, 1))
    q, k_new, v_new = _qkv(p, cfg, x, positions)
    cache = update_kv_cache(cache, k_new, v_new, pos)
    k, v = read_kv_cache(cache, x.dtype)
    S = k.shape[1]
    j = jnp.arange(S)[None, None, None, :]                # (1,1,1,S)
    mask = j <= pos
    if window > 0:
        mask = mask & (j > pos - window)
    out = _sdpa(q, k, v, mask, cfg.q_per_kv) @ p["wo"]
    return sharding.act(out, "batch", None, "dmodel"), cache


def attention_decode_windowed(p, cfg: ModelConfig, x, cache, pos):
    """Rolling-window decode for local attention: cache holds the last W
    positions; slot = pos % W; absolute positions tracked in cache["pos_ids"]."""
    B = x.shape[0]
    W = cache["k"].shape[1]
    positions = jnp.broadcast_to(pos, (B, 1))
    q, k_new, v_new = _qkv(p, cfg, x, positions)
    slot = jnp.remainder(pos, W)
    pos_ids = jax.lax.dynamic_update_slice_in_dim(
        cache["pos_ids"], pos[None].astype(jnp.int32), slot, axis=0)
    cache = dict(cache)
    cache["pos_ids"] = pos_ids
    cache = update_kv_cache(cache, k_new, v_new, slot)
    k, v = read_kv_cache(cache, x.dtype)
    valid = (pos_ids >= 0) & (pos_ids <= pos) & (pos_ids > pos - W)
    mask = valid[None, None, None, :]
    out = _sdpa(q, k, v, mask, cfg.q_per_kv) @ p["wo"]
    return sharding.act(out, "batch", None, "dmodel"), cache


def _fill_cache(cfg: ModelConfig, k, v, spec: CacheSpec):
    """Quantize/cast full-sequence K,V (B,S,KV,hd) into a decode cache."""
    if spec.dtype == "int8":
        k8, ks = _quant(k)
        v8, vs = _quant(v)
        return {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
    dt = jnp.dtype(spec.dtype)
    return {"k": k.astype(dt), "v": v.astype(dt)}


def attention_prefill(p, cfg: ModelConfig, x, positions, window: int, spec: CacheSpec):
    """Full-sequence attention that also emits the populated decode cache."""
    B, S, D = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    out = full_seq_sdpa(cfg, q, k, v, window) @ p["wo"]
    out = sharding.act(out, "batch", "seq", "dmodel")
    if window > 0:
        W = min(window, S)
        abs_pos = jnp.arange(S - W, S, dtype=jnp.int32)
        slots = jnp.remainder(abs_pos, W)          # slot = abs_pos % W
        # place the window into its rolling slots
        rolled = {}
        for kk, vv in _fill_cache(cfg, k[:, S - W:], v[:, S - W:], spec).items():
            rolled[kk] = jnp.zeros_like(vv).at[:, slots].set(vv)
        rolled["pos_ids"] = jnp.zeros((W,), jnp.int32).at[slots].set(abs_pos)
        cache = rolled
    else:
        cache = _fill_cache(cfg, k, v, spec)
    return out, cache


# ----------------------------------------------------------------------- MLP

def init_mlp(key, cfg: ModelConfig, dtype, d_ff: int | None = None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = split_keys(key, 2)
    return {
        "ln": jnp.zeros((D,), dtype),
        "wi_mlp_up": dense_init(ks[0], (D, 2 * F), dtype),
        "wo_mlp": dense_init(ks[1], (F, D), dtype, scale=F ** -0.5),
    }


def mlp(p, x):
    h = rmsnorm(x, p["ln"])
    gu = h @ p["wi_mlp_up"]
    gate, up = jnp.split(gu, 2, axis=-1)
    gate = sharding.act(gate, "batch", "seq", "ff")
    h = jax.nn.silu(gate) * up
    out = h @ p["wo_mlp"]
    return sharding.act(out, "batch", "seq", "dmodel")


# ----------------------------------------------------------------- embeddings

def init_embed(key, cfg: ModelConfig, dtype):
    V = cfg.padded_vocab()
    return {"embedding": dense_init(key, (V, cfg.d_model), dtype, scale=0.02)}


def embed(p, tokens):
    """Token embedding lookup against the vocab-sharded table.

    Explicit shard_map: each vocab shard gathers locally and a (B,S,D) psum
    combines -- the partitioner's default strategy materializes a full-vocab
    one-hot (observed 12 GiB/device), which this avoids."""
    from jax.sharding import PartitionSpec as P

    table = p["embedding"]
    V = table.shape[0]
    mesh = sharding.current_mesh()
    if mesh is None or "model" not in mesh.shape or V % mesh.shape["model"]:
        out = jnp.take(table, tokens, axis=0)
        return sharding.act(out, "batch", "seq", "dmodel")

    dp = sharding.batch_axes_for(tokens.shape[0])
    Vloc = V // mesh.shape["model"]

    def f(tab, toks):
        off = jax.lax.axis_index("model") * Vloc
        idx = toks - off
        inb = (idx >= 0) & (idx < Vloc)
        rows = jnp.take(tab, jnp.clip(idx, 0, Vloc - 1), axis=0)
        rows = jnp.where(inb[..., None], rows, 0)
        return jax.lax.psum(rows, "model")

    out = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("model", None), P(dp, None)),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(table, tokens)
    return sharding.act(out, "batch", "seq", "dmodel")


def unembed_logits(p, x):
    """Logits (B,S,V), vocab-sharded."""
    logits = x @ p["embedding"].T
    return sharding.act(logits, "batch", "seq", "vocab")


def _xent_from_logits(lg, labels, offset, valid_cols):
    """Per-shard xent pieces. lg: (B,S,Vloc) fp32 (already masked); labels
    global ids; offset = first global column of this shard."""
    Vloc = lg.shape[-1]
    m_local = jnp.max(lg, axis=-1)
    idx = labels - offset
    inb = (idx >= 0) & (idx < valid_cols)
    ll = jnp.take_along_axis(lg, jnp.clip(idx, 0, Vloc - 1)[..., None], axis=-1)[..., 0]
    return m_local, ll, inb


def softmax_xent(p_embed, x, labels, vocab_size: int):
    """Cross-entropy over a (possibly model-axis-sharded) vocab, computed with
    an explicit shard_map: local reductions + tiny (B,S) pmax/psum.  This keeps
    the partitioner from all-gathering full logits (~12 GiB/device observed)
    or resharding the embedding table for a label gather."""
    from jax.sharding import PartitionSpec as P

    logits = unembed_logits(p_embed, x)
    V = logits.shape[-1]
    mesh = sharding.current_mesh()

    if mesh is None or "model" not in mesh.shape or V % mesh.shape["model"]:
        lg = logits.astype(jnp.float32)
        if V > vocab_size:
            lg = jnp.where(jnp.arange(V) < vocab_size, lg, -1e30)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - ll)

    dp = sharding.batch_axes_for(logits.shape[0])
    Vloc = V // mesh.shape["model"]

    @jax.custom_jvp
    def pmax_const(v):
        return jax.lax.pmax(v, "model")

    @pmax_const.defjvp
    def _pmax_jvp(primals, tangents):
        # the max is a constant log-shift (cancels analytically) -> zero tangent
        (v,), (dv,) = primals, tangents
        return pmax_const(v), jnp.zeros_like(dv)

    def f(lg, lab):
        shard = jax.lax.axis_index("model")
        offset = shard * Vloc
        lg = lg.astype(jnp.float32)
        if V > vocab_size:
            cols = offset + jnp.arange(Vloc)
            lg = jnp.where(cols < vocab_size, lg, -1e30)
        valid = jnp.minimum(jnp.maximum(vocab_size - offset, 0), Vloc)
        m_local, ll, inb = _xent_from_logits(lg, lab, offset, valid)
        m = pmax_const(m_local)
        z = jax.lax.psum(jnp.sum(jnp.exp(lg - m[..., None]), axis=-1), "model")
        lse = jnp.log(z) + m
        label_logit = jax.lax.psum(jnp.where(inb, ll, 0.0), "model")
        return lse - label_logit

    per_tok = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(dp, None, "model"), P(dp, None)),
        out_specs=P(dp, None),
        check_vma=False,
    )(logits, labels)
    return jnp.mean(per_tok)
