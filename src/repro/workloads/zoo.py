"""Zoo workload generation: `ModelConfig` -> named `ConvLayer` sets.

Every matmul-shaped term in `repro.models.flops` becomes a `ConvLayer` in the
standard GEMM-as-1x1-conv encoding (d_in -> C, d_out -> K, tokens -> P);
the one genuinely convolutional term (the rglru temporal conv) becomes a real
conv layer.  A per-block-kind extractor registry (`BLOCK_EXTRACTORS`) emits
`(role, layer, count)` items per block instance; assembly dedups identical
shapes (e.g. a Q and O projection when `num_heads * head_dim == d_model`, or
a dense FFN and a same-shaped MoE expert) by summing their counts, so the
searched set stays small (4-15 unique layers per model) while the counts keep
the full-model MACs bookkeeping exact.

Sets are generated for one step of a `ShapeConfig`: `ZOO_SHAPE` (a 64-token
training tile, the default) or a deployed shape such as `MOONLIGHT_DECODE`.
A block processes `global_batch * seq_len` tokens in train and prefill and
`global_batch` in decode; the unembed sees every token in train and one per
sequence otherwise.

The contract that keeps generated shapes provably consistent with the repo's
own cost math: `2 * sum(count * layer.macs)` must equal
`forward_flops(cfg, shape)` up to the *documented* non-matmul remainder --
plain attention's scores+PV (at the 64-token tile ctx averages 32; decode
shapes of plain-attention models are not covered yet and fail the check), and
a handful of elementwise gate/normalizer terms.  Generation raises if
coverage falls outside `[1 - MACS_RTOL, 1]`; the measured coverage ships in
`ZooWorkload.coverage` and is pinned by tests.

Departures from a deployed step, by block:

- Routed experts: routing is taken as uniform, so each of the experts a step
  activates sees `tokens * top_k / num_experts` tokens (`routed_tokens`),
  not the uneven split a real router gives.
- MLA at decode runs the absorbed path: per head, q_nope is mapped into the
  latent (`attn_absorb_k`), scores and PV are GEMMs of the H heads against
  the cached latent (C = r + dr -> K = cache for scores, cache -> r for PV,
  P = H, once per sequence), and the latent context is mapped back to v
  (`attn_absorb_v`).  Scores and PV each read the latent cache as their
  weight operand, where a fused kernel reads it once.  Train and prefill
  up-project the latent to per-head keys and values (`attn_kv_b`), and their
  scores+PV are the remainder as for plain attention.
- RoPE, the norms, the router's sigmoid and top-k, and the gates'
  elementwise products are elementwise and left out.

Extractor registry contract (for adding a block kind): an extractor takes the
`ModelConfig` and the `ShapeConfig` and returns
`[(role, ConvLayer, count_per_block), ...]` covering every matmul term of the
matching `_<kind>_flops_per_token` formula in `repro/models/flops.py`
exactly, skipping only sub-quadratic terms -- then the cross-check holds
automatically for every model using that kind.
"""

from __future__ import annotations

import collections
import dataclasses
import functools

from repro.configs.base import ARCH_IDS, ModelConfig, ShapeConfig, get_config
from repro.models.flops import forward_flops
from repro.timeloop.workloads import _TOKENS, MODEL_LAYERS, ConvLayer, fc

# The shape cell every registry zoo set is generated (and cross-checked) at:
# one 64-token training tile, matching the paper workloads' `_TOKENS` GEMM
# encoding. `forward_flops` at this shape uses tokens = 64 and causal average
# context 32.
ZOO_SHAPE = ShapeConfig(name="zoo_tile", seq_len=_TOKENS, global_batch=1,
                        kind="train")

# One decode step of Moonlight-16B-A3B as deployed: 128 sequences (the batch
# of `SHAPES["decode_32k"]`), each with a full cache of its 8192-token context.
MOONLIGHT_DECODE = ShapeConfig(name="decode_8k_b128", seq_len=8192,
                               global_batch=128, kind="decode")

# Measured non-matmul remainder across the 10-model zoo: 0.03%-0.54%, worst
# on smollm-360m (smallest d_model, so the skipped scores+PV and elementwise
# terms weigh the most); generation fails loudly outside [1 - MACS_RTOL, 1].
MACS_RTOL = 0.01

_Item = tuple[str, ConvLayer, int]


def step_tokens(shape: ShapeConfig) -> int:
    """Tokens a block processes in one step of `shape`."""
    if shape.kind == "decode":
        return shape.global_batch
    return shape.global_batch * shape.seq_len


def routed_tokens(tokens: int, top_k: int,
                  num_experts: int) -> list[tuple[int, int]]:
    """Uniform routing of `tokens`, each to `top_k` of `num_experts`
    experts: `[(tokens per expert, experts), ...]`, as even as whole tokens
    allow (one group when `tokens * top_k` divides evenly; experts left idle
    when there are fewer routed slots than experts)."""
    slots = tokens * top_k
    active = min(num_experts, slots)
    per, extra = divmod(slots, active)
    return [(t, n) for t, n in ((per + 1, extra), (per, active - extra)) if n]


def _attn_items(cfg: ModelConfig, tokens: int) -> list[_Item]:
    # proj = 2*D*(H + 2*KV)*hd + 2*H*hd*D; scores+pv (2*2*ctx*H*hd) skipped.
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return [
        ("attn_q", fc("attn_q", D, H * hd, tokens), 1),
        ("attn_kv", fc("attn_kv", D, KV * hd, tokens), 2),
        ("attn_o", fc("attn_o", H * hd, D, tokens), 1),
    ]


def _mla_items(cfg: ModelConfig, shape: ShapeConfig) -> list[_Item]:
    # flops.py `_mla_flops_per_token`: q, kv_a and o always; decode absorbs
    # the up-projections and attends over the latent cache (exact), train and
    # prefill up-project with kv_b and skip scores+pv.
    D, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    T = step_tokens(shape)
    items = [
        ("attn_q", fc("attn_q", D, H * (dn + dr), T), 1),
        ("attn_kv_a", fc("attn_kv_a", D, r + dr, T), 1),
    ]
    if shape.kind == "decode":
        B, S = shape.global_batch, shape.seq_len
        items += [
            ("attn_absorb_k", fc("attn_absorb_k", dn, r, B), H),
            ("attn_scores", fc("attn_scores", r + dr, S, H), B),
            ("attn_pv", fc("attn_pv", S, r, H), B),
            ("attn_absorb_v", fc("attn_absorb_v", r, dv, B), H),
        ]
    else:
        items.append(("attn_kv_b", fc("attn_kv_b", r, H * (dn + dv), T), 1))
    items.append(("attn_o", fc("attn_o", H * dv, D, T), 1))
    return items


def _attention(cfg: ModelConfig, shape: ShapeConfig) -> list[_Item]:
    if cfg.mla:
        return _mla_items(cfg, shape)
    return _attn_items(cfg, step_tokens(shape))


def _mlp_items(cfg: ModelConfig, tokens: int, d_ff: int | None = None,
               role: str = "mlp") -> list[_Item]:
    # 6*D*d_ff = gated up + gate (2x) + down (1x).
    D, F = cfg.d_model, cfg.d_ff if d_ff is None else d_ff
    if not F:
        return []
    return [
        (f"{role}_up", fc(f"{role}_up", D, F, tokens), 2),
        (f"{role}_down", fc(f"{role}_down", F, D, tokens), 1),
    ]


def _moe_items(cfg: ModelConfig, shape: ShapeConfig) -> list[_Item]:
    # router = 2*D*E, shared = 6*D*(n_shared*d_ff) as one MLP, routed experts
    # = top_k * 6*D*d_ff, each expert at its routed token count.
    D, E, F = cfg.d_model, cfg.num_experts, cfg.d_ff
    T = step_tokens(shape)
    items = [("moe_router", fc("moe_router", D, E, T), 1)]
    items += _mlp_items(cfg, T, cfg.num_shared_experts * F, role="shared")
    groups = routed_tokens(T, cfg.top_k, E)
    for t, n in groups:
        tag = f"_{t}tok" if len(groups) > 1 else ""
        items += [
            (f"moe_up{tag}", fc(f"moe_up{tag}", D, F, t), 2 * n),
            (f"moe_down{tag}", fc(f"moe_down{tag}", F, D, t), n),
        ]
    return items


def _mlstm_items(cfg: ModelConfig, shape: ShapeConfig) -> list[_Item]:
    # proj = 2*D*Din*2 + 2*Din*D + 3*2*Din*dh (+ 2*4*Din elementwise, skipped);
    # cell = 4*Lc*Din (intra-chunk, Lc = mlstm_chunk in train, 1 in decode)
    # + 6*dh*Din.
    D = cfg.d_model
    Din = 2 * D
    dh = Din // cfg.num_heads
    Lc = 1 if shape.kind == "decode" else cfg.mlstm_chunk
    T = step_tokens(shape)
    return [
        ("mlstm_in", fc("mlstm_in", D, Din, T), 2),
        ("mlstm_out", fc("mlstm_out", Din, D, T), 1),
        ("mlstm_qkv", fc("mlstm_qkv", Din, dh, T), 3),
        ("mlstm_intra", fc("mlstm_intra", Lc, Din, T), 2),
        ("mlstm_cell", fc("mlstm_cell", dh, Din, T), 3),
    ]


def _slstm_items(cfg: ModelConfig, shape: ShapeConfig) -> list[_Item]:
    # 4*2*D*D (gates) + 4*2*D*dh (recurrent) + 2*D*D (out) + 6*D*F (FFN);
    # fully matmul -- this extractor is exact.
    D = cfg.d_model
    dh = D // cfg.num_heads
    F = ((4 * D // 3 + 63) // 64) * 64
    T = step_tokens(shape)
    return [
        ("slstm_gates", fc("slstm_gates", D, D, T), 4),
        ("slstm_rec", fc("slstm_rec", D, dh, T), 4),
        ("slstm_out", fc("slstm_out", D, D, T), 1),
        ("slstm_ffn_up", fc("slstm_ffn_up", D, F, T), 2),
        ("slstm_ffn_down", fc("slstm_ffn_down", F, D, T), 1),
    ]


def _rglru_items(cfg: ModelConfig, shape: ShapeConfig) -> list[_Item]:
    # 5*2*D*D (gate/proj matmuls) + 2*W*D temporal conv (+ 12*D elementwise,
    # skipped).  The conv is a real depthwise temporal conv over the token
    # axis: R = conv_width taps, K = d_model channels.
    D, W = cfg.d_model, cfg.rglru_conv_width
    T = step_tokens(shape)
    conv = ConvLayer(name="rglru_conv", R=W, S=1, P=T, Q=1, C=1, K=D)
    return [
        ("rglru_proj", fc("rglru_proj", D, D, T), 5),
        ("rglru_conv", conv, 1),
    ]


BLOCK_EXTRACTORS = {
    "attn": lambda cfg, sh: _attention(cfg, sh) + _mlp_items(
        cfg, step_tokens(sh)),
    # local attention narrows the (skipped) scores context only; the
    # projections and FFN are identical to global attention.
    "local_attn": lambda cfg, sh: _attention(cfg, sh) + _mlp_items(
        cfg, step_tokens(sh)),
    "moe": lambda cfg, sh: _attention(cfg, sh) + _moe_items(cfg, sh),
    # a MoE model's leading dense layers (`ModelConfig.dense_layers`)
    "dense": lambda cfg, sh: _attention(cfg, sh) + _mlp_items(
        cfg, step_tokens(sh), cfg.dense_d_ff, role="dense"),
    "mlstm": _mlstm_items,
    "slstm": _slstm_items,
    "rglru": lambda cfg, sh: _rglru_items(cfg, sh) + _mlp_items(
        cfg, step_tokens(sh)),
}


@dataclasses.dataclass(frozen=True)
class ZooWorkload:
    """A generated workload set plus its MACs-vs-flops audit trail."""

    arch: str                       # dashed config id ("qwen3-14b")
    name: str                       # registry name ("qwen3_14b")
    layers: tuple[ConvLayer, ...]   # unique shapes, first-occurrence order
    counts: tuple[int, ...]         # full-model replication per layer
    total_macs: int                 # sum(count * layer.macs)
    model_flops: float              # forward_flops(cfg, shape)
    coverage: float                 # 2 * total_macs / model_flops
    shape: ShapeConfig = ZOO_SHAPE  # the step the set is generated for


def _norm(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


ZOO_NAMES: tuple[str, ...] = tuple(_norm(a) for a in ARCH_IDS)
_ARCH_BY_NAME: dict[str, str] = {_norm(a): a for a in ARCH_IDS}


def generate_workload(arch: str, cfg: ModelConfig | None = None,
                      tolerance: float = MACS_RTOL, *,
                      shape: ShapeConfig = ZOO_SHAPE) -> ZooWorkload:
    """Build (and MACs-cross-check) the workload set of one step of `shape`
    for one model config."""
    cfg = cfg if cfg is not None else get_config(arch)
    name = _norm(arch)
    order: dict[tuple, list] = {}  # shape key -> [ConvLayer, count]

    def add(role: str, layer: ConvLayer, count: int) -> None:
        key = (layer.R, layer.S, layer.P, layer.Q, layer.C, layer.K,
               layer.stride)
        if key in order:
            order[key][1] += count
        else:
            order[key] = [
                dataclasses.replace(layer, name=f"{name}-{role}"), count]

    for kind, n_blocks in collections.Counter(cfg.layer_kinds()).items():
        if kind not in BLOCK_EXTRACTORS:
            raise ValueError(
                f"{arch}: no extractor for block kind {kind!r}; known: "
                f"{sorted(BLOCK_EXTRACTORS)}")
        for role, layer, count in BLOCK_EXTRACTORS[kind](cfg, shape):
            add(role, layer, count * n_blocks)

    # Unembed: 2 * D * padded_vocab for every token in train, for one token
    # per sequence in prefill and decode.
    T = step_tokens(shape)
    logits = T if shape.kind == "train" else shape.global_batch
    add("unembed", fc("unembed", cfg.d_model, cfg.padded_vocab(), logits), 1)

    if cfg.family == "encdec" and cfg.encoder_layers:
        # Encoder blocks run at the source tile S_src = max(S // 8, 16): a
        # genuinely smaller-token GEMM, kept as distinct `enc_*` shapes.
        enc_tokens = shape.global_batch * max(shape.seq_len // 8, 16)
        for role, layer, count in (_attn_items(cfg, enc_tokens)
                                   + _mlp_items(cfg, enc_tokens)):
            add(f"enc_{role}", layer, count * cfg.encoder_layers)
        # Decoder cross-attention: flops.py counts Q/K/V projections but no
        # output projection (`cross` has no `2*H*hd*D` term) -- mirror that.
        for role, layer, count in _attn_items(cfg, T):
            if role != "attn_o":
                add(role, layer, count * cfg.num_layers)

    layers = tuple(v[0] for v in order.values())
    counts = tuple(int(v[1]) for v in order.values())
    total_macs = sum(c * l.macs for c, l in zip(counts, layers))
    flops = forward_flops(cfg, shape)
    coverage = 2.0 * total_macs / flops
    if not (1.0 - tolerance <= coverage <= 1.0 + 1e-9):
        raise ValueError(
            f"zoo workload {name}: extracted MACs cover {coverage:.4f} of "
            f"forward_flops (2*{total_macs} vs {flops:.6g}); expected within "
            f"[{1.0 - tolerance:.3f}, 1.0] -- extractor and "
            "repro/models/flops.py disagree")
    return ZooWorkload(arch=arch, name=name, layers=layers, counts=counts,
                       total_macs=total_macs, model_flops=flops,
                       coverage=coverage, shape=shape)


@functools.lru_cache(maxsize=None)
def _cached_workload(arch: str) -> ZooWorkload:
    return generate_workload(arch)


def zoo_workload(name: str) -> ZooWorkload:
    """Generated (and cross-checked) workload for a zoo model name (dashed
    arch ids and underscored registry names both accepted)."""
    key = _norm(name)
    if key not in _ARCH_BY_NAME:
        raise ValueError(
            f"unknown zoo model {name!r}; known: {sorted(ZOO_NAMES)}")
    return _cached_workload(_ARCH_BY_NAME[key])


def workload_set(name: str) -> list[ConvLayer]:
    """`MODEL_LAYERS`-compatible layer list for a zoo model name."""
    return list(zoo_workload(name).layers)


def known_workloads() -> tuple[str, ...]:
    """Every addressable workload name: the paper's four + the zoo."""
    return tuple(sorted(MODEL_LAYERS)) + tuple(sorted(ZOO_NAMES))


def resolve_workload(name: str) -> list[ConvLayer]:
    """Resolve any workload name -- paper set ("resnet") or zoo model
    ("llama4_maverick_400b_a17b", dashed aliases accepted) -- to layers."""
    if name in MODEL_LAYERS:
        return list(MODEL_LAYERS[name])
    if _norm(name) in _ARCH_BY_NAME:
        return workload_set(name)
    raise ValueError(
        f"unknown workload {name!r}; known: {list(known_workloads())}")
