"""Dry-run analysis machinery: HLO collective parsing, scan-undercount
demonstration, depth variants, analytic FLOPs sanity."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.models.flops import cell_bytes, cell_flops, param_count


def test_cost_analysis_counts_scan_body_once():
    """The documented XLA pitfall that motivates depth extrapolation."""
    def f_scan(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=8)
        return h

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ca = jax.jit(f_scan).lower(x, w).compile().cost_analysis()
    one_iter = 2 * 64 * 128 * 128
    assert abs(ca["flops"] - one_iter) / one_iter < 0.1  # body counted ONCE


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
  %all-gather = f32[16,1024]{1,0} all-gather(%p0), channel_id=1
  %ar = bf16[8,256]{1,0} all-reduce(%p1), channel_id=2
  %rs.1 = f32[4,4]{1,0} reduce-scatter(%p2), channel_id=3
  %cp = u8[100]{0} collective-permute(%p3), channel_id=4
  %ags = f32[2,2]{1,0} all-gather-start(%p4), channel_id=5
  %agd = f32[2,2]{1,0} all-gather-done(%ags), channel_id=5
  %noise = f32[9,9]{1,0} add(%a, %b)
"""
    got = collective_bytes(hlo)
    assert got["all-gather"] == 16 * 1024 * 4 + 2 * 2 * 4  # incl. -start, not -done
    assert got["all-reduce"] == 8 * 256 * 2
    assert got["reduce-scatter"] == 4 * 4 * 4
    assert got["collective-permute"] == 100
    assert got["total"] == sum(v for k, v in got.items()
                               if k not in ("total", "ops"))


def test_depth_variant_preserves_pattern():
    from repro.launch.dryrun import _depth_variant

    cfg = get_config("llama4-maverick-400b-a17b")  # pattern period 2
    v1 = _depth_variant(cfg, 1)
    assert v1.num_layers == 2 and v1.block_pattern == cfg.block_pattern
    v2 = _depth_variant(cfg, 2)
    assert v2.num_layers == 4
    enc = _depth_variant(get_config("seamless-m4t-large-v2"), 2)
    assert enc.encoder_layers == 2 and enc.num_layers == 2


@pytest.mark.parametrize("arch,lo,hi", [
    ("smollm-360m", 0.3e9, 0.5e9),
    ("phi3-medium-14b", 12e9, 16e9),
    ("qwen3-14b", 13e9, 17e9),
    ("stablelm-12b", 11e9, 14e9),
    ("qwen2-vl-72b", 65e9, 80e9),
    ("moonshot-v1-16b-a3b", 15e9, 17e9),   # "16B" total, 15.96e9 counted
])
def test_param_count_plausible(arch, lo, hi):
    n = param_count(get_config(arch))
    assert lo < n < hi, (arch, n / 1e9)


def test_analytic_flops_train_matches_6nd():
    """Dense-arch training FLOPs must track 6*N*D within ~35% (attention +
    vocab overheads on top of the parameter term)."""
    cfg = get_config("qwen3-14b")
    shape = SHAPES["train_4k"]
    af = cell_flops(cfg, shape)
    n = param_count(cfg)
    tokens = shape.global_batch * shape.seq_len
    six_nd = 6.0 * n * tokens
    assert 0.9 * six_nd < af["useful"] < 1.6 * six_nd


def test_analytic_bytes_decode_dominated_by_params_and_cache():
    cfg = get_config("phi3-medium-14b")
    b = cell_bytes(cfg, SHAPES["decode_32k"], 256, 16)["bytes_per_dev"]
    params_dev = param_count(cfg) * 4 / 256
    assert b >= params_dev  # at least one full param read per step