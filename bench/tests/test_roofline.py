"""The forward's counted bytes and operations against `_forward` itself."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.timeloop import batch_jax

import roofline

ROWS = 256


def shapes(dtype):
    f, i = dtype, jnp.int32
    return [jax.ShapeDtypeStruct(s, t) for s, t in (
        ((ROWS, 5, 6), f), ((ROWS, 6), i), ((ROWS, 6), i), ((ROWS, 15), f),
        ((ROWS, 8), f))]


@pytest.mark.parametrize("dtype,width", [(jnp.float32, 4)])
def test_bytes_per_row_match_forward_shapes(dtype, width):
    args = shapes(dtype)
    out = jax.eval_shape(lambda *a: batch_jax._forward(*a, mode="jnp"), *args)
    moved = sum(a.size * a.dtype.itemsize for a in args)
    moved += sum(o.size * o.dtype.itemsize for o in out.values())
    assert moved == ROWS * roofline.bytes_per_row(width)


def test_counted_ops_do_not_exceed_the_compiled_forward():
    """The count is a floor of the model's arithmetic: XLA's own count of
    the compiled forward (which adds compares, selects and padding) is
    higher, so the least time is never overstated by the ops bound."""
    compiled = jax.jit(lambda *a: batch_jax._forward(*a, mode="jnp")).lower(
        *shapes(jnp.float32)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert roofline.OPS_PER_ROW * ROWS <= cost["flops"]


def test_least_seconds_names_its_bound():
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    t, bound = roofline.least_seconds(1000, 4, peaks)
    assert bound == "bytes"
    assert t == pytest.approx(1000 * roofline.bytes_per_row(4) / 819e9)
    t, bound = roofline.least_seconds(1000, 4, {"hbm_bytes_per_s": 1e30,
                                                "bf16_flops_per_s": 1e9})
    assert bound == "ops"
    assert np.isclose(t, 1000 * roofline.OPS_PER_ROW / 1e9)
