"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed alongside jax, compiles
for a chip that is described and not attached, and refuses what the chip's
compiler would refuse (unsupported primitives in a Pallas kernel, unaligned
blocks, programs that do not fit).  The row counts are the ones the search
dispatches: 256 for one probe's pool bucket, 4096 for a speculative
`spec_k=4` fan-out over four layers.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this module.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gp
from repro.kernels.edp_reduce import edp_reduce
from repro.timeloop import batch_jax as jtlb

STACK_BUCKET = 256  # gp._bucket_stack(250): the inner search's last GP bucket
N_FEATURES = 14


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    # A compile for a described chip can be written to a persistent cache
    # but never read back without one; keep the cache out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("rows", [256, 4096])
def test_edp_reduce_compiles_for_v5e(one_chip, rows):
    shapes = [(rows, 2, 6), (rows, 2, 3, 6), (rows, 2, 3), (rows, 6),
              (rows, 7)]
    compiled = jax.jit(lambda *a: edp_reduce(*a, interpret=False)).lower(
        *(_spec(one_chip, s) for s in shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_forward_pallas_compiles_for_v5e(one_chip):
    rows = 4096
    compiled = jtlb._forward.lower(
        _spec(one_chip, (rows, 5, 6)),
        _spec(one_chip, (rows, 6), jnp.int32),
        _spec(one_chip, (rows, 6), jnp.int32),
        _spec(one_chip, (rows, 15)),
        _spec(one_chip, (rows, 8)),
        mode="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def _stack_params(one_chip, runs):
    init = dict(gp._init_params("linear", N_FEATURES),
                mean_const=jnp.zeros(()), log_tau=jnp.zeros(()))
    return {k: _spec(one_chip, (runs, *v.shape), jnp.float64)
            for k, v in init.items()}


# (4, 256): the inner search's last bucket at the paper's 250 trials;
# (16, 32) and (16, 40): the ResNet cell's widest stacks, both through the
# Woodbury NLL; (60, 40): the Moonlight decode cell's (4 probes x 15 layers).
@pytest.mark.parametrize("runs, bucket", [(4, STACK_BUCKET), (16, 32),
                                          (16, 40), (60, 40)])
def test_gp_stack_fit_compiles_for_v5e_in_f64(one_chip, runs, bucket):
    with jax.enable_x64(True):
        compiled = gp._fit_stack.lower(
            _stack_params(one_chip, runs),
            _spec(one_chip, (runs, bucket, N_FEATURES), jnp.float64),
            _spec(one_chip, (runs, bucket), jnp.float64),
            _spec(one_chip, (runs, bucket), jnp.float64),
            kind="linear", steps=80, train_tau=True).compile()
    assert compiled.memory_analysis() is not None


def test_gp_stack_score_compiles_for_v5e_in_f64(one_chip):
    runs, bucket, pool = 16, 40, 256
    with jax.enable_x64(True):
        compiled = gp._score_stack.lower(
            _stack_params(one_chip, runs),
            _spec(one_chip, (runs, bucket, N_FEATURES), jnp.float64),
            _spec(one_chip, (runs, bucket), jnp.float64),
            _spec(one_chip, (runs, bucket), jnp.float64),
            _spec(one_chip, (runs, pool, N_FEATURES), jnp.float64),
            _spec(one_chip, (runs, 1), jnp.float64),
            kind="linear", acq_fn=gp._acq_device_cached("lcb", 1.0)).compile()
    assert compiled.memory_analysis() is not None
