"""Substrate tests: optimizer, data pipeline, checkpointing, fault tolerance."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_smoke_config
from repro.checkpoint import checkpoint as ckpt
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticSource
from repro.optim import adamw
from repro.runtime.fault_tolerance import (InjectedFault, ResilientLoop,
                                           StragglerMonitor)


def test_adamw_decreases_quadratic():
    cfg = adamw.AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=1,
                            total_steps=200, clip_norm=100.0)
    params = {"w": jnp.array([3.0, -2.0, 1.0])}
    state = adamw.init_state(cfg, params)

    def loss_fn(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(150):
        g = jax.grad(loss_fn)(params)
        params, state, _ = adamw.apply_updates(cfg, params, state, g)
    assert float(loss_fn(params)) < 1e-2


def test_grad_clip_norm():
    g = {"a": jnp.full((4,), 100.0)}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(200.0)
    assert float(adamw.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_int8_grad_compression_bounded_error():
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(128,)), jnp.float32)}
    deq = adamw.decompress_int8(adamw.compress_int8(g))
    err = float(jnp.max(jnp.abs(deq["w"] - g["w"])))
    assert err <= float(jnp.max(jnp.abs(g["w"]))) / 127 + 1e-6


def test_schedule_warmup_and_decay():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100)
    assert float(adamw.schedule(cfg, jnp.asarray(5))) == pytest.approx(0.5)
    assert float(adamw.schedule(cfg, jnp.asarray(10))) == pytest.approx(1.0, rel=1e-2)
    assert float(adamw.schedule(cfg, jnp.asarray(100))) == pytest.approx(cfg.min_lr_frac, rel=1e-2)


def test_prefetcher_matches_direct():
    cfg = get_smoke_config("smollm-360m")
    shape = ShapeConfig("t", 16, 4, "train")
    src = SyntheticSource(cfg, shape, DataConfig(seed=1))
    pf = Prefetcher(src, start_step=0)
    try:
        for want in range(3):
            step, batch = next(pf)
            assert step == want
            direct = src.batch(step)
            assert np.array_equal(batch["tokens"], direct["tokens"])
    finally:
        pf.close()


def test_checkpoint_roundtrip(tmp_path):
    state = {"params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
             "opt": {"step": jnp.asarray(7, jnp.int32)}}
    ckpt.save(str(tmp_path), 7, state)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    restored, step = ckpt.restore(str(tmp_path), like)
    assert step == 7
    assert np.array_equal(restored["params"]["w"], np.asarray(state["params"]["w"]))
    assert ckpt.latest_step(str(tmp_path)) == 7


def test_checkpoint_latest_pointer_advances(tmp_path):
    state = {"x": jnp.zeros(3)}
    ckpt.save(str(tmp_path), 1, state)
    ckpt.save(str(tmp_path), 2, state)
    assert ckpt.latest_step(str(tmp_path)) == 2


class _CountingSource:
    def __init__(self):
        self.calls = []

    def batch(self, step):
        self.calls.append(step)
        return {"step": step}


def test_resilient_loop_restarts_and_replays(tmp_path):
    """Injected faults must restore from the latest checkpoint and replay the
    exact same data steps (determinism contract)."""
    src = _CountingSource()
    trace = []

    def step_fn(state, batch):
        trace.append(batch["step"])
        return state + 1, {"loss": 0.0}

    loop = ResilientLoop(step_fn, src, str(tmp_path), save_every=4)
    state, step, mlog, monitor = loop.run(
        jnp.asarray(0), 0, 12, fault_schedule={6, 9})
    assert step == 12
    # state was rolled back on each restart, so it counts only the steps on
    # the surviving path: exactly 12
    assert int(state) == 12
    assert len(trace) > 12                    # replays actually executed
    assert trace.count(4) >= 2 or trace.count(8) >= 2  # same data replayed


def test_straggler_monitor_flags_outlier():
    mon = StragglerMonitor(z_threshold=3.0)
    for _ in range(20):
        mon.observe(0.1 + np.random.default_rng(0).normal() * 0)
    assert bool(mon.observe(10.0))
    assert mon.flagged == 1


def test_sharding_filter_spec():
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import _filter_spec
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    spec = _filter_spec(mesh, (("pod", "data"), None, "model"))
    assert spec == P(("data",), None, None)


def test_param_spec_roles():
    from repro.parallel.sharding import AxisRules, param_spec
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = AxisRules()
    spec = param_spec("blocks/pos0/mlp/wi_mlp_up", (4, 64, 256), mesh, rules)
    assert spec[2] == "model" and spec[1] == "data"  # ff + fsdp
    spec = param_spec("embed/embedding", (512, 64), mesh, rules, stacked=False)
    assert spec[0] == "model"                         # vocab
    spec = param_spec("blocks/pos0/moe/expert_wi", (4, 8, 64, 128), mesh, rules)
    assert spec[1] == "model"                         # expert axis