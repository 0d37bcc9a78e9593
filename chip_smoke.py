"""Smoke test of the co-design search and service on one TPU chip.

    python chip_smoke.py

Drives the main path once through its user entry points, at the paper's
search setup (Eyeriss-168 budget, 150-candidate pools, 30 warmup trials per
inner search, the paper's layer shapes) with the trial budgets cut to fit one
chip call, and checks every result against the repo's own references:

  device   JAX's first device is a TPU; anything else fails here, before any
           work, so nothing runs on the CPU in the chip's place.
  kernel   `batch_jax.forward_device(mode="pallas")` in float32 on the chip
           vs the NumPy f64 engine `batch.evaluate_batch`, on pools for every
           layer of the four paper sets: masks equal, EDP within 1e-6
           relative on valid rows; the compiled `_forward` holds the kernel.
  search   `CodesignEngine(config).run(resnet)` with the jax backend and the
           speculative strategy; every layer's best mapping re-scored by the
           scalar `model.evaluate` must be valid and match the reported EDP.
  service  `CodesignService` answers dqn, transformer and a zoo workload,
           checked the same way; resubmitted against the same design store,
           the warm pass must replay with zero store misses and equal results.

Each phase prints its wall-clock and compile time.  The last line of
standard output is `{"ok": true, "device": {...}}`, printed only when every
check passed; any failure exits non-zero.
"""

from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Trial budgets.  The paper runs 50 outer trials over inner searches of 250;
# on one v5e, 6 outer trials at the full inner budget already took 1453 s
# (the stacked GP runs in emulated f64 on the chip), so the smoke run cuts
# outer trials first and inner trials second.  Pools, the inner warmup and
# the layer shapes stay as in the paper.
HW_TRIALS = 3
HW_WARMUP = 2
SW_TRIALS = 40
KERNEL_POOL = 256
SERVICE_WORKLOADS = ("dqn", "transformer", "smollm_360m")
EDP_RTOL = 1e-6


class CheckFailed(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


class Phase:
    """Wall clock and XLA compile time (summed from jax.monitoring) of one
    phase; prints them when the phase ends."""

    compile_s = 0.0
    n_compiles = 0

    @classmethod
    def listen(cls, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            cls.compile_s += duration
            cls.n_compiles += 1

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.n0 = Phase.compile_s, Phase.n_compiles
        print(f"[{self.name}] start", flush=True)
        return self

    def __exit__(self, exc_type, *_):
        wall = time.perf_counter() - self.t0
        status = "done" if exc_type is None else "FAILED"
        print(f"[{self.name}] {status}: wall {wall:.1f}s, compile "
              f"{Phase.compile_s - self.c0:.1f}s "
              f"({Phase.n_compiles - self.n0} programs)", flush=True)


def device_info() -> dict:
    """The chip as JAX reports it; fails unless the first device is a TPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise CheckFailed(f"no TPU found: {e}") from None
    d = devices[0]
    check(d.platform == "tpu",
          f"no TPU found: JAX's first device is {d.platform!r} "
          f"({d.device_kind}); this smoke test runs on the chip only")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def kernel_parity() -> None:
    """The fused forward with the Pallas kernel vs the NumPy f64 engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.timeloop import MODEL_LAYERS, eyeriss_168
    from repro.timeloop import batch as tlb
    from repro.timeloop import batch_jax as jtlb
    from repro.timeloop.mapping import sample_constrained_batch

    n = KERNEL_POOL
    resolved = jtlb._resolve(None, None)
    print(f"  engine resolves to mode={resolved[0]} dtype={resolved[1]}")
    check(resolved == ("pallas", "float32"),
          f"batch_jax resolved to {resolved}, not ('pallas', 'float32')")
    f32, i32 = jnp.float32, jnp.int32
    shapes = [jax.ShapeDtypeStruct(s, t) for s, t in (
        ((n, 5, 6), f32), ((n, 6), i32), ((n, 6), i32), ((n, 15), f32),
        ((n, 8), f32))]
    text = jtlb._forward.lower(*shapes, mode="pallas").compile().as_text()
    check("tpu_custom_call" in text,
          "compiled _forward holds no tpu_custom_call: the Pallas kernel is "
          "not in the program")
    print("  compiled _forward holds tpu_custom_call")

    hw = eyeriss_168()
    rng = np.random.default_rng(0)
    worst, rows, n_valid = 0.0, 0, 0
    for set_name in ("resnet", "dqn", "mlp", "transformer"):
        for layer in MODEL_LAYERS[set_name]:
            # Half a valid pool, half raw constrained draws (which the GB
            # capacity check partly rejects), so the masks are tested too.
            valid = tlb.sample_valid_pool(rng, hw, layer, n // 2)
            check(valid is not None, f"{layer.name}: no valid pool")
            raw = tlb.MappingBatch(
                *sample_constrained_batch(rng, hw, layer, n - n // 2))
            mb = tlb.concat([valid, raw])
            ref = tlb.evaluate_batch(hw, mb, layer)
            out = jtlb.forward_device(hw, mb, layer, mode="pallas",
                                      dtype="float32")
            got_valid = np.asarray(out["valid"])
            got_edp = np.asarray(out["edp"], np.float64)
            check(np.array_equal(got_valid, ref["valid"]),
                  f"{layer.name}: validity masks differ on "
                  f"{int((got_valid != ref['valid']).sum())} rows")
            v = ref["valid"]
            check(np.isinf(got_edp[~v]).all(),
                  f"{layer.name}: finite EDP on an invalid row")
            err = float(np.max(np.abs(got_edp[v] - ref["edp"][v])
                               / ref["edp"][v]))
            check(err <= EDP_RTOL,
                  f"{layer.name}: EDP relative error {err:.3e} > {EDP_RTOL}")
            worst = max(worst, err)
            rows += len(mb)
            n_valid += int(v.sum())
    print(f"  kernel parity: {rows} rows over 12 layers ({n_valid} valid), "
          f"masks equal, max EDP rel err {worst:.3e} (bar {EDP_RTOL})")


def check_result(label: str, res, layers) -> None:
    """A finished co-design result, re-scored by the scalar reference."""
    from repro.timeloop.mapping import mapping_is_valid
    from repro.timeloop.model import evaluate

    check(math.isfinite(res.best_model_edp),
          f"{label}: best model EDP {res.best_model_edp} is not finite")
    worst = 0.0
    for layer in layers:
        m = res.best_mappings[layer.name]
        ok, reason = mapping_is_valid(m, res.best_hw, layer)
        check(ok, f"{label}: best mapping of {layer.name} is invalid "
                  f"({reason})")
        ref = evaluate(res.best_hw, m, layer).edp
        err = abs(ref - res.layer_edps[layer.name]) / ref
        check(err <= EDP_RTOL,
              f"{label}: {layer.name} EDP {res.layer_edps[layer.name]:.6e} "
              f"vs scalar reference {ref:.6e} (rel err {err:.3e})")
        worst = max(worst, err)
    print(f"  {label}: model EDP {res.best_model_edp:.6e}, {len(layers)} "
          f"layers re-scored by model.evaluate, max rel err {worst:.3e}")


def search_config(seed: int):
    from repro.core import (CodesignConfig, EngineConfig, HWSearchConfig,
                            SWSearchConfig)

    return CodesignConfig(
        sw=SWSearchConfig(n_trials=SW_TRIALS),
        hw=HWSearchConfig(n_trials=HW_TRIALS, n_warmup=HW_WARMUP),
        engine=EngineConfig(backend="jax", strategy="speculative"),
        seed=seed)


def search() -> None:
    from repro.core import CodesignEngine
    from repro.timeloop import MODEL_LAYERS

    layers = MODEL_LAYERS["resnet"]
    engine = CodesignEngine(search_config(0))
    check(engine.backend == "jax", f"engine backend is {engine.backend}")
    res = engine.run(layers)
    check_result("resnet search", res, layers)


def service() -> None:
    from repro.core import ServiceConfig
    from repro.service import CodesignService, ServiceRequest
    from repro.workloads.zoo import resolve_workload

    requests = [
        ServiceRequest(layers=tuple(resolve_workload(name)),
                       config=search_config(i),
                       rid=name)
        for i, name in enumerate(SERVICE_WORKLOADS)]
    with tempfile.TemporaryDirectory(prefix="design_store_") as store:
        passes = []
        for label in ("cold", "warm"):
            t0 = time.perf_counter()
            with CodesignService(ServiceConfig(store_dir=store)) as svc:
                for r in requests:
                    svc.submit(r)
                responses = svc.run()
            print(f"  {label} pass: {len(responses)} responses in "
                  f"{time.perf_counter() - t0:.1f}s")
            for r in requests:
                resp = responses[r.rid]
                stats = resp.result.stats
                print(f"    {r.rid}: latency {resp.latency_s:.1f}s, store "
                      f"{stats['store_hits']} hits / "
                      f"{stats['store_misses']} misses")
                check_result(f"{label} {r.rid}", resp.result, r.layers)
            passes.append(responses)
    cold, warm = passes
    for r in requests:
        a, b = cold[r.rid].result, warm[r.rid].result
        check(warm[r.rid].result.stats["store_misses"] == 0,
              f"warm {r.rid}: {b.stats['store_misses']} store misses")
        check(a.best_hw == b.best_hw and a.best_model_edp == b.best_model_edp
              and a.layer_edps == b.layer_edps
              and a.best_mappings == b.best_mappings,
              f"warm {r.rid}: replay differs from the cold result")
    print("  warm pass replayed every request from the store, results equal")


def main() -> int:
    try:
        with Phase("device"):
            dev = device_info()
            print(f"  device: {dev['platform']} {dev['kind']} "
                  f"x{dev['count']}")
        sys.path.insert(0, os.path.join(HERE, "src"))
        try:
            from repro.jax_cache import enable_compile_cache
        except ImportError as e:
            raise CheckFailed(f"the repro package is not next to this "
                              f"script ({e})") from None
        import jax.monitoring

        from repro.core import HWSearchConfig, SWSearchConfig

        print(f"  compile cache: {enable_compile_cache()}")
        jax.monitoring.register_event_duration_secs_listener(Phase.listen)
        paper_hw, paper_sw = HWSearchConfig(), SWSearchConfig()
        print(f"cuts: outer trials {paper_hw.n_trials} -> {HW_TRIALS} "
              f"({paper_hw.n_warmup} -> {HW_WARMUP} warmup), inner trials "
              f"{paper_sw.n_trials} -> {SW_TRIALS}; inner warmup "
              f"({paper_sw.n_warmup}), pools ({paper_sw.pool_size}) and layer "
              f"shapes as in the paper")
        with Phase("kernel"):
            kernel_parity()
        with Phase("search"):
            search()
        with Phase("service"):
            service()
    except CheckFailed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
