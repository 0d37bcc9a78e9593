"""Fig. 4 + Fig. 5a: nested hardware/software co-design vs the Eyeriss baseline.

Reports per-model EDP improvement over the hand-designed accelerator (Eyeriss
+ heuristic random mapper, Timeloop-style), the paper's headline table
(18.3% / 40.2% / 21.8% / 16.0% for ResNet / DQN / MLP / Transformer).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core import (CodesignConfig, CodesignEngine, EngineConfig,
                        HWSearchConfig, SWSearchConfig, optimize_software)
from repro.core.hwspace import HardwareSpace
from repro.core.baselines import random_search
from repro.timeloop import MODEL_LAYERS, eyeriss_baseline_edp


def bench_config(model: str, n_hw: int, n_sw: int, seed: int = 0,
                 backend: str | None = None, gp_refit_every: int = 1,
                 batched: bool = True) -> CodesignConfig:
    """The benchmark suite's reduced-budget `CodesignConfig` (pool 60, warmup
    n_sw//3 capped at 20)."""
    num_pes = 256 if model == "transformer" else 168
    return CodesignConfig(
        sw=SWSearchConfig(n_trials=n_sw, n_warmup=min(20, n_sw // 3),
                          pool_size=60),
        hw=HWSearchConfig(n_trials=n_hw, pool_size=60, num_pes=num_pes),
        engine=EngineConfig(backend=backend, gp_refit_every=gp_refit_every,
                            batched=batched, use_cache=batched),
        seed=seed,
    )


def run_model(model: str, n_hw: int = 12, n_sw: int = 60, seeds=(0,),
              baseline_budget: int = 4000, hw_search: str = "bo",
              engine: str = "batched", backend: str | None = None,
              gp_refit_every: int = 1, config: CodesignConfig | None = None):
    from repro.core.swspace import default_backend

    backend = backend or default_backend()  # None -> $REPRO_BACKEND or numpy
    layers = MODEL_LAYERS[model]
    num_pes = 256 if model == "transformer" else 168
    if config is not None:
        backend = config.engine.resolve_backend()  # record what actually ran
        num_pes = config.hw.num_pes  # baseline at the SAME PE budget as the search
    base = eyeriss_baseline_edp(layers, num_pes=num_pes, budget=baseline_budget)
    base_total = sum(base.values())
    batched = engine == "batched"
    bests, curves, times = [], [], []
    for seed in seeds:
        t0 = time.time()
        if hw_search == "bo":
            cfg = (dataclasses.replace(config, seed=seed)
                   if config is not None else
                   bench_config(model, n_hw, n_sw, seed=seed, backend=backend,
                                gp_refit_every=gp_refit_every,
                                batched=batched))
            res = CodesignEngine(cfg).run(layers)
            bests.append(res.best_model_edp)
            curves.append(res.hw_result.history)
        else:  # constrained random hardware search (paper's HW baseline)
            from repro.timeloop.model import evaluate as tl_eval

            if config is not None:  # honor the config here too
                sw_cfg, eng_cfg = config.sw, config.engine
            else:
                sw_cfg = SWSearchConfig(n_trials=n_sw,
                                        n_warmup=min(20, n_sw // 3),
                                        pool_size=60)
                eng_cfg = EngineConfig(backend=backend, batched=batched)

            def eval_hw(hw):
                total = 0.0
                for layer in layers:
                    r = optimize_software(hw, layer, sw_cfg, seed=seed + 1,
                                          engine=eng_cfg)
                    if r.best_point is None:
                        return None, False
                    total += tl_eval(hw, r.best_point, layer).edp
                eval_hw.best = min(getattr(eval_hw, "best", np.inf), total)
                return -float(np.log10(total)), True

            space = HardwareSpace(num_pes=num_pes, evaluate_fn=eval_hw)
            r = random_search(space, n_trials=n_hw, seed=seed)
            bests.append(getattr(eval_hw, "best", np.inf))
            curves.append(r.history)
        times.append(time.time() - t0)
    best = float(np.mean(bests))
    return {
        "model": model,
        "eyeriss_edp": base_total,
        "codesign_edp": best,
        "improvement_pct": (1 - best / base_total) * 100.0,
        "curve": np.mean(np.asarray(curves, dtype=np.float64), axis=0),
        "wall_time_s": times,
        "best_log10_edp_per_seed": [float(np.log10(b)) for b in bests],
        "engine": engine,
        "backend": backend,
    }


def run(n_hw: int = 12, n_sw: int = 60, seeds=(0,), quiet: bool = False,
        backend: str | None = None, gp_refit_every: int = 1,
        config: CodesignConfig | None = None):
    """Fig. 4/5a over the four seed models.  `config` (e.g. loaded from
    `benchmarks/run.py --config path.json`) overrides the per-model budget
    construction entirely -- only the seed is replaced per run."""
    out = {}
    for model in ("resnet", "dqn", "mlp", "transformer"):
        r = run_model(model, n_hw=n_hw, n_sw=n_sw, seeds=seeds, backend=backend,
                      gp_refit_every=gp_refit_every, config=config)
        out[model] = r
        if not quiet:
            print(f"fig5a,{model},eyeriss={r['eyeriss_edp']:.3e},"
                  f"codesign={r['codesign_edp']:.3e},"
                  f"improvement={r['improvement_pct']:.1f}%,"
                  f"time={sum(r['wall_time_s']):.1f}s")
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="paper-scale budgets (50 HW x 250 SW)")
    ap.add_argument("--hw-search", default="bo", choices=("bo", "random"))
    ap.add_argument("--backend", default=None, choices=("numpy", "jax"),
                    help="inner evaluation engine for the co-design runs "
                         "(default: $REPRO_BACKEND or numpy)")
    ap.add_argument("--gp-refit-every", type=int, default=1,
                    help="inner-loop surrogate refit stride (GP amortization)")
    args = ap.parse_args()
    if args.paper:
        run(n_hw=50, n_sw=250, seeds=(0, 1, 2), backend=args.backend,
            gp_refit_every=args.gp_refit_every)
    else:
        run(backend=args.backend, gp_refit_every=args.gp_refit_every)
