"""Benchmark entry point: one section per paper table/figure plus the roofline
summary.  Prints `name,metric,...` CSV lines.

    PYTHONPATH=src python -m benchmarks.run            # reduced budgets
    PYTHONPATH=src python -m benchmarks.run --paper    # paper-scale budgets

The sections report search quality (EDP, improvement over Eyeriss); speed is
measured on the chip by `bench/run.py`.
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true")
    ap.add_argument("--backend", default=None, choices=("numpy", "jax"),
                    help="batched evaluation engine for the co-design section "
                         "(default: $REPRO_BACKEND or numpy)")
    ap.add_argument("--gp-refit-every", type=int, default=1,
                    help="inner-loop surrogate refit stride (GP amortization "
                         "knob, threaded to the co-design engine)")
    ap.add_argument("--config", default=None, metavar="PATH",
                    help="JSON CodesignConfig (CodesignConfig.from_dict) for "
                         "the co-design section; overrides the budget/backend "
                         "flags for that section")
    args, _ = ap.parse_known_args()

    from repro.core import CodesignConfig
    from repro.core.swspace import default_backend
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()

    config = None
    if args.config is not None:
        with open(args.config) as f:
            config = CodesignConfig.from_dict(json.load(f))

    backend = args.backend or default_backend()
    if config is not None:
        backend = config.engine.resolve_backend()

    from benchmarks import bo_ablation, bo_codesign, bo_software, roofline

    t0 = time.time()

    print("# Fig. 3 -- software-mapping optimization (best log10 EDP, lower wins)")
    bo_software.run(n_trials=250 if args.paper else 100,
                    seeds=tuple(range(3)) if args.paper else (0, 1))

    print("# feasibility -- raw design-space validity rate (paper: ~0.7%)")
    for name, ok, n, rate in bo_software.feasibility_report(
            samples=30_000 if args.paper else 8_000):
        print(f"feasibility,{name},{ok}/{n},{rate:.4%}")

    print(f"# Fig. 4 / 5a -- HW/SW co-design vs Eyeriss (backend={backend})")
    if args.paper:
        bo_codesign.run(n_hw=50, n_sw=250, seeds=(0, 1, 2), backend=backend,
                        gp_refit_every=args.gp_refit_every, config=config)
    else:
        bo_codesign.run(n_hw=12, n_sw=60, seeds=(0,), backend=backend,
                        gp_refit_every=args.gp_refit_every, config=config)

    print("# Fig. 5b/5c -- surrogate/acquisition + lambda ablations")
    bo_ablation.run(n_trials=250 if args.paper else 80,
                    seeds=(0, 1, 2) if args.paper else (0, 1))

    print("# Roofline -- dry-run derived terms (see EXPERIMENTS.md for tables)")
    s = roofline.run()
    if s:
        print(f"roofline,summary,{s}")

    print(f"# total {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
