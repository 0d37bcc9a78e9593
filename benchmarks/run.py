"""Benchmark entry point: one section per paper table/figure plus the roofline
summary.  Prints `name,metric,...` CSV lines.

    PYTHONPATH=src python -m benchmarks.run            # reduced budgets
    PYTHONPATH=src python -m benchmarks.run --paper    # paper-scale budgets
    PYTHONPATH=src python -m benchmarks.run --json     # also write BENCH_codesign.json

`--json` records the co-design section's wall time and best log10 EDP per seed,
plus the batched-engine speedup over the scalar path, to BENCH_codesign.json so
the perf trajectory is tracked across PRs.
"""

from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_codesign.json (wall time, best log10 EDP "
                         "per seed, engine speedups)")
    ap.add_argument("--backend", default=None, choices=("numpy", "jax"),
                    help="batched evaluation engine for the co-design section "
                         "(default: $REPRO_BACKEND or numpy; the speedup "
                         "section always times both)")
    ap.add_argument("--gp-refit-every", type=int, default=1,
                    help="inner-loop surrogate refit stride (GP amortization "
                         "knob, threaded to codesign)")
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
    collect: dict | None = {} if args.json else None

    print("# Fig. 3 -- software-mapping optimization (best log10 EDP, lower wins)")
    bo_software.run(n_trials=250 if args.paper else 100,
                    seeds=tuple(range(3)) if args.paper else (0, 1))

    print("# feasibility -- raw design-space validity rate (paper: ~0.7%)")
    for name, ok, n, rate in bo_software.feasibility_report(
            samples=30_000 if args.paper else 8_000):
        print(f"feasibility,{name},{ok}/{n},{rate:.4%}")

    print(f"# Fig. 4 / 5a -- HW/SW co-design vs Eyeriss (backend={backend})")
    if args.paper:
        bo_codesign.run(n_hw=50, n_sw=250, seeds=(0, 1, 2), collect=collect,
                        backend=backend, gp_refit_every=args.gp_refit_every,
                        config=config)
    else:
        bo_codesign.run(n_hw=12, n_sw=60, seeds=(0,), collect=collect,
                        backend=backend, gp_refit_every=args.gp_refit_every,
                        config=config)

    print("# engines -- hot-path + end-to-end speedups (numpy + jax) vs scalar")
    eng = bo_codesign.engine_speedup()
    e2e = bo_codesign.e2e_speedup()
    print("# layer-batched nested search vs sequential layers (per backend)")
    lbe = bo_codesign.layer_batch_speedup()
    print("# probe-fanout warmup vs per-probe layer-batched (per backend)")
    pfe = bo_codesign.probe_fanout_speedup()
    print("# speculative scored-trial fan-out vs probe_fanout (per backend)")
    spec = bo_codesign.speculative_speedup()
    print("# bound-gated pruning (prune=safe) vs speculative alone "
          "(paper-scale outer budget, per backend)")
    prune = bo_codesign.prune_speedup()
    print("# co-design service -- fused concurrent requests vs sequential "
          "standalone (per backend)")
    svc = bo_codesign.service_speedup()
    print("# process executor -- multiprocess fan-out vs single-process "
          "service (numpy; speedup scales with cores)")
    execu = bo_codesign.executor_speedup()
    print("# workload portfolio -- one chip for a weighted zoo mix vs "
          "per-model specialists (wall + cross-model EDP table)")
    pfo = bo_codesign.portfolio_speedup()
    print("# cross-run transfer -- warmed store + trial history with "
          "hw.warm_start on vs served cold (per backend)")
    xfer = bo_codesign.transfer_speedup()
    bo_codesign.print_speedups(eng, e2e, lbe, pfe, spec, prune, svc, execu,
                               portfolio=pfo, transfer=xfer)

    print("# Fig. 5b/5c -- surrogate/acquisition + lambda ablations")
    bo_ablation.run(n_trials=250 if args.paper else 80,
                    seeds=(0, 1, 2) if args.paper else (0, 1))

    print("# Roofline -- dry-run derived terms (see EXPERIMENTS.md for tables)")
    s = roofline.run()
    if s:
        print(f"roofline,summary,{s}")

    total = time.time() - t0
    if collect is not None:
        collect["engine_speedup"] = eng
        collect["e2e_speedup"] = e2e
        collect["layer_batch_e2e"] = lbe
        collect["probe_fanout_e2e"] = pfe
        collect["speculative_e2e"] = spec
        collect["prune_e2e"] = prune
        collect["service_e2e"] = svc
        collect["executor_e2e"] = execu
        collect["portfolio_e2e"] = pfo
        collect["transfer_e2e"] = xfer
        collect["backend"] = backend
        collect["paper_budgets"] = bool(args.paper)
        collect["total_s"] = round(total, 1)
        with open("BENCH_codesign.json", "w") as f:
            json.dump(collect, f, indent=2, sort_keys=True)
        print("# wrote BENCH_codesign.json")

    print(f"# total {total:.0f}s")


if __name__ == "__main__":
    main()
