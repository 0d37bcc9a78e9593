"""Beyond-paper: the constrained-BO engine autotuning THIS framework's own
sharding/remat/block configuration, with `lower().compile()` + roofline as the
expensive black-box simulator (see DESIGN.md and EXPERIMENTS.md §Perf).

    PYTHONPATH=src python examples/autotune_sharding.py \
        --arch smollm-360m --shape train_4k --trials 8
"""

# The dry-run needs the 512 placeholder devices BEFORE any jax import.
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--trials", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=3)
    args = ap.parse_args()

    from repro.configs.base import SHAPES, get_config
    from repro.jax_cache import enable_compile_cache
    from repro.core.autotune import TuneConfig, TuneSpace, autotune

    enable_compile_cache()

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    print(f"autotuning {args.arch} x {args.shape}: mesh split x fsdp x remat x "
          f"flash blocks ({args.trials} compiles, each is the expensive sample)")

    space = TuneSpace(cfg, shape)
    base = TuneConfig()  # the framework's hand-written default
    base_util, base_ok = space.evaluate(base)
    base_step = space.last_record["roofline"]["step_time_s"] if base_ok else None
    print(f"baseline {base}: step {base_step:.4f}s" if base_ok else "baseline infeasible")

    best, result = autotune(cfg, shape, n_trials=args.trials,
                            n_warmup=args.warmup, pool_size=24, seed=0)
    space.evaluate(best)
    rec = space.last_record
    t = rec["roofline"]
    print(f"\nbest tune: {best}")
    print(f"  step {t['step_time_s']:.4f}s (bound: {t['bound']}) "
          f"mem {rec['memory']['total_gib_per_dev']} GiB/dev "
          f"MFU~{rec['mfu_estimate']:.2%}")
    if base_ok:
        print(f"  speedup over hand-written default: "
              f"{base_step / t['step_time_s']:.2f}x")
    print(f"  infeasible compiles hit (unknown constraints): {result.n_infeasible}")


if __name__ == "__main__":
    main()
