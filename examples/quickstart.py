"""Quickstart: the paper's technique in ~40 lines.

Optimize the software mapping of one ResNet layer on the Eyeriss accelerator
with constrained Bayesian optimization, and compare against constrained random
search.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import SoftwareSpace, bo_maximize, random_search
from repro.jax_cache import enable_compile_cache
from repro.timeloop import PAPER_WORKLOADS, evaluate, eyeriss_168


def main():
    enable_compile_cache()
    hw = eyeriss_168()
    layer = PAPER_WORKLOADS["ResNet-K2"]
    space = SoftwareSpace(hw, layer)
    print(f"layer {layer.name}: {layer.macs/1e6:.1f}M MACs on Eyeriss "
          f"({hw.pe_mesh_x}x{hw.pe_mesh_y} PEs)")

    r_random = random_search(space, n_trials=100, seed=0)
    r_bo = bo_maximize(space, n_trials=100, n_warmup=25, pool_size=100, seed=0)

    for name, r in (("random", r_random), ("constrained BO", r_bo)):
        ev = evaluate(hw, r.best_point, layer)
        print(f"{name:16s}: EDP {ev.edp:.3e} pJ*cycles "
              f"(energy {ev.energy_pj:.3e} pJ, delay {ev.delay_cycles:.3e} cyc)")
    gain = 10 ** (r_bo.best_value - r_random.best_value)
    print(f"BO finds a {gain:.2f}x better EDP within the same 100-trial budget")

    m = r_bo.best_point
    print("\nbest mapping (factors per level, dims R,S,P,Q,C,K):")
    for lvl, row in zip(("LB", "spatialX", "spatialY", "GB", "DRAM"), m.factors):
        print(f"  {lvl:9s} {row}")
    print(f"  loop order GB:   {m.order_gb}")
    print(f"  loop order DRAM: {m.order_dram}")


if __name__ == "__main__":
    main()
