"""The paper's contribution: nested constrained Bayesian optimization for
hardware/software co-design, plus the beyond-paper TPU sharding autotuner.

The search surface is the typed config API (`repro.core.config`):
`CodesignConfig` (sw/hw/engine sections, JSON round-trip) run by a
`CodesignEngine`.
"""

from repro.core.config import (ACQUISITIONS, BACKENDS, EXECUTOR_KINDS,
                               PALLAS_MODES, PRUNE_MODES, STRATEGIES,
                               SURROGATES, CodesignConfig, EngineConfig,
                               ExecutorConfig, HWSearchConfig, SearchConfig,
                               ServiceConfig, SWSearchConfig)
from repro.core.cache import LRUCache, SlotCache
from repro.core.trace import counters_snapshot
from repro.core.gp import GP, GPClassifier, GPClassifierStack, GPStack
from repro.core.acquisition import expected_improvement, lcb, make_acquisition
from repro.core.bo import (BOLoop, BOResult, FanoutSearchSpec, bo_maximize,
                           bo_maximize_many, score_topk)
from repro.core.swspace import LayerStackSpace, SoftwareSpace, fanout_spaces
from repro.core.hwspace import HardwareSpace
from repro.core.nested import (CoDesignResult, CodesignEngine, SearchSession,
                               optimize_software, optimize_software_fanout,
                               optimize_software_many)
from repro.core.baselines import random_search, relax_round_bo, tvm_style_search
from repro.core.trees import GradientBoostedTrees, RandomForestSurrogate

__all__ = [
    "ACQUISITIONS",
    "BACKENDS",
    "EXECUTOR_KINDS",
    "PALLAS_MODES",
    "PRUNE_MODES",
    "STRATEGIES",
    "SURROGATES",
    "CodesignConfig",
    "EngineConfig",
    "ExecutorConfig",
    "HWSearchConfig",
    "SearchConfig",
    "ServiceConfig",
    "SWSearchConfig",
    "LRUCache",
    "SlotCache",
    "counters_snapshot",
    "GP",
    "GPClassifier",
    "GPClassifierStack",
    "GPStack",
    "expected_improvement",
    "lcb",
    "make_acquisition",
    "BOLoop",
    "BOResult",
    "FanoutSearchSpec",
    "bo_maximize",
    "bo_maximize_many",
    "score_topk",
    "LayerStackSpace",
    "SoftwareSpace",
    "fanout_spaces",
    "HardwareSpace",
    "CoDesignResult",
    "CodesignEngine",
    "SearchSession",
    "optimize_software",
    "optimize_software_fanout",
    "optimize_software_many",
    "random_search",
    "relax_round_bo",
    "tvm_style_search",
    "GradientBoostedTrees",
    "RandomForestSurrogate",
]
