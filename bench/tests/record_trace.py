"""Records `data/small.xplane.pb`, the trace the reduction tests read.

    python3 bench/tests/record_trace.py <out.xplane.pb>    # on the chip

A few calls of the program's fused forward and one stacked GP fit and
scoring, under the harness's window and step spans, traced as a run traces
(`xplane.start`), so that the trace holds
`jit__forward`, `jit__fit_stack` and `jit__score_stack` programs on the
device and idle gaps inside each kind of span.  The committed file was
recorded on a TPU v5e (GP fit cut to 4 Adam steps) by an earlier
`xplane.start` that also set the profiler mode TRACE_ONLY_XLA, and its `XLA Ops`
lines, which the reduction does not read, were then dropped from the
protobuf to keep it under 300 KB: as recorded it held 25 MB.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))
sys.path.insert(0, os.path.join(HERE, ".."))

import xplane  # noqa: E402


def main(out: str) -> None:
    import jax
    import numpy as np

    from repro.core.gp import GPStack
    from repro.timeloop import MODEL_LAYERS, eyeriss_168
    from repro.timeloop import batch as tlb
    from repro.timeloop import batch_jax as jtlb

    rng = np.random.default_rng(0)
    hw, layers = eyeriss_168(), MODEL_LAYERS["dqn"]
    pools = [tlb.sample_valid_pool(rng, hw, ly, 150) for ly in layers]
    X = [rng.normal(size=(32, 14)) for _ in layers]
    y = [rng.normal(size=32) for _ in layers]

    def work():
        feats = jtlb.forward_device_stacked(hw, pools, layers)["features"]
        gps = GPStack(kind="linear").fit(X, y)
        jax.block_until_ready(gps.score_device(
            feats, np.zeros((len(layers), 1)), "lcb", 1.0))

    work()  # compile outside the trace
    tmp = tempfile.mkdtemp()
    xplane.start(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for kind in ("warmup", "trial"):
            with jax.profiler.TraceAnnotation(f"bench.step.{kind}"):
                work()
                time.sleep(0.01)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))[0], out)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
