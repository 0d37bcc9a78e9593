"""The plain GP reference against the program's stacked GP, on observations
shaped like an inner search's (30 to 40 rows of 14 features, pools of 150).
The float32 control departs on a search's own observations
(`test_harness.py`)."""

import importlib.util
import json
import os

import numpy as np
import pytest

from repro.core.gp import GPStack

from conftest import BENCH

spec = importlib.util.spec_from_file_location(
    "reference_gp", os.path.join(BENCH, "references", "gp.py"))
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)

with open(os.path.join(BENCH, "configs", "dqn-eyeriss168.json")) as f:
    SURROGATE = json.load(f)["surrogate"]


def data(seed, n):
    rng = np.random.default_rng(seed)
    Xs = [rng.normal(size=(n, 14)) for _ in range(3)]
    Xs[0][:, 3] = 0.0      # a feature that never varies
    ys = [X @ rng.normal(size=14) * 0.3 + rng.normal(size=n) * 0.1
          for X in Xs]
    return Xs, ys, rng.normal(size=(3, 150, 14))


@pytest.mark.parametrize("n", [30, 40])
def test_reference_matches_program(n):
    Xs, ys, pools = data(n, n)
    program = GPStack(kind="linear", noisy=False).fit(Xs, ys)
    mu, var = program.posterior(pools)
    idx, _ = program.score_device(pools, np.zeros((3, 1)), "lcb", 1.0)
    for k in range(3):
        gp = ref.LinearGP(Xs[k], ys[k], SURROGATE)
        m2, v2 = gp.posterior(pools[k])
        np.testing.assert_allclose(m2, mu[k], rtol=0, atol=1e-6 * np.std(m2))
        np.testing.assert_allclose(v2, var[k], rtol=1e-4)
        util = ref.utilities(Xs[k], ys[k], pools[k], 0.0, SURROGATE, "lcb",
                             1.0)
        assert int(np.argmax(util)) == int(idx[k])

