"""Regression tests for the GP/JAX boundary bugs (ISSUE 2).

1. `repro.core.gp` used to run `jax.config.update("jax_enable_x64", True)` at
   import time, silently flipping the whole process to x64 (conflicting with a
   float32 Pallas engine).  x64 is now scoped to the GP computations.
2. `GPClassifier.prob_feasible` used to return a JAX array, silently promoting
   the host acquisition computation in `bo_maximize` to device arrays with a
   blocking transfer per trial.  It now returns NumPy.
3. With `noisy=False`, `GP.fit` pinned `log_tau=-6` but `_fit` still trained
   it, so the other hyperparameters were optimized against a drifting noise
   level before the pin was re-applied after the fact.  The pin is now frozen
   during the fit.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.gp import GP, GPClassifier

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_gp_import_does_not_flip_global_x64():
    """Importing the BO core in a fresh process leaves the default dtype f32."""
    code = (
        "import repro.core.gp, repro.core, jax, jax.numpy as jnp\n"
        "assert not jax.config.jax_enable_x64\n"
        "assert jnp.asarray(1.0).dtype == jnp.float32, jnp.asarray(1.0).dtype\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    subprocess.run([sys.executable, "-c", code], check=True, env=env)


def test_gp_still_computes_in_f64_scoped():
    """The scoped x64 context still gives the Cholesky solves full precision
    without touching the process-global flag."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(16, 3))
    y = X.sum(axis=1)
    gp = GP(kind="se", noisy=False).fit(X, y)
    params, Xp, yp, mask = gp._state
    assert Xp.dtype == jnp.float64
    assert all(v.dtype == jnp.float64 for v in jax.tree.leaves(params))
    assert not jax.config.jax_enable_x64
    assert jnp.asarray(1.0).dtype == jnp.float32  # process default untouched


def test_prob_feasible_returns_numpy():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(30, 2))
    clf = GPClassifier().fit(X, X[:, 0] > 0)
    p = clf.prob_feasible(X)
    assert isinstance(p, np.ndarray) and not isinstance(p, jax.Array)
    assert ((0.0 <= p) & (p <= 1.0)).all()
    # unfitted classifier too (warmup path)
    assert isinstance(GPClassifier().prob_feasible(X), np.ndarray)
    # the acquisition product therefore stays a host array
    utility = np.ones(len(X)) * p
    assert isinstance(utility, np.ndarray) and not isinstance(utility, jax.Array)


def test_prob_feasible_device_twin_matches_host():
    rng = np.random.default_rng(2)
    X = rng.normal(size=(40, 3))
    clf = GPClassifier().fit(X, X[:, 0] + X[:, 1] > 0)
    np.testing.assert_allclose(
        np.asarray(clf.prob_feasible_device(jnp.asarray(X))),
        clf.prob_feasible(X),
        atol=1e-6,
    )


def test_deterministic_gp_log_tau_stays_pinned():
    """noisy=False: log_tau comes out of the fit exactly where it was pinned,
    so the historical post-fit re-pin is a no-op (the fitted params are
    invariant to it)."""
    rng = np.random.default_rng(3)
    X = rng.uniform(-1, 1, size=(20, 4))
    y = np.sin(X[:, 0]) + X[:, 1]
    gp = GP(kind="linear", noisy=False).fit(X, y)
    assert float(gp.params["log_tau"]) == -6.0
    # re-pinning after the fact changes nothing about the posterior
    mu_before, var_before = gp.posterior(X)
    with jax.enable_x64(True):  # match the stored f64 dtype, as GP.fit does
        gp.params["log_tau"] = jnp.asarray(-6.0)
    mu_after, var_after = gp.posterior(X)
    np.testing.assert_array_equal(mu_before, mu_after)
    np.testing.assert_array_equal(var_before, var_after)


def test_noisy_gp_still_trains_log_tau():
    rng = np.random.default_rng(4)
    X = rng.uniform(-1, 1, size=(30, 2))
    y = X.sum(axis=1) + 0.3 * rng.normal(size=30)
    init = float(np.log(max(y.std(), 1e-3) * 0.1))
    gp = GP(kind="se", noisy=True).fit(X, y)
    assert float(gp.params["log_tau"]) != pytest.approx(init, abs=1e-6)


def test_deterministic_fit_is_reproducible():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(18, 3))
    y = X @ np.array([1.0, -1.0, 0.5])
    p1 = GP(kind="linear", noisy=False).fit(X, y).params
    p2 = GP(kind="linear", noisy=False).fit(X, y).params
    for k in p1:
        np.testing.assert_array_equal(np.asarray(p1[k]), np.asarray(p2[k]))
