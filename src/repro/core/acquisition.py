"""Acquisition functions (paper §3.3) in the *maximization* convention.

The optimizer maximizes utility = normalized reciprocal EDP (equivalently we fit
the GP on -log EDP).  LCB here follows the paper's formula a = mu + lambda*sigma
(an upper bound in maximize convention; the paper keeps the LCB name).
"""

from __future__ import annotations

import numpy as np


def _norm_pdf(z):
    return np.exp(-0.5 * z * z) / np.sqrt(2.0 * np.pi)


def _norm_cdf(z):
    # Standard normal CDF: Phi(z) = (1 + erf(z / sqrt(2))) / 2.  (The sqrt(2)
    # was historically missing, which made EI use an N(0, 1/2) CDF and
    # diverge from the device-resident twin below.)
    from scipy.special import erf

    z = np.asarray(z, dtype=np.float64)
    return 0.5 * (1.0 + erf(z / np.sqrt(2.0)))


def expected_improvement(mu: np.ndarray, var: np.ndarray, best: float) -> np.ndarray:
    sigma = np.sqrt(var)
    z = (mu - best) / np.maximum(sigma, 1e-12)
    return (mu - best) * _norm_cdf(z) + sigma * _norm_pdf(z)


def lcb(mu: np.ndarray, var: np.ndarray, lam: float = 1.0) -> np.ndarray:
    return mu + lam * np.sqrt(var)


def make_acquisition(name: str, lam: float = 1.0):
    if name == "ei":
        return lambda mu, var, best: expected_improvement(mu, var, best)
    if name == "lcb":
        return lambda mu, var, best: lcb(mu, var, lam)
    raise ValueError(name)


def make_acquisition_device(name: str, lam: float = 1.0):
    """`jnp` twins of the acquisitions, for the device-resident pool-scoring
    path (JAX evaluation engine + GP posterior, no host round-trip).  Each
    twin traces under scoped x64 -- without it, transcendental ops like erf
    canonicalize their internal constants to f32 and silently degrade the f64
    posterior's precision (the same class of bug as the old global-flag
    import side effect, just in the other direction)."""
    import math

    import jax
    import jax.numpy as jnp
    from jax.scipy.special import erf

    def ei(mu, var, best):
        with jax.enable_x64(True):
            sigma = jnp.sqrt(var)
            z = (mu - best) / jnp.maximum(sigma, 1e-12)
            pdf = jnp.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)
            cdf = 0.5 * (1.0 + erf(z / math.sqrt(2.0)))
            return (mu - best) * cdf + sigma * pdf

    def lcb(mu, var, best):
        with jax.enable_x64(True):
            return mu + lam * jnp.sqrt(var)

    if name == "ei":
        return ei
    if name == "lcb":
        return lcb
    raise ValueError(name)
