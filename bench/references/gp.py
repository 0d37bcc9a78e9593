"""Plain reference of the search's surrogate: an exact Gaussian process with a
linear kernel on explicit features (arXiv 2010.02075 Sec. 3.2), its
hyperparameters fit by full-batch Adam on the negative log marginal
likelihood, and the acquisition that ranks a candidate pool.

Written from the model's stated rules in NumPy, with nothing taken from the
program under test: it reads plain arrays and the configuration's
`surrogate` block, never the program's objects or fitted values.

For n observations X (n, d), y (n,):

    k(x, x') = sum_j w_j^2 x_j x'_j + b^2        w = exp(log_w), b = exp(log_bias)
    K        = k(X, X) + s0 I                    s0 = exp(2 log_tau) + jitter
    NLL      = 0.5 (r' K^-1 r + log det K + n log 2 pi),   r = y - c

The kernel is that of Bayesian linear regression on phi(x) = [x w, b]
(d + 1 entries) with a standard normal prior on the weights, so the GP is
computed in that weight space: with V = phi(X) (n, d + 1),

    A    = I + V'V / s0            (posterior precision of the weights)
    beta = A^-1 V' r / s0          (posterior mean of the weights)
    mu(x)  = c + phi(x)' beta,     var(x) = max(phi(x)' A^-1 phi(x), var_floor)

and, since V' K^-1 V = I - A^-1 and V' K^-1 r = beta, the NLL's gradient in
the log-scale of column j of V (log_w_j, or log_bias for the last) is
(I - A^-1)_jj - beta_j^2, and in c it is -sum(r - V beta) / s0.  A is
inverted by LU.  (The n-square K has rank d + 1 plus the tiny s0, and its
Cholesky factor, like one of A, does not exist in float32 for most of the
searches' fits: the float32 control would then give no answer at all.)

Fit: start at log_w = 0, log_bias = 0, c = mean(y) and the stated log_tau,
held fixed (the evaluator is deterministic, so `train_noise` is false),
then `steps` Adam steps (beta 0.9 / 0.999, eps 1e-8, learning rate `lr`).
Acquisition (maximised): lcb is mu + lam sqrt(var); ei is the expected
improvement over the incumbent.

`dtype` is the arithmetic precision: every input is cast to it and every
operation rounds to it.  float64 is the reference; float32 is the control
that the check must reject.
"""

from __future__ import annotations

import math

import numpy as np
from scipy.special import erf


def _features(X, w, b):
    return np.concatenate([X * w, np.full((len(X), 1), b, X.dtype)], axis=1)


def _weights(V, r, s0):
    """A^-1 and beta (module doc), A inverted by LU."""
    A = np.eye(V.shape[1], dtype=V.dtype) + (V.T @ V) / s0
    Ainv = np.linalg.inv(A)
    return Ainv, Ainv @ (V.T @ r) / s0


class LinearGP:
    """One fitted linear-kernel GP."""

    def __init__(self, X, y, spec: dict, dtype=np.float64):
        if spec["train_noise"]:
            raise ValueError("the reference fits a GP with its noise held")
        self.dtype = dtype
        cast = lambda v: np.asarray(v, np.float64).astype(dtype)  # noqa: E731
        X, y = cast(X), cast(y)
        d = X.shape[1]
        lr, steps = cast(spec["lr"]), int(spec["steps"])
        b1, b2, eps, one = cast(0.9), cast(0.999), cast(1e-8), cast(1.0)
        self.s0 = np.exp(cast(2.0) * cast(spec["log_tau"])) + cast(
            spec["jitter"])
        self.var_floor = cast(spec["var_floor"])
        p = {"log_w": np.zeros(d, dtype), "log_bias": cast(0.0),
             "c": cast(y.mean())}
        m = {k: np.zeros_like(v) for k, v in p.items()}
        v = {k: np.zeros_like(v) for k, v in p.items()}
        for t in range(1, steps + 1):
            V = _features(X, np.exp(p["log_w"]), np.exp(p["log_bias"]))
            r = y - p["c"]
            Ainv, beta = _weights(V, r, self.s0)
            g_col = (one - np.diagonal(Ainv)) - beta * beta
            g = {"log_w": g_col[:d], "log_bias": g_col[d],
                 "c": -np.sum(r - V @ beta) / self.s0}
            tt = cast(t)
            for k in p:
                m[k] = b1 * m[k] + (one - b1) * g[k]
                v[k] = b2 * v[k] + (one - b2) * g[k] * g[k]
                mh = m[k] / (one - b1 ** tt)
                vh = v[k] / (one - b2 ** tt)
                p[k] = p[k] - lr * mh / (np.sqrt(vh) + eps)
        self.w, self.b = np.exp(p["log_w"]), np.exp(p["log_bias"])
        self.c = p["c"]
        self.Ainv, self.beta = _weights(_features(X, self.w, self.b),
                                        y - self.c, self.s0)

    def posterior(self, Xs):
        Phi = _features(np.asarray(Xs, np.float64).astype(self.dtype),
                        self.w, self.b)
        mu = self.c + Phi @ self.beta
        var = np.sum((Phi @ self.Ainv) * Phi, axis=1)
        return mu, np.maximum(var, self.var_floor)


def acquisition(name: str, lam: float, mu, var, best):
    """Utility of each candidate (higher is better)."""
    if name == "lcb":
        return mu + lam * np.sqrt(var)
    if name == "ei":
        sigma = np.sqrt(var)
        z = (mu - best) / np.maximum(sigma, 1e-12)
        pdf = np.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)
        cdf = 0.5 * (1.0 + erf(z / math.sqrt(2.0)))
        return (mu - best) * cdf + sigma * pdf
    raise ValueError(name)


def utilities(X, y, pool, best, spec: dict, acq: str, lam: float,
              dtype=np.float64):
    """Fit on (X, y) and score every candidate row of `pool`."""
    gp = LinearGP(X, y, spec, dtype)
    mu, var = gp.posterior(pool)
    return acquisition(acq, lam, mu, var, np.asarray(best, dtype)).astype(
        np.float64)
