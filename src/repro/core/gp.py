"""Exact Gaussian processes in JAX (paper §3.2).

Kernels: squared-exponential (ARD optional), linear-on-features, and an additive
noise kernel.  Hyperparameters live in log space and are fit by full-batch Adam
on the negative marginal log-likelihood.  Dataset sizes here are tiny (<= a few
hundred), so exact Cholesky GPs are cheap; to keep the jitted fit fast on CPU we
pad X/y to bucketed sizes (powers of two) with masked-out rows so the compiled
function is reused across BO iterations.

`GPStack` / `GPClassifierStack` fit and query L *independent* GPs as one
batched program (`vmap` over the leading run axis: batched Cholesky
solves for the fit, one device posterior over the stacked candidate pools).
The layer-batched nested search uses this to replace L sequential per-layer
surrogate refits -- the end-to-end bottleneck once the evaluation engine is
vectorized -- with a single batched fit per BO round.  Padding is *exactly*
zero-influence (masked kernel rows make the padded block of the Cholesky
factor decouple: alpha is exactly 0 on padded rows, and the NLL masks their
logdet terms), so each slice of a stack reproduces the corresponding
individual `GP` fit regardless of how runs are padded to the shared bucket.

The Cholesky solves need float64, but that is scoped to the GP computations via
the `jax.enable_x64(True)` context -- importing this module does NOT flip the
process-global x64 flag (which would silently force every other JAX program in
the process, e.g. the float32 Pallas evaluation engine, to f64).  The fitted
state is held as f64 device arrays, and every jnp op that touches them, or the
f64 posteriors and utilities they produce, must run inside that scope: outside
it jax rejects or truncates f64 operands.  Callers that leave the scope take
host copies (`np.asarray`) at the boundary.

Where the default backend is a TPU, the stacked fit runs on the host's CPU
device (`_fit_device`).  A fit is 80 dependent Adam steps, each a Cholesky
(or Woodbury) factorization of a few dozen rows, its solves and log-determinant
in float64.  The TPU has no float64 units and emulates it with pairs of
float32 ops, so there the fit is a long chain of tiny dependent ops, bound by
latency; the host's CPU runs the same program in native IEEE float64.  The
fitted state then moves to the chip, where the stacked scoring runs against
the candidate pools' features.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import erf as _erf

from repro.core import trace

_JITTER = 1e-6
_PAD_NOISE = 1e6  # effective infinite noise on padded rows -> zero influence
# Stacked linear-kernel fits switch to the O(n d^2) Woodbury NLL above this
# many (padded) data rows (`_fit_lowrank`).  One blocking fit on the host's
# CPU (80 Adam steps, d = 14, float64) costs less through Woodbury at every
# stack width from bucket 24 up; at bucket 16 the two NLLs cost about the
# same and Cholesky is the cheaper for narrow stacks.  Up to 16 rows the
# Cholesky NLL keeps the stacked fit within f64 roundoff of the sequential
# one, so lockstep and speculative searches at small budgets reproduce
# sequential ones exactly (see `_fit_stack`).
_LOWRANK_MIN_ROWS = 16


def _bucket(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


def se_kernel(params, x1, x2):
    """Squared exponential with scalar lengthscale (paper's constraint GP)."""
    alpha = jnp.exp(params["log_alpha"])
    ell = jnp.exp(params["log_ell"])
    d2 = jnp.sum((x1[:, None, :] - x2[None, :, :]) ** 2, axis=-1)
    return alpha**2 * jnp.exp(-d2 / (ell**2))


def linear_kernel(params, x1, x2):
    """Linear kernel on explicit features with learned per-feature scales
    (paper §3.2: "a linear kernel on top of explicit features")."""
    w = jnp.exp(params["log_w"])
    return (x1 * w) @ (x2 * w).T + jnp.exp(params["log_bias"]) ** 2


KERNELS = {"se": se_kernel, "linear": linear_kernel}


def _init_params(kind: str, dim: int) -> dict:
    if kind == "se":
        return {"log_alpha": jnp.zeros(()), "log_ell": jnp.zeros(())}
    if kind == "linear":
        return {"log_w": jnp.zeros((dim,)), "log_bias": jnp.zeros(())}
    raise ValueError(kind)


def _nll_linear_lowrank(params, X, y, mask):
    """`_nll(kind="linear")` via Woodbury -- same value, O(n d^2) not O(n^3).

    The linear kernel is rank d+1: K = (M V0)(M V0)^T + bias^2 (M 1)(M 1)^T
    + D with V0 = X * w, M = diag(mask), D the masked noise/pad diagonal.
    With V = M [V0, bias 1] (n, d+1) and A = I + V^T D^-1 V:

      quad            r^T K^-1 r = r^T D^-1 r - u^T A^-1 u,  u = V^T D^-1 r
      masked logdet   sum_masked log D_ii + logdet A

    (pad rows have V = 0 and r = 0, so they drop out of both terms exactly,
    matching the masked Cholesky logdet of `_nll`).  Used by the stacked
    multi-run fit, where the surrogate refit is the dominant per-trial cost;
    agrees with `_nll` to f64 roundoff (~1e-12 relative), parity-tested."""
    n = X.shape[0]
    noise = jnp.exp(2.0 * params["log_tau"])
    diag = jnp.where(mask > 0.5, noise + _JITTER, _PAD_NOISE)
    w = jnp.exp(params["log_w"])
    V = jnp.concatenate(
        [X * w, jnp.full((n, 1), jnp.exp(params["log_bias"]))], axis=1)
    V = V * mask[:, None]
    r = jnp.where(mask > 0.5, y - params["mean_const"], 0.0)
    Vd = V / diag[:, None]
    A = jnp.eye(V.shape[1], dtype=X.dtype) + V.T @ Vd
    La = jnp.linalg.cholesky(A)
    u = Vd.T @ r
    quad = r @ (r / diag) - u @ jax.scipy.linalg.cho_solve((La, True), u)
    logdet = (jnp.sum(jnp.where(mask > 0.5, jnp.log(diag), 0.0))
              + 2.0 * jnp.sum(jnp.log(jnp.diagonal(La))))
    n_eff = jnp.sum(mask)
    return 0.5 * (quad + logdet + n_eff * jnp.log(2.0 * jnp.pi))


@functools.partial(jax.jit, static_argnames=("kind",))
def _nll(params, X, y, mask, kind):
    k = KERNELS[kind]
    n = X.shape[0]
    noise = jnp.exp(2.0 * params["log_tau"])
    diag = jnp.where(mask > 0.5, noise + _JITTER, _PAD_NOISE)
    K = k(params, X, X) * (mask[:, None] * mask[None, :]) + jnp.diag(diag)
    c = params["mean_const"]
    r = jnp.where(mask > 0.5, y - c, 0.0)
    L = jnp.linalg.cholesky(K)
    alpha = jax.scipy.linalg.cho_solve((L, True), r)
    quad = r @ alpha
    logdet = 2.0 * jnp.sum(jnp.where(mask > 0.5, jnp.log(jnp.diagonal(L)), 0.0))
    n_eff = jnp.sum(mask)
    return 0.5 * (quad + logdet + n_eff * jnp.log(2.0 * jnp.pi))


@functools.partial(jax.jit,
                   static_argnames=("kind", "steps", "lr", "train_tau",
                                    "lowrank", "tol"))
def _fit(params, X, y, mask, kind, steps=80, lr=0.05, train_tau=True,
         lowrank=False, tol=0.0):
    # lowrank: optimize the Woodbury form of the linear-kernel NLL (same
    # function to f64 roundoff, O(n d^2) per step) -- the stacked multi-run
    # fit uses it; the single-run path keeps the Cholesky NLL.
    if lowrank:
        assert kind == "linear", "lowrank NLL exists for the linear kernel"
        grad_fn = jax.grad(
            lambda p, xx, yy, mm, _k: _nll_linear_lowrank(p, xx, yy, mm))
    else:
        grad_fn = jax.grad(_nll)

    def adam_update(carry, g):
        p, m, v, t = carry
        if not train_tau:
            # Deterministic evaluator: the noise level is pinned, so exclude it
            # from the update entirely -- otherwise the other hyperparameters
            # are optimized against a drifting noise level that is only
            # re-pinned after the fact.
            g = dict(g, log_tau=jnp.zeros_like(g["log_tau"]))
        t = t + 1
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        mh = jax.tree.map(lambda a: a / (1 - 0.9**t), m)
        vh = jax.tree.map(lambda a: a / (1 - 0.999**t), v)
        p = jax.tree.map(lambda a, b, c: a - lr * b / (jnp.sqrt(c) + 1e-8), p, mh, vh)
        return p, m, v, t

    zeros = jax.tree.map(jnp.zeros_like, params)
    if tol == 0.0:
        # Fixed-length scan: the default path, byte-for-byte the pre-tol fit.
        def adam_step(carry, _):
            g = grad_fn(carry[0], X, y, mask, kind)
            return adam_update(carry, g), None

        (params, _, _, _), _ = jax.lax.scan(
            adam_step, (params, zeros, zeros, 0.0), None, length=steps
        )
        return params

    # Gradient-norm early-exit (tolerance-gated): identical Adam updates, but
    # the loop stops once the global gradient norm of the step just applied
    # drops below `tol` -- converged fits skip the remaining steps instead of
    # always burning all `steps` of them.
    def cond(carry):
        _, _, _, t, gn = carry
        return (t < steps) & (gn >= tol)

    def body(carry):
        p, m, v, t, _ = carry
        g = grad_fn(p, X, y, mask, kind)
        if not train_tau:
            g = dict(g, log_tau=jnp.zeros_like(g["log_tau"]))
        gn = jnp.sqrt(sum(jnp.sum(leaf ** 2) for leaf in jax.tree.leaves(g)))
        p, m, v, t = adam_update((p, m, v, t), g)
        return p, m, v, t, gn

    params, _, _, _, _ = jax.lax.while_loop(
        cond, body, (params, zeros, zeros, 0.0, jnp.asarray(jnp.inf, X.dtype)))
    return params


# --- incremental (rank-1) posterior updates --------------------------------------
#
# Between aligned refits the BO loop's surrogate hyperparameters are frozen, so
# appending one observation only changes the DATA side of the posterior: the
# padded kernel matrix gains one real row/column in the first padded slot.
# Because padded rows are exactly decoupled (zero off-diagonal, _PAD_NOISE
# diagonal -- see module docstring), the Cholesky factor of the updated matrix
# differs from the cached one in exactly that row: a standard border update
# L[n, :n] = L^-1 k_new, L[n, n] = sqrt(k(x,x) + noise + jitter - |L[n,:n]|^2),
# computed in O(n^2) instead of the O(n^3) refactorization `_posterior` does
# per call.  Posterior queries then reuse the cached factor (`_posterior_chol`)
# -- the same downstream solves as `_posterior`, parity-pinned to <= 1e-8 in
# tests/test_gp_rank1.py against a frozen-hyperparameter refit from scratch.

@functools.partial(jax.jit, static_argnames=("kind",))
def _chol_factor(params, X, mask, kind):
    """Cholesky factor of the masked padded kernel matrix (the same K that
    `_nll` / `_posterior` build internally)."""
    k = KERNELS[kind]
    noise = jnp.exp(2.0 * params["log_tau"])
    diag = jnp.where(mask > 0.5, noise + _JITTER, _PAD_NOISE)
    K = k(params, X, X) * (mask[:, None] * mask[None, :]) + jnp.diag(diag)
    return jnp.linalg.cholesky(K)


@functools.partial(jax.jit, static_argnames=("kind",))
def _append_row(params, L, X, y, mask, x, val, kind):
    """Rank-1 border update: append one observation into the first padded
    slot, updating the cached factor in O(n^2).  Returns (L, X, y, mask)."""
    k = KERNELS[kind]
    n = jnp.sum(mask).astype(jnp.int32)  # first padded slot (pads trail)
    kv = k(params, X, x[None])[:, 0] * mask  # zero on padded rows
    w = jax.scipy.linalg.solve_triangular(L, kv, lower=True)
    noise = jnp.exp(2.0 * params["log_tau"])
    knn = k(params, x[None], x[None])[0, 0] + noise + _JITTER
    row = w.at[n].set(jnp.sqrt(knn - w @ w))
    return (L.at[n, :].set(row), X.at[n, :].set(x), y.at[n].set(val),
            mask.at[n].set(1.0))


@functools.partial(jax.jit, static_argnames=("kind",))
def _posterior_chol(params, L, X, y, mask, Xs, kind):
    """`_posterior` with the Cholesky factor precomputed (the incremental
    path): identical solves, no per-query refactorization."""
    k = KERNELS[kind]
    c = params["mean_const"]
    r = jnp.where(mask > 0.5, y - c, 0.0)
    alpha = jax.scipy.linalg.cho_solve((L, True), r)
    Ks = k(params, Xs, X) * mask[None, :]
    mu = Ks @ alpha + c
    v = jax.scipy.linalg.solve_triangular(L, Ks.T, lower=True)
    kss = jax.vmap(lambda x: k(params, x[None], x[None])[0, 0])(Xs)
    var = jnp.maximum(kss - jnp.sum(v**2, axis=0), 1e-10)
    return mu, var


@functools.partial(jax.jit, static_argnames=("kind",))
def _posterior(params, X, y, mask, Xs, kind):
    k = KERNELS[kind]
    noise = jnp.exp(2.0 * params["log_tau"])
    diag = jnp.where(mask > 0.5, noise + _JITTER, _PAD_NOISE)
    K = k(params, X, X) * (mask[:, None] * mask[None, :]) + jnp.diag(diag)
    c = params["mean_const"]
    r = jnp.where(mask > 0.5, y - c, 0.0)
    L = jnp.linalg.cholesky(K)
    alpha = jax.scipy.linalg.cho_solve((L, True), r)
    Ks = k(params, Xs, X) * mask[None, :]
    mu = Ks @ alpha + c
    v = jax.scipy.linalg.solve_triangular(L, Ks.T, lower=True)
    kss = jax.vmap(lambda x: k(params, x[None], x[None])[0, 0])(Xs)
    var = jnp.maximum(kss - jnp.sum(v**2, axis=0), 1e-10)
    return mu, var


def apply_prior_mean(mu, ms):
    """Add an externally supplied prior-mean offset `ms` to posterior means
    `mu` (variances are untouched).

    Residual prior-mean contract: the caller fits the GP on residuals
    y - m(x) and adds m back at query time via this helper.  Any
    *ordering-accurate* mean (one that ranks points like the true objective,
    e.g. -log10 of the analytic EDP lower bound, ROADMAP "the bound is
    ordering-accurate") shifts the acquisition landscape toward genuinely
    promising hardware without touching the calibrated posterior variances
    -- the GP only has to learn the (smoother) gap between bound and
    achieved utility."""
    return np.asarray(mu) + np.asarray(ms, dtype=np.float64)


@dataclasses.dataclass
class GP:
    """Exact GP regressor.

    kind:        'se' or 'linear'
    noisy:       if False, the noise is pinned tiny (deterministic evaluator,
                 paper §4.3); if True it is a learned hyperparameter (paper §4.2).
    fit_tol:     gradient-norm early-exit tolerance for the hyperparameter fit
                 (0.0 = off: the fixed-length scan, bit-identical to the
                 historical fit).
    """

    kind: str = "linear"
    noisy: bool = True
    steps: int = 80
    fit_tol: float = 0.0
    _state: tuple | None = None
    # Cached Cholesky factor of the data kernel matrix, maintained by
    # `append_observation` between aligned refits.  None (the default) keeps
    # every posterior on the factor-free `_posterior` path -- the incremental
    # machinery is strictly opt-in, so fitted GPs behave byte-for-byte as
    # before unless the BO loop explicitly appends.
    _fac: jax.Array | None = None

    def fit(self, X: np.ndarray, y: np.ndarray) -> "GP":
        X = np.asarray(X, np.float64)
        y = np.asarray(y, np.float64)
        n, d = X.shape
        b = _bucket(n)
        Xp = np.zeros((b, d))
        yp = np.zeros((b,))
        mask = np.zeros((b,))
        Xp[:n], yp[:n], mask[:n] = X, y, 1.0
        to_device = trace.to_device
        with jax.enable_x64(True):
            params = _init_params(self.kind, d)
            params["mean_const"] = to_device(float(y.mean()))
            params["log_tau"] = to_device(
                np.log(max(y.std(), 1e-3) * 0.1) if self.noisy else -6.0)
            # With noisy=False the pinned log_tau is frozen *during* the fit
            # (zeroed gradient), so the remaining hyperparameters are trained
            # against the true fixed noise level -- no post-fit re-pin needed.
            params = _fit(params, to_device(Xp), to_device(yp),
                          to_device(mask), self.kind, self.steps,
                          train_tau=self.noisy, tol=self.fit_tol)
            trace.dispatched()
            self._state = (params, to_device(Xp), to_device(yp),
                           to_device(mask))
        self._fac = None  # a full refit invalidates any incremental factor
        return self

    def posterior(self, Xs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        mu, var = self.posterior_device(Xs)
        return trace.fetch(mu), trace.fetch(var)

    def posterior_device(self, Xs) -> tuple[jax.Array, jax.Array]:
        """Posterior as device arrays -- lets the batched-engine acquisition
        scoring stay device-resident (no host round-trip per BO trial).
        With an incremental factor cached (`append_observation`), reuses it
        instead of refactorizing per call."""
        assert self._state is not None, "fit() first"
        params, Xp, yp, mask = self._state
        trace.dispatched()
        with jax.enable_x64(True):
            Xs = trace.to_device(Xs, jnp.float64)
            if self._fac is not None:
                return _posterior_chol(params, self._fac, Xp, yp, mask, Xs,
                                       self.kind)
            return _posterior(params, Xp, yp, mask, Xs, self.kind)

    def append_observation(self, x: np.ndarray, y: float) -> "GP":
        """Fold one observation into the posterior WITHOUT refitting
        hyperparameters: an O(n^2) rank-1 border update of the cached Cholesky
        factor (built lazily on first append).  Between aligned refits this
        keeps the surrogate's data current at a fraction of a full fit's cost;
        the next `fit()` discards the factor and re-learns hyperparameters as
        usual.  Parity: matches `with_data` (frozen-hyperparameter refit from
        scratch) to <= 1e-8."""
        assert self._state is not None, "fit() first"
        params, Xp, yp, mask = self._state
        n = int(trace.fetch(mask).sum())
        b = Xp.shape[0]
        with jax.enable_x64(True):
            if n >= b:
                # Bucket overflow: repad to the next bucket and refactorize
                # (O(n^3), but only at power-of-two boundaries -- amortized
                # O(n^2) per append).
                b2 = _bucket(n + 1)
                Xp2 = np.zeros((b2, Xp.shape[1]))
                yp2 = np.zeros((b2,))
                mask2 = np.zeros((b2,))
                Xp2[:n] = trace.fetch(Xp)[:n]
                yp2[:n] = trace.fetch(yp)[:n]
                mask2[:n] = 1.0
                Xp, yp, mask = (trace.to_device(Xp2), trace.to_device(yp2),
                                trace.to_device(mask2))
                self._fac = None
            if self._fac is None:
                self._fac = _chol_factor(params, Xp, mask, self.kind)
                trace.dispatched()
            self._fac, Xp, yp, mask = _append_row(
                params, self._fac, Xp, yp, mask,
                trace.to_device(np.asarray(x, np.float64)), float(y),
                self.kind)
            trace.dispatched()
        self._state = (params, Xp, yp, mask)
        return self

    def with_data(self, X: np.ndarray, y: np.ndarray) -> "GP":
        """A new GP with THIS model's (frozen) hyperparameters and the given
        dataset, state rebuilt from scratch -- the refit-from-scratch parity
        reference for `append_observation`."""
        assert self._state is not None, "fit() first"
        params = self._state[0]
        X = np.asarray(X, np.float64)
        y = np.asarray(y, np.float64)
        n, d = X.shape
        b = _bucket(n)
        Xp = np.zeros((b, d))
        yp = np.zeros((b,))
        mask = np.zeros((b,))
        Xp[:n], yp[:n], mask[:n] = X, y, 1.0
        other = GP(kind=self.kind, noisy=self.noisy, steps=self.steps,
                   fit_tol=self.fit_tol)
        with jax.enable_x64(True):
            other._state = (params, jnp.asarray(Xp), jnp.asarray(yp),
                            jnp.asarray(mask))
        return other

    @property
    def params(self):
        return self._state[0] if self._state else None


@dataclasses.dataclass
class GPClassifier:
    """GP "classifier" for unknown (output) constraints (paper §3.4): GP
    regression on +/-1 labels with a probit link on the latent posterior --
    the standard cheap approximation used in constrained BO."""

    steps: int = 80
    _gp: GP | None = None

    def fit(self, X: np.ndarray, feasible: np.ndarray) -> "GPClassifier":
        y = np.where(np.asarray(feasible), 1.0, -1.0)
        self._gp = GP(kind="se", noisy=True, steps=self.steps).fit(X, y)
        return self

    def prob_feasible(self, Xs: np.ndarray) -> np.ndarray:
        """Host-side P(feasible): returns a plain NumPy array.  (The erf runs
        on the host -- a JAX array here would silently promote the whole
        acquisition computation in `bo_maximize` to device arrays with a
        blocking transfer per trial.)"""
        if self._gp is None:
            return np.ones(len(Xs))
        mu, var = self._gp.posterior(Xs)
        z = mu / np.sqrt(1.0 + var)
        return 0.5 * (1.0 + _erf(z / np.sqrt(2.0)))

    def prob_feasible_device(self, Xs) -> jax.Array:
        """Device-resident twin of `prob_feasible` for the fused scoring path.
        (The erf must trace under scoped x64, or its internal constants
        canonicalize to f32 and poison the f64 posterior's precision.  Even
        then jax's and scipy's erf differ by ~1e-8 -- implementation, not
        dtype -- so host/device probabilities agree to ~1e-8, far below
        anything the acquisition argmax can resolve.)"""
        if self._gp is None:
            return jnp.ones(len(Xs))
        mu, var = self._gp.posterior_device(Xs)
        with jax.enable_x64(True):
            z = mu / jnp.sqrt(1.0 + var)
            return 0.5 * (1.0 + jax.scipy.special.erf(z / np.sqrt(2.0)))


# --- stacked (multi-run) GPs ----------------------------------------------------

@functools.cache
def _fit_device():
    """The device `GPStack` fits on: the host's first CPU device where the
    default backend is a TPU (see the module docstring), else None, the
    default device.  Also None where no CPU backend is present
    (`JAX_PLATFORMS` naming the TPU alone)."""
    if jax.default_backend() != "tpu":
        return None
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


@functools.partial(jax.jit, static_argnames=("kind", "steps", "train_tau"))
def _fit_stack(params, X, y, mask, kind, steps, train_tau):
    """Batched `_fit` over the leading run axis (params leaves lead with L).

    `vmap`, not a loop over runs: the Adam scan runs once for the whole
    stack, each step on (L, ...) operands, so a fit's chain of dependent
    device ops is as long as one run's whether the stack holds 1 run or 16.
    (A `lax.map` runs the L scans one after another: on the TPU a fit then
    costs L times one run's chain of small emulated-f64 ops.)  The runs stay
    independent -- no op of the batched program mixes slices, so a run's fit
    does not depend on its partners or its place in the stack -- but batched
    linalg rounds differently from the single-run kernels, so a slice matches
    the single-run `_fit` to f64 roundoff rather than bit for bit.

    Above `_LOWRANK_MIN_ROWS` data rows the linear kernel (the objective
    surrogate) fits through the Woodbury NLL (`_fit_lowrank`): the per-trial
    refit is the layer-batched search's dominant cost, and the low-rank form
    cuts it from O(n^3) to O(n d^2) per Adam step, the cheaper of the two at
    every stack width from 24 rows up.  It computes the same NLL to f64
    roundoff, but through the ill-conditioned quad-term subtraction its
    gradients drift from the Cholesky path's by ~1e-8 relative, which after 80
    Adam steps perturbs the posterior at the ~1e-7 level -- statistically
    nothing, but further from the sequential fits than the buckets of 8 and
    16 rows keep (the bucket is a static shape, so the switch is
    deterministic and visible in the jit cache)."""
    lowrank = _fit_lowrank(kind, X.shape[1])
    return jax.vmap(
        lambda p, xx, yy, mm: _fit(p, xx, yy, mm, kind, steps, 0.05,
                                   train_tau, lowrank=lowrank)
    )(params, X, y, mask)


def _fit_lowrank(kind: str, rows: int) -> bool:
    """Whether a stacked fit of `rows` padded data rows fits through the
    Woodbury NLL: the linear kernel above `_LOWRANK_MIN_ROWS` rows."""
    return kind == "linear" and rows > _LOWRANK_MIN_ROWS


@functools.partial(jax.jit, static_argnames=("kind",))
def _posterior_stack(params, X, y, mask, Xs, kind):
    """Batched `_posterior`: (L, P, d) pools -> (L, P) mu/var (`vmap` over
    the run axis, as `_fit_stack`)."""
    return jax.vmap(
        lambda p, xx, yy, mm, xs: _posterior(p, xx, yy, mm, xs, kind)
    )(params, X, y, mask, Xs)


def _bucket_stack(n: int) -> int:
    """Finer-grained buckets for the stacked fit: multiples of 8 up to 64
    rows, multiples of 32 beyond.  The multi-run surrogate refit dominates the
    layer-batched search's per-trial cost, so the padding waste of
    power-of-two buckets (rows up to 2x -> Woodbury flops up to 2x and
    Cholesky flops up to 8x just below a boundary) costs more than the extra
    compile-cache entries.  The bucket also picks the linear fit's NLL:
    Cholesky at 8 and 16 rows, Woodbury from 24 up (`_fit_lowrank`).
    Padding rows are exactly zero-influence (see module docstring), so the
    bucket choice is otherwise purely a flops/compile-count tradeoff --
    results are unchanged."""
    if n <= 8:
        return 8
    step = 8 if n <= 64 else 32
    return -(-n // step) * step


def _pad_runs(Xs, ys) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack ragged per-run datasets to (L, b, d)/(L, b) with (L, b) masks,
    b = shared fine-grained bucket over the largest run."""
    L = len(Xs)
    d = Xs[0].shape[1]
    b = _bucket_stack(max(len(y) for y in ys))
    X = np.zeros((L, b, d))
    y = np.zeros((L, b))
    mask = np.zeros((L, b))
    for k, (Xk, yk) in enumerate(zip(Xs, ys)):
        n = len(yk)
        X[k, :n], y[k, :n], mask[k, :n] = Xk, yk, 1.0
    return X, y, mask


@functools.lru_cache(maxsize=None)
def _acq_device_cached(name: str, lam: float):
    """One device-acquisition closure per (name, lam): the SAME function the
    op-by-op scoring paths use, with a stable identity so it can serve as a
    static jit argument of `_score_stack` (a fresh closure per call would
    defeat the jit cache)."""
    from repro.core.acquisition import make_acquisition_device

    return make_acquisition_device(name, lam)


@functools.partial(jax.jit, static_argnames=("kind", "acq_fn"))
def _score_stack(params, X, y, mask, feats, best, kind, acq_fn):
    """Fused multi-run pool scoring: stacked posterior + acquisition + per-run
    argmax + winner-row gather, one compiled program.  The acquisition is the
    `make_acquisition_device` closure itself (traced inline), so the fused
    path computes exactly what the op-by-op paths compute -- no second copy of
    the acquisition math to drift."""
    mu, var = _posterior_stack(params, X, y, mask, feats, kind)
    util = acq_fn(mu, var, best)
    idx = jnp.argmax(util, axis=1)
    rows = jnp.take_along_axis(feats, idx[:, None, None], axis=1)[:, 0, :]
    return idx, rows


@dataclasses.dataclass
class GPStack:
    """L independent exact GP regressors, fit and queried as one batched
    program.  Per-slice numerics match the individual `GP` to f64 roundoff
    (the same `_fit` / `_posterior` bodies, `vmap`ped over the runs; padding
    is exactly zero-influence), so a stacked multi-run BO engine reproduces
    L sequential runs.

    The fit runs where `_fit_device` says: on the host's CPU device when the
    default backend is a TPU, which emulates float64 (counted in
    `gp.host_fits`).  Its params stay on the host until the first query
    copies them to the default device, so the caller goes on (sampling the
    next candidate pools) while the CPU fits; the padded data is copied
    there at fit time.  The posterior and the fused scoring run on the
    default device.

    kind / noisy / steps: as on `GP`, shared across the stack (the runs are
    peers -- per-layer searches of one hardware probe).
    """

    kind: str = "linear"
    noisy: bool = True
    steps: int = 80
    _state: tuple | None = None
    _params_on_host: bool = False

    def fit(self, Xs, ys) -> "GPStack":
        """Fit from per-run datasets: Xs[k] is (n_k, d), ys[k] is (n_k,).
        Traced as `codesign.gp`."""
        with trace.span("codesign.gp"):
            return self._fit_runs(Xs, ys)

    def _fit_runs(self, Xs, ys) -> "GPStack":
        Xs = [np.asarray(Xk, np.float64) for Xk in Xs]
        ys = [np.asarray(yk, np.float64) for yk in ys]
        X, y, mask = _pad_runs(Xs, ys)
        L, b, d = X.shape
        trace.COUNTERS["gp.fits"] += 1
        trace.COUNTERS["gp.runs"] += L
        trace.COUNTERS["gp.rows"] += sum(len(yk) for yk in ys)
        trace.COUNTERS["gp.slots"] += L * b
        to_device = trace.to_device
        host = _fit_device()
        # Copies onto the host's CPU device stay in host memory: not counted.
        put = to_device if host is None else jnp.asarray
        with jax.enable_x64(True):
            with (contextlib.nullcontext() if host is None
                  else jax.default_device(host)):
                params = jax.tree.map(
                    lambda leaf: jnp.broadcast_to(leaf, (L, *leaf.shape)),
                    _init_params(self.kind, d))
                params = dict(
                    params,
                    mean_const=put([float(yk.mean()) for yk in ys]),
                    log_tau=put(
                        [np.log(max(yk.std(), 1e-3) * 0.1) for yk in ys]
                        if self.noisy else [-6.0] * L),
                )
                params = _fit_stack(params, put(X), put(y), put(mask),
                                    self.kind, self.steps, self.noisy)
            trace.dispatched()
            self._state = (params, to_device(X), to_device(y),
                           to_device(mask))
        self._params_on_host = host is not None
        if self._params_on_host:
            trace.COUNTERS["gp.host_fits"] += 1
        if _fit_lowrank(self.kind, b):
            trace.COUNTERS["gp.lowrank_fits"] += 1
        return self

    def _fitted(self) -> tuple:
        """The fitted state, its params copied to the default device on the
        first query after a fit on the host (which waits for that fit)."""
        assert self._state is not None, "fit() first"
        if self._params_on_host:
            params, *data = self._state
            with jax.enable_x64(True):
                params = jax.tree.map(
                    lambda leaf: trace.to_device(np.asarray(leaf)), params)
            self._state = (params, *data)
            self._params_on_host = False
        return self._state

    def __len__(self) -> int:
        return int(self._state[1].shape[0]) if self._state else 0

    def posterior(self, Xs) -> tuple[np.ndarray, np.ndarray]:
        mu, var = self.posterior_device(Xs)
        return trace.fetch(mu), trace.fetch(var)

    def posterior_device(self, Xs) -> tuple[jax.Array, jax.Array]:
        """Stacked posterior: Xs is (L, P, d) -- one candidate pool per run --
        returning (L, P) device arrays (the fused multi-run scoring path)."""
        params, Xp, yp, mask = self._fitted()
        trace.dispatched()
        with jax.enable_x64(True):
            Xs = trace.to_device(Xs, jnp.float64)
            return _posterior_stack(params, Xp, yp, mask, Xs, self.kind)

    def score_device(
        self, feats, best, acquisition: str = "lcb", lam: float = 1.0,
    ) -> tuple[np.ndarray, np.ndarray]:
        """One-dispatch pool scoring for the multi-run BO trial: stacked
        posterior, acquisition (vs per-run incumbents `best`, shape (L, 1)),
        per-run argmax, and the winners' feature rows -- only the (L,) indices
        and (L, d) rows return to the host.  Traced as `codesign.gp`."""
        acq_fn = _acq_device_cached(acquisition, float(lam))
        with trace.span("codesign.gp"):
            params, Xp, yp, mask = self._fitted()
            with jax.enable_x64(True):
                idx, rows = _score_stack(
                    params, Xp, yp, mask,
                    trace.to_device(feats, jnp.float64),
                    trace.to_device(best, jnp.float64),
                    self.kind, acq_fn)
            trace.dispatched()
            return trace.fetch(idx), trace.fetch(rows, np.float64)


@dataclasses.dataclass
class GPClassifierStack:
    """Stacked twin of `GPClassifier`: L per-run feasibility classifiers
    (SE-kernel GP regression on +/-1 labels, probit link) fit as one batched
    program for the multi-run BO engine's unknown-constraint weighting."""

    steps: int = 80
    _stack: GPStack | None = None

    def fit(self, Xs, feas) -> "GPClassifierStack":
        """Traced as `codesign.gp`."""
        with trace.span("codesign.gp"):
            ys = [np.where(np.asarray(f), 1.0, -1.0) for f in feas]
            self._stack = GPStack(kind="se", noisy=True,
                                  steps=self.steps).fit(Xs, ys)
        return self

    def prob_feasible(self, Xs) -> np.ndarray:
        """Host-side (L, P) P(feasible) -- NumPy + scipy erf, mirroring
        `GPClassifier.prob_feasible` exactly so the multi-run host scoring
        path picks the same candidates as L sequential runs."""
        assert self._stack is not None, "fit() first"
        mu, var = self._stack.posterior(Xs)
        z = mu / np.sqrt(1.0 + var)
        return 0.5 * (1.0 + _erf(z / np.sqrt(2.0)))

    def prob_feasible_device(self, Xs) -> jax.Array:
        """(L, P) P(feasible) as device arrays (see `GPClassifier` notes on
        erf precision under scoped x64)."""
        assert self._stack is not None, "fit() first"
        mu, var = self._stack.posterior_device(Xs)
        with jax.enable_x64(True):
            z = mu / jnp.sqrt(1.0 + var)
            return 0.5 * (1.0 + jax.scipy.special.erf(z / np.sqrt(2.0)))
