"""Layer-batched nested search: the multi-run BO engine (`bo_maximize_many` /
`LayerStackSpace` / `GPStack`) against the sequential per-layer path.

Parity bars (ISSUE 3):
  * NumPy fallback: same seeds => *identical* best mappings / EDPs (the
    lockstep engine reproduces L sequential `bo_maximize` runs bit-for-bit in
    the small-bucket Cholesky regime these tests run in);
  * JAX f64: <= 1e-6 relative EDP (in practice also identical here);
  * all four seed workload sets (ResNet / DQN / MLP / Transformer).

Plus units for the stacked building blocks: `forward_device_stacked` row
parity, `GPStack`/`GPClassifierStack` vs individual fits, the low-rank linear
NLL, the batched hardware-pool protocol, and the end-to-end `gp_refit_every`
threading (which also exercises the multi-cohort refit schedule).
"""

import jax
import numpy as np
import pytest

from repro.core.bo import BOResult, bo_maximize, bo_maximize_many
from repro.core.gp import GP, GPClassifier, GPClassifierStack, GPStack
from repro.core.hwspace import HardwareSpace
from repro.core.config import (CodesignConfig, EngineConfig, HWSearchConfig,
                               SWSearchConfig)
from repro.core.nested import (CodesignEngine, optimize_software,
                               optimize_software_many)
from repro.core.swspace import LayerStackSpace, SoftwareSpace
from repro.timeloop import MODEL_LAYERS, eyeriss_168
from repro.timeloop import batch as tlb
from repro.timeloop import batch_jax as jtlb

MODELS = ("resnet", "dqn", "mlp", "transformer")
# Budgets chosen to stay inside the stacked fit's Cholesky regime
# (<= gp._LOWRANK_MIN_ROWS = 16 padded data rows: 14 trials), where
# lockstep == sequential exactly.
KW = dict(n_trials=14, n_warmup=6, pool_size=20, seed=3)


def _assert_run_parity(seq: BOResult, many: BOResult, backend: str):
    assert many.best_point == seq.best_point
    # Same winner => identical EDP; the histories pin the whole trajectory.
    assert np.array_equal(many.history, seq.history)
    if seq.best_point is not None:
        edp_s, edp_m = 10.0 ** -seq.best_value, 10.0 ** -many.best_value
        assert edp_m == pytest.approx(edp_s, rel=1e-6)  # ISSUE bar (jax f64)


@pytest.mark.parametrize("model", MODELS)
def test_layer_batched_matches_sequential_numpy(model):
    hw = eyeriss_168()
    layers = MODEL_LAYERS[model]
    seq = [optimize_software(hw, ly, backend="numpy", **KW) for ly in layers]
    many = optimize_software_many(hw, layers, backend="numpy", **KW)
    assert len(many) == len(layers)
    for rs, rm in zip(seq, many):
        _assert_run_parity(rs, rm, "numpy")


@pytest.mark.parametrize("model", MODELS)
def test_layer_batched_matches_sequential_jax(model):
    hw = eyeriss_168()
    layers = MODEL_LAYERS[model]
    seq = [optimize_software(hw, ly, backend="jax", **KW) for ly in layers]
    many = optimize_software_many(hw, layers, backend="jax", **KW)
    for rs, rm in zip(seq, many):
        _assert_run_parity(rs, rm, "jax")


def _codesign(layers, n_hw, n_sw, n_sw_warmup, pool, seed, strategy="auto",
              **engine):
    config = CodesignConfig(
        sw=SWSearchConfig(n_trials=n_sw, n_warmup=n_sw_warmup, pool_size=pool),
        hw=HWSearchConfig(n_trials=n_hw, pool_size=pool),
        engine=EngineConfig(strategy=strategy, **engine), seed=seed)
    return CodesignEngine(config).run(layers)


def test_codesign_layer_batched_identical_to_sequential():
    """The speculative path collapses eval_hw's layer loop into one
    bo_maximize_many call per probe; with the shared (hw, layer) cache the
    whole nested search must land on the same design as the sequential path."""
    layers = MODEL_LAYERS["dqn"]
    kw = dict(n_hw=3, n_sw=12, n_sw_warmup=6, pool=20, seed=0,
              backend="numpy")
    r_seq = _codesign(layers, strategy="sequential", **kw)
    r_lb = _codesign(layers, strategy="speculative", **kw)
    assert r_lb.best_hw == r_seq.best_hw
    assert r_lb.best_model_edp == r_seq.best_model_edp
    assert r_lb.best_mappings == r_seq.best_mappings
    assert np.array_equal(r_lb.hw_result.history, r_seq.hw_result.history)


def test_codesign_layer_batched_defaults_by_backend():
    """strategy="auto" resolves to the backend: speculative on jax,
    sequential on numpy (forcing speculative on jax gives the same run)."""
    layers = MODEL_LAYERS["dqn"]
    kw = dict(n_hw=2, n_sw=10, n_sw_warmup=5, pool=16, seed=1, backend="jax")
    r = _codesign(layers, **kw)  # auto -> speculative
    assert r.best_hw is not None and np.isfinite(r.best_model_edp)
    r2 = _codesign(layers, strategy="speculative", **kw)
    assert r2.best_model_edp == r.best_model_edp


# --- stacked forward ------------------------------------------------------------


def test_forward_device_stacked_matches_per_layer():
    """The (L*B,)-row fused program computes per row exactly what L separate
    forward_device calls compute; rows past a pool's length are padding."""
    hw = eyeriss_168()
    layers = [MODEL_LAYERS["resnet"][0], MODEL_LAYERS["dqn"][1],
              MODEL_LAYERS["mlp"][0], MODEL_LAYERS["transformer"][2]]
    rng = np.random.default_rng(0)
    pools = [tlb.sample_valid_pool(rng, hw, ly, 12 + 5 * i)
             for i, ly in enumerate(layers)]
    out = jtlb.forward_device_stacked(hw, pools, layers)
    B = max(len(p) for p in pools)
    assert out["features"].shape == (len(layers), B, 14)
    for k, (p, ly) in enumerate(zip(pools, layers)):
        ref = jtlb.forward_device(hw, p, ly)
        n = len(p)
        np.testing.assert_array_equal(
            np.asarray(out["valid"][k][:n]), np.asarray(ref["valid"]))
        for key in ("edp", "utility", "features"):
            np.testing.assert_allclose(
                np.asarray(out[key][k][:n]), np.asarray(ref[key]), rtol=1e-12)
        assert not np.asarray(out["valid"][k][n:]).any()


def test_forward_device_stacked_interpret_mode():
    """The Pallas-kernel path handles the stacked row count (L*bucket is not
    a multiple of the 128-row lane block) by padding to whole blocks."""
    hw = eyeriss_168()
    layers = MODEL_LAYERS["resnet"][:3]
    rng = np.random.default_rng(1)
    pools = [tlb.sample_valid_pool(rng, hw, ly, 10) for ly in layers]
    ref = jtlb.forward_device_stacked(hw, pools, layers, mode="jnp")
    out = jtlb.forward_device_stacked(hw, pools, layers, mode="interpret")
    np.testing.assert_array_equal(np.asarray(out["valid"]),
                                  np.asarray(ref["valid"]))
    v = np.asarray(ref["valid"])
    np.testing.assert_allclose(np.asarray(out["edp"])[v],
                               np.asarray(ref["edp"])[v], rtol=1e-12)


def test_layer_stack_space_protocol():
    hw = eyeriss_168()
    layers = MODEL_LAYERS["dqn"]
    spaces = [SoftwareSpace(hw, ly, backend="jax") for ly in layers]
    stack = LayerStackSpace.maybe(spaces)
    assert stack is not None and stack.supports_device
    rng = np.random.default_rng(0)
    pools = [s.sample_pool(rng, 8) for s in spaces]
    fwd = stack.forward_stacked(pools)
    for k, s in enumerate(spaces):
        np.testing.assert_allclose(
            fwd["features"][k], s.features_batch(pools[k]), rtol=1e-12)
        vals, feas = s.evaluate_batch(pools[k])
        np.testing.assert_array_equal(fwd["valid"][k], feas)
        np.testing.assert_allclose(fwd["utility"][k], vals, rtol=1e-12)
    # mixed-backend / non-software spaces don't stack
    assert LayerStackSpace.maybe(
        [SoftwareSpace(hw, layers[0], backend="jax"),
         SoftwareSpace(hw, layers[1], backend="numpy")]) is None
    assert LayerStackSpace.maybe([HardwareSpace()]) is None


# --- stacked GPs ----------------------------------------------------------------


def test_gp_stack_matches_individual_fits():
    """Each slice of a GPStack reproduces the corresponding individual GP fit
    (ragged run sizes share one padded bucket; padding is zero-influence)."""
    rng = np.random.default_rng(0)
    Xs = [rng.normal(size=(n, 5)) for n in (6, 13, 26)]
    ys = [X @ np.array([1.0, -2.0, 0.5, 0.0, 3.0]) + 0.05 * rng.normal(size=len(X))
          for X in Xs]
    pools = np.stack([rng.normal(size=(9, 5)) for _ in Xs])
    for kind in ("linear", "se"):
        for noisy in (False, True):
            stack = GPStack(kind=kind, noisy=noisy).fit(Xs, ys)
            mu_s, var_s = stack.posterior(pools)
            for k, (X, y) in enumerate(zip(Xs, ys)):
                mu, var = GP(kind=kind, noisy=noisy).fit(X, y).posterior(pools[k])
                np.testing.assert_allclose(mu_s[k], mu, atol=1e-8)
                np.testing.assert_allclose(var_s[k], var, atol=1e-8)


@pytest.mark.parametrize("sizes", [(27, 32), (40, 52)],
                         ids=["bucket-32", "bucket-56"])
def test_gp_stack_lowrank_regime_close_to_cholesky(sizes):
    """Above the row threshold the linear-kernel stack fits through the
    Woodbury NLL; the posterior agrees with the Cholesky fit to far below
    anything an acquisition argmax can resolve at those data sizes."""
    from repro.core import gp

    rng = np.random.default_rng(1)
    Xs = [rng.normal(size=(n, 6)) for n in sizes]
    assert gp._fit_lowrank("linear", gp._bucket_stack(max(sizes)))
    ys = [X @ rng.normal(size=6) + 0.05 * rng.normal(size=len(X)) for X in Xs]
    stack = GPStack(kind="linear", noisy=False).fit(Xs, ys)
    pools = np.stack([rng.normal(size=(7, 6)) for _ in Xs])
    mu_s, _ = stack.posterior(pools)
    for k, (X, y) in enumerate(zip(Xs, ys)):
        mu, _ = GP(kind="linear", noisy=False).fit(X, y).posterior(pools[k])
        np.testing.assert_allclose(mu_s[k], mu, atol=1e-5)


def _fit_and_score_stack(X, y, mask, pools):
    """`GPStack.fit`'s linear objective fit and posterior on padded stacks,
    called directly so a slice of the stack keeps the stack's bucket."""
    import jax.numpy as jnp

    from repro.core import gp

    L, _, d = X.shape
    n = mask.sum(axis=1)
    mean = (y * mask).sum(axis=1) / n
    std = np.sqrt((((y - mean[:, None]) * mask) ** 2).sum(axis=1) / n)
    with jax.enable_x64(True):
        params = jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf, (L, *leaf.shape)),
            gp._init_params("linear", d))
        params = dict(params, mean_const=jnp.asarray(mean),
                      log_tau=jnp.asarray(np.log(np.maximum(std, 1e-3) * 0.1)))
        args = [jnp.asarray(a) for a in (X, y, mask)]
        fitted = gp._fit_stack(params, *args, "linear", 80, True)
        mu, var = gp._posterior_stack(fitted, *args, jnp.asarray(pools),
                                      "linear")
        return ({k: np.asarray(v) for k, v in fitted.items()},
                np.asarray(mu), np.asarray(var))


@pytest.mark.parametrize("bucket, atol",
                         [(16, 1e-10), (32, 1e-8), (40, 1e-8)],
                         ids=["cholesky", "woodbury-32", "woodbury"])
def test_gp_stack_runs_fit_alone_as_in_the_batch(bucket, atol):
    """Batching the run axis couples no runs: each run of a ragged 16-run
    linear stack, fit alone in a stack of 1 at the same bucket, gives the
    same hyperparameters and posterior; so does the stack in reverse order.
    Bucket 16 fits through the Cholesky NLL, buckets 32 and 40 through
    Woodbury."""
    from repro.core import gp

    rng = np.random.default_rng(4)
    L, d, P = 16, 14, 9
    sizes = rng.integers(bucket - 12, bucket + 1, size=L)
    sizes[3] = bucket
    Xs = [rng.normal(size=(n, d)) for n in sizes]
    ys = [X @ rng.normal(size=d) + 0.05 * rng.normal(size=len(X)) for X in Xs]
    X, y, mask = gp._pad_runs(Xs, ys)
    assert X.shape[1] == bucket
    assert gp._fit_lowrank("linear", bucket) == (bucket > 16)
    pools = rng.normal(size=(L, P, d))
    params, mu, var = _fit_and_score_stack(X, y, mask, pools)
    rev = slice(None, None, -1)
    params_r, mu_r, var_r = _fit_and_score_stack(X[rev], y[rev], mask[rev],
                                                 pools[rev])
    for k in range(L):
        one = slice(k, k + 1)
        params_1, mu_1, var_1 = _fit_and_score_stack(X[one], y[one],
                                                     mask[one], pools[one])
        for name, leaf in params.items():
            np.testing.assert_allclose(leaf[k], params_1[name][0], rtol=0,
                                       atol=atol)
            np.testing.assert_allclose(leaf[k], params_r[name][L - 1 - k],
                                       rtol=0, atol=atol)
        for stacked, alone, reverse in ((mu, mu_1, mu_r), (var, var_1, var_r)):
            np.testing.assert_allclose(stacked[k], alone[0], rtol=0,
                                       atol=atol)
            np.testing.assert_allclose(stacked[k], reverse[L - 1 - k],
                                       rtol=0, atol=atol)


@pytest.fixture
def fit_device_cleared():
    """`gp._fit_device` is worked out once a process: clear it before and
    after a test that steers what it reads, so that neither this test nor
    a later one sees a stale answer."""
    from repro.core import gp

    gp._fit_device.cache_clear()
    yield gp
    gp._fit_device.cache_clear()


def _fit_and_query_stacks(Xs, ys, feas, pools, best):
    """A linear `GPStack` and a `GPClassifierStack` fit on the same runs,
    and every query of them: posterior, fused picks, P(feasible) both ways.
    Returns the stacks (as left after the queries) and the answers."""
    stack = GPStack(kind="linear").fit(Xs, ys)
    clf = GPClassifierStack().fit(Xs, feas)
    fitted_on_host = (stack._params_on_host, clf._stack._params_on_host)
    mu, var = stack.posterior(pools)
    idx, rows = stack.score_device(pools, best)
    answers = (mu, var, idx, rows, clf.prob_feasible(pools),
               np.asarray(clf.prob_feasible_device(pools)))
    return stack, clf, fitted_on_host, answers


@pytest.mark.parametrize("placed", [True, False],
                         ids=["placement-on", "placement-off"])
def test_gp_stack_fit_placed_on_the_host_cpu_under_a_tpu(
        placed, fit_device_cleared, monkeypatch):
    """Where the default backend is a TPU (here `jax.default_backend` is
    made to say so), the stacked fits of `GPStack` and `GPClassifierStack`
    run on the host's CPU device in float64, `gp.host_fits` counts them,
    the fitted state lands on the default device, and every posterior and
    pick is the unplaced run's bit for bit.  On a CPU-only host the CPU
    device is also the default one, so where the fit ran is read from the
    default device that `jax.default_device` names while `_fit_stack`
    runs."""
    from repro.core import trace

    gp = fit_device_cleared
    rng = np.random.default_rng(7)
    d, P = 14, 9
    Xs = [rng.normal(size=(n, d)) for n in (5, 12, 20)]
    ys = [X @ rng.normal(size=d) + 0.05 * rng.normal(size=len(X))
          for X in Xs]
    feas = [X[:, 0] > 0 for X in Xs]
    pools = rng.normal(size=(len(Xs), P, d))
    best = np.array([[y.min()] for y in ys])
    *_, reference = _fit_and_query_stacks(Xs, ys, feas, pools, best)

    fits = []
    fit_stack = gp._fit_stack

    def spy(*args):
        out = fit_stack(*args)
        fits.append((jax.config.jax_default_device, out))
        return out

    monkeypatch.setattr(gp, "_fit_stack", spy)
    if placed:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    gp._fit_device.cache_clear()
    cpu, default = jax.devices("cpu")[0], jax.devices()[0]
    before = trace.counters_snapshot()
    stack, clf, on_host, answers = _fit_and_query_stacks(Xs, ys, feas,
                                                         pools, best)
    host_fits = (trace.COUNTERS["gp.host_fits"]
                 - before.get("gp.host_fits", 0))
    assert trace.COUNTERS["gp.fits"] - before.get("gp.fits", 0) == 2
    assert host_fits == (2 if placed else 0)
    assert on_host == (placed, placed)
    assert len(fits) == 2
    for where, params in fits:
        assert where == (cpu if placed else None)
        for leaf in jax.tree.leaves(params):
            assert leaf.dtype == np.float64
            assert leaf.devices() == {cpu}
    for s in (stack, clf._stack):
        assert not s._params_on_host
        for leaf in jax.tree.leaves(s._state):
            assert leaf.dtype == np.float64
            assert leaf.devices() == {default}
    for got, want in zip(answers, reference):
        np.testing.assert_array_equal(got, want)


def test_fit_device_is_the_default_off_the_tpu_or_without_a_cpu_backend(
        fit_device_cleared, monkeypatch):
    """`_fit_device` is None (the default device) where the backend is the
    CPU, and where the backend is a TPU but no CPU backend is present; the
    fit then runs on the default device and `gp.host_fits` stays put."""
    from repro.core import trace

    gp = fit_device_cleared
    assert gp._fit_device() is None
    devices = jax.devices

    def no_cpu(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return devices(backend)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", no_cpu)
    gp._fit_device.cache_clear()
    assert gp._fit_device() is None
    rng = np.random.default_rng(8)
    Xs = [rng.normal(size=(n, 6)) for n in (4, 7)]
    before = trace.counters_snapshot()
    stack = GPStack(kind="linear").fit(Xs, [rng.normal(size=len(X))
                                             for X in Xs])
    assert not stack._params_on_host
    assert trace.COUNTERS["gp.fits"] - before.get("gp.fits", 0) == 1
    assert (trace.COUNTERS["gp.host_fits"]
            - before.get("gp.host_fits", 0)) == 0
    mu, _ = stack.posterior(rng.normal(size=(2, 3, 6)))
    assert mu.shape == (2, 3) and np.isfinite(mu).all()


def test_gp_classifier_stack_matches_individual():
    rng = np.random.default_rng(2)
    Xs = [rng.normal(size=(n, 3)) for n in (18, 30)]
    feas = [X[:, 0] > 0 for X in Xs]
    cs = GPClassifierStack().fit(Xs, feas)
    pools = np.stack([rng.normal(size=(6, 3)) for _ in Xs])
    ps = cs.prob_feasible(pools)
    pd = np.asarray(cs.prob_feasible_device(pools))
    for k, (X, f) in enumerate(zip(Xs, feas)):
        p = GPClassifier().fit(X, f).prob_feasible(pools[k])
        np.testing.assert_allclose(ps[k], p, atol=1e-8)
        np.testing.assert_allclose(pd[k], p, atol=1e-6)


def test_lowrank_nll_matches_cholesky_nll():
    import jax.numpy as jnp

    from repro.core.gp import _init_params, _nll, _nll_linear_lowrank

    rng = np.random.default_rng(3)
    n, npad, d = 21, 32, 7
    X = np.zeros((npad, d)); y = np.zeros(npad); mask = np.zeros(npad)
    X[:n] = rng.normal(size=(n, d)); y[:n] = rng.normal(size=n); mask[:n] = 1.0
    with jax.enable_x64(True):
        params = dict(_init_params("linear", d),
                      mean_const=jnp.asarray(0.4), log_tau=jnp.asarray(-6.0),
                      log_w=jnp.asarray(rng.normal(size=d) * 0.3),
                      log_bias=jnp.asarray(0.1))
        a = float(_nll(params, jnp.asarray(X), jnp.asarray(y),
                       jnp.asarray(mask), "linear"))
        b = float(_nll_linear_lowrank(params, jnp.asarray(X), jnp.asarray(y),
                                      jnp.asarray(mask)))
    assert b == pytest.approx(a, rel=1e-8)


# --- batched hardware pools -----------------------------------------------------


def test_hardware_space_batched_protocol():
    from repro.timeloop.arch import hw_is_valid

    sp = HardwareSpace(num_pes=168)
    assert sp.supports_batch
    rng = np.random.default_rng(0)
    pool = sp.sample_pool(rng, 64)
    assert len(pool) == 64
    assert all(hw_is_valid(hw)[0] for hw in pool)
    feats = sp.features_batch(pool)
    ref = np.stack([sp.features(hw) for hw in pool])
    np.testing.assert_array_equal(feats, ref)  # bitwise twin of the scalar path


def test_hardware_space_bo_takes_batched_path():
    """The outer BO loop runs the hardware space through the batched protocol
    end-to-end (warmup pool + per-trial acquisition pools) with a synthetic
    evaluator, including unknown-constraint (infeasible) outcomes."""
    def eval_fn(hw):
        if hw.df_fw == 2:  # synthetic unknown constraint
            return None, False
        return -float(np.log10(hw.lb_input + 2.0 * hw.lb_output)), True

    sp = HardwareSpace(num_pes=168, evaluate_fn=eval_fn)
    r = bo_maximize(sp, n_trials=14, n_warmup=6, pool_size=16, noisy=True,
                    seed=0)
    assert len(r.history) == 14
    assert np.isfinite(r.best_value)
    assert r.n_infeasible > 0  # classifier path exercised


# --- gp_refit_every threading + multi-cohort schedule ---------------------------


def test_gp_refit_every_parity_and_threading():
    """The amortization stride is reachable end-to-end and the lockstep
    engine's cohort schedule reproduces the sequential per-run refit schedule
    (runs whose surrogate first fits off-schedule form their own cohort)."""
    hw = eyeriss_168()
    layers = MODEL_LAYERS["mlp"]
    kw = dict(n_trials=14, n_warmup=6, pool_size=20, seed=5,
              gp_refit_every=4, backend="numpy")
    seq = [optimize_software(hw, ly, **kw) for ly in layers]
    many = optimize_software_many(hw, layers, **kw)
    for rs, rm in zip(seq, many):
        assert rm.best_point == rs.best_point
        assert np.array_equal(rm.history, rs.history)
    r = _codesign(MODEL_LAYERS["dqn"], n_hw=2, n_sw=10, n_sw_warmup=5,
                  pool=16, seed=0, gp_refit_every=3, backend="numpy")
    assert np.isfinite(r.best_model_edp)


# --- engine fallbacks / early-stop ----------------------------------------------


class _BatchQuad:
    """Minimal batched-protocol space: maximize -(x-c)^2 over [-1, 1]^3."""

    name = "quad"
    feature_dim = 3
    supports_batch = True

    def __init__(self, c, fail=False):
        self.c = np.asarray(c, dtype=np.float64)
        self.fail = fail

    def sample(self, rng):
        return rng.uniform(-1, 1, 3)

    def is_valid(self, x):
        return True

    def features(self, x):
        return np.asarray(x, dtype=np.float64)

    def evaluate(self, x):
        return -float(np.sum((np.asarray(x) - self.c) ** 2)), True

    def sample_pool(self, rng, n):
        if self.fail:
            return None
        return [self.sample(rng) for _ in range(n)]

    def features_batch(self, pool):
        return np.asarray(pool, dtype=np.float64)

    def evaluate_batch(self, pool):
        vals = -np.sum((np.asarray(pool) - self.c) ** 2, axis=1)
        return vals, np.ones(len(pool), dtype=bool)


def test_bo_maximize_many_generic_spaces_match_sequential():
    """Spaces that don't stack (not SoftwareSpace) still advance in lockstep
    through per-space batched calls, matching sequential runs exactly."""
    cs = ([0.3, -0.2, 0.5], [-0.4, 0.1, 0.0], [0.0, 0.6, -0.3])
    seq = [bo_maximize(_BatchQuad(c), n_trials=16, n_warmup=6, pool_size=24,
                       seed=7) for c in cs]
    many = bo_maximize_many([_BatchQuad(c) for c in cs], n_trials=16,
                            n_warmup=6, pool_size=24, seed=7)
    for rs, rm in zip(seq, many):
        assert np.array_equal(rm.best_point, rs.best_point)
        assert np.array_equal(rm.history, rs.history)


def test_bo_maximize_many_early_stop_mask():
    """A run whose space is unsampleable finishes early with an empty result;
    the other runs are unaffected."""
    good, bad = _BatchQuad([0.2, 0.2, 0.2]), _BatchQuad([0.0] * 3, fail=True)
    ref = bo_maximize_many([good], n_trials=12, n_warmup=5, pool_size=16, seed=1)
    many = bo_maximize_many([_BatchQuad([0.2, 0.2, 0.2]), bad],
                            n_trials=12, n_warmup=5, pool_size=16, seed=1)
    assert many[1].best_point is None and many[1].history == []
    assert np.array_equal(many[0].history, ref[0].history)


def test_bo_maximize_many_fallbacks():
    sp = _BatchQuad([0.1, 0.1, 0.1])
    assert bo_maximize_many([], n_trials=4) == []
    (single,) = bo_maximize_many([sp], n_trials=10, n_warmup=4, pool_size=12,
                                 seed=2)
    ref = bo_maximize(_BatchQuad([0.1, 0.1, 0.1]), n_trials=10, n_warmup=4,
                      pool_size=12, seed=2)
    assert np.array_equal(single.history, ref.history)
    rf = bo_maximize_many([_BatchQuad([0.1] * 3), _BatchQuad([0.2] * 3)],
                          n_trials=10, n_warmup=4, pool_size=12, seed=2,
                          surrogate="rf")
    assert all(np.isfinite(r.best_value) for r in rf)
