"""JAX backend for the batched mapping-evaluation protocol.

Drop-in twin of `repro.timeloop.batch` (the NumPy engine) over the same packed
encoding -- `MappingBatch.factors` int (B, 5, 6) plus (B, 6) loop-order
permutations -- with the whole per-trial pipeline traced into one jitted device
program:

  valid_batch      (B,) bool      validity masks (exact parity with NumPy)
  evaluate_batch   dict of (B,)   energy / delay / EDP / -log10(EDP) utility
  features_batch   (B, 14)        the BO surrogate's feature matrix
  forward_device   dict of jax.Array -- everything above, device-resident, for
                   fused GP-acquisition pool scoring (`core.bo` consumes this
                   through `SoftwareSpace.features_batch_device`)

Structure: per-mapping tile/validity/gather prep is a `jax.vmap` of
`_prep_one`; the inner trip-count/energy reduction is
`repro.kernels.edp_reduce` -- the compiled Pallas kernel on TPU
(`mode="pallas"`, the default there), the same numerics as plain `jnp` ops
(`mode="jnp"`, the default off-TPU), or the kernel body through the Pallas
interpreter (`mode="interpret"`, exercised in CI).

Hardware and layer parameters enter as *arrays* (`hw_vec` / `layer_vec`), not
static arguments, so one compiled program serves every (hardware, layer) pair
the nested co-design search probes; pools are padded to power-of-two buckets so
the jit cache stays small across pool sizes.  Both vectors are carried *per
row* -- the rows of one batch may belong to different layers AND different
hardware configs -- which is what lets `forward_device_stacked` pack candidate
pools into a single stacked device program: all L layers of one hardware probe
(the layer-batched nested search, (L*B,) rows), or all H*L (probe, layer)
searches of the outer loop's warmup fan-out (`strategy="speculative"`,
(H*L*B,) rows).  Either way it is the *same* jitted `_forward` program as the
single-layer path, so per-row results are identical.

Precision: off-TPU the engine computes in float64 by default (scoped via
`jax.enable_x64(True)` -- no global flag is touched), which keeps parity with
the NumPy engine at ~1e-12.  On TPU the default is float32, which meets the
1e-6 EDP bar with exact validity masks.  The outputs carry the dtype they were
computed in; the f64 ones are read on the host or inside another x64 scope.

Backend selection from the search stack: `SoftwareSpace(..., backend="jax")`,
`EngineConfig(backend="jax")`, `benchmarks/run.py --backend jax`, or the
`REPRO_BACKEND=jax` environment variable (see README).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.kernels.edp_reduce import edp_reduce, reduce_edp_terms
from repro.timeloop.arch import HardwareConfig
from repro.timeloop.batch import (
    D_R,
    D_S,
    L_DRAM,
    L_GB,
    L_LB,
    L_SX,
    L_SY,
    MappingBatch,
    PaddingPool,
    REL_MASKS,
    TENSORS,
)
from repro.timeloop.mapping import LEVELS
from repro.timeloop.workloads import DIMS, ConvLayer

N_DIMS = len(DIMS)
N_LEVELS = len(LEVELS)

# (3, 6) relevance masks, tensors in TENSORS order (W, I, O), dims in DIMS order.
_REL = np.stack([REL_MASKS[t] for t in TENSORS]).astype(np.float64)

# hw_vec layout: validity bounds first, then energy/bandwidth constants.
(H_LBW, H_LBI, H_LBO, H_GBE, H_MX, H_MY, H_DFW, H_DFH,
 H_EMAC, H_ELB, H_ENOC, H_EGB, H_EDRAM, H_GBBW, H_DRAMBW) = range(15)
# layer_vec layout: the six loop extents (DIMS order), stride, macs.
L_STRIDE, L_MACS = 6, 7


def hw_vec(hw: HardwareConfig) -> np.ndarray:
    """Hardware constants as a (15,) float vector (see index constants above)."""
    e = hw.energy
    return np.array(
        [
            hw.lb_weight, hw.lb_input, hw.lb_output, hw.gb_entries,
            hw.pe_mesh_x, hw.pe_mesh_y, hw.df_fw, hw.df_fh,
            e.mac, e.lb, e.noc, hw.gb_access_energy, e.dram,
            hw.gb_bandwidth, hw.dram_bandwidth,
        ],
        dtype=np.float64,
    )


def layer_vec(layer: ConvLayer) -> np.ndarray:
    """Layer constants as an (8,) float vector: dims, stride, macs."""
    return np.array(
        [*(layer.dim(d) for d in DIMS), layer.stride, layer.macs],
        dtype=np.float64,
    )


def layer_vecs(layers) -> np.ndarray:
    """(L, 8) stacked layer vectors for the layer-batched forward."""
    return np.stack([layer_vec(layer) for layer in layers])


def hw_vecs(hws) -> np.ndarray:
    """(L, 15) stacked hardware vectors for the probe-stacked forward."""
    return np.stack([hw_vec(hw) for hw in hws])


def _prep_one(factors, order_gb, order_dram, hwv, layv):
    """Per-mapping tiles, validity, and gathered reduction operands.

    factors: (5, 6) float, orders: (6,) int, layv: (8,) -- one row of the
    packed pool (the layer vector is per-row so stacked multi-layer pools work).
    Returns (ok, fo (2,6), relo (2,3,6), tiles (2,3), sp (6,), sx, sy).
    All quantities entering the validity comparisons are < 2^24, so they are
    exact in float32 as well as float64 -- masks never depend on the dtype.
    """
    dims = layv[:N_DIMS]
    stride = layv[L_STRIDE]

    def ext(p, r):  # input halo extent, same formula as ConvLayer.input_extent
        return (p - 1.0) * stride + r

    def tiles(f):
        r, s, p, q, c, k = (f[i] for i in range(N_DIMS))
        return jnp.stack([r * s * c * k, ext(p, r) * ext(q, s) * c, p * q * k])

    lb = tiles(factors[L_LB])
    gbt = tiles(jnp.prod(factors[: L_GB + 1], axis=0))

    ok = jnp.all(jnp.prod(factors, axis=0) == dims)
    ok &= jnp.where(hwv[H_DFW] == 2.0, factors[L_LB, D_S] == dims[D_S], True)
    ok &= jnp.where(hwv[H_DFH] == 2.0, factors[L_LB, D_R] == dims[D_R], True)
    ok &= (lb[0] <= hwv[H_LBW]) & (lb[1] <= hwv[H_LBI]) & (lb[2] <= hwv[H_LBO])
    ok &= jnp.sum(gbt) <= hwv[H_GBE]
    sx = jnp.prod(factors[L_SX])
    sy = jnp.prod(factors[L_SY])
    ok &= (sx <= hwv[H_MX]) & (sy <= hwv[H_MY])

    rel = jnp.asarray(_REL, factors.dtype)  # (3, 6) compile-time constant
    sp = factors[L_SX] * factors[L_SY]      # (6,) per-dim spatial factors
    sp_rel = jnp.prod(jnp.where(rel > 0.5, sp[None, :], 1.0), axis=1)
    fo = jnp.stack([factors[L_GB][order_gb], factors[L_DRAM][order_dram]])
    relo = jnp.stack([rel[:, order_gb], rel[:, order_dram]])
    spv = jnp.concatenate(
        [sp_rel, jnp.stack([jnp.prod(sp), sx * sy, layv[L_MACS]])])
    return ok, fo, relo, jnp.stack([lb, gbt]), spv, sx, sy


@functools.partial(jax.jit, static_argnames=("mode",))
def _forward(factors, order_gb, order_dram, hwv, layv, mode: str):
    """The fused device program: validity + EDP + features for a whole pool.

    `hwv` is (B, 15) and `layv` is (B, 8) -- one hardware and one layer vector
    per row -- so a single compiled program serves the single-(hw, layer)
    path (rows share both), the layer-stacked path (rows span L layers), and
    the probe-stacked path (rows span H*L (hardware, layer) pairs).
    """
    ok, fo, relo, tl, spv, sx, sy = jax.vmap(
        _prep_one, in_axes=(0, 0, 0, 0, 0)
    )(factors, order_gb, order_dram, hwv, layv)

    consts = hwv[:, H_EMAC:]
    if mode == "jnp":
        ev, trips = reduce_edp_terms(fo, relo, tl, spv, consts)
    elif mode in ("pallas", "interpret"):
        ev, trips = edp_reduce(fo, relo, tl, spv, consts,
                               interpret=mode == "interpret")
    else:
        raise ValueError(f"mode must be jnp|pallas|interpret, got {mode!r}")

    energy, delay, edp = ev[:, 0], ev[:, 1], ev[:, 2]
    used = spv[:, 4]
    feats = jnp.stack(
        [
            tl[:, 0, 1] / hwv[:, H_LBI],
            tl[:, 0, 0] / hwv[:, H_LBW],
            tl[:, 0, 2] / hwv[:, H_LBO],
            jnp.sum(tl[:, 1, :], axis=1) / hwv[:, H_GBE],
            sx / hwv[:, H_MX],
            sy / hwv[:, H_MY],
            *[jnp.log1p(trips[:, j]) for j in range(2 * len(TENSORS))],
            jnp.log1p(used),
            jnp.log1p(layv[:, L_MACS] / used),
        ],
        axis=1,
    )
    inf = jnp.asarray(jnp.inf, energy.dtype)
    # Guard the log10 against invalid rows (inf EDP -> nan under where).
    utility = jnp.where(ok, -jnp.log10(jnp.where(ok, edp, 1.0)), -inf)
    return {
        "valid": ok,
        "energy_pj": jnp.where(ok, energy, inf),
        "delay_cycles": jnp.where(ok, delay, inf),
        "edp": jnp.where(ok, edp, inf),
        "utility": utility,
        "features": feats,
    }


def _bucket(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


def _dtype_scope(dtype: str):
    """Scoped x64 for the float64 engine; float32 needs no scope."""
    if dtype == "float64":
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def _resolve(mode: str | None, dtype: str | None) -> tuple[str, str]:
    on_tpu = jax.default_backend() == "tpu"
    if mode is None:
        mode = "pallas" if on_tpu else "jnp"
    if dtype is None:
        dtype = "float32" if on_tpu else "float64"
    return mode, dtype


def forward_device(
    hw: HardwareConfig,
    mb: MappingBatch,
    layer: ConvLayer,
    mode: str | None = None,
    dtype: str | None = None,
) -> dict[str, jax.Array]:
    """Run the fused program; returns device-resident arrays (no host copy).

    `mode`: "jnp" (default off-TPU), "pallas" (default on TPU), or "interpret"
    (Pallas interpreter -- the kernel body, executed in Python).  `dtype`:
    "float64" (default off-TPU; scoped x64, parity with the NumPy engine) or
    "float32".
    """
    mode, dtype = _resolve(mode, dtype)
    B = len(mb)
    b = _bucket(B)
    # Benign padding rows: all-ones factors are invalid (factorization check)
    # but produce finite arithmetic everywhere (used_pes = 1, trips = 1).
    factors = np.ones((b, N_LEVELS, N_DIMS), np.int64)
    orders = np.tile(np.arange(N_DIMS, dtype=np.int32), (2, b, 1))
    if B:
        factors[:B] = mb.factors
        orders[0, :B] = mb.order_gb
        orders[1, :B] = mb.order_dram
    _count(0 if isinstance(mb, PaddingPool) else B, b)
    to_device = trace.to_device
    with _dtype_scope(dtype):
        out = _forward(
            to_device(factors, dtype),
            to_device(orders[0], jnp.int32),
            to_device(orders[1], jnp.int32),
            to_device(np.broadcast_to(hw_vec(hw), (b, 15)), dtype),
            to_device(np.broadcast_to(layer_vec(layer), (b, 8)), dtype),
            mode=mode,
        )
    return {k: v[:B] for k, v in out.items()}


def _count(rows: int, slots: int) -> None:
    """One forward program launched over `slots` rows, `rows` of them a
    pool's own mappings (`repro.core.trace` counters)."""
    trace.COUNTERS["forward.rows"] += rows
    trace.COUNTERS["forward.slots"] += slots
    trace.dispatched()


def forward_device_stacked(
    hw,
    pools,
    layers,
    mode: str | None = None,
    dtype: str | None = None,
) -> dict[str, jax.Array]:
    """Stacked fused program: L per-run pools, one device dispatch.

    `pools` is a sequence of L `MappingBatch`es (lengths may differ), `layers`
    the matching `ConvLayer`s, and `hw` either ONE `HardwareConfig` shared by
    every run (the layer-batched nested search) or a sequence of L per-run
    configs (the probe-fanout search, where the runs span H hardware probes).
    All pools are packed into one (L*bucket,)-row batch -- the hardware and
    layer vectors ride per row -- and evaluated by the *same* jitted
    `_forward` program as the single-layer path, so per-row results are
    identical to L separate `forward_device` calls.  Returns device-resident
    arrays with a leading (L, B) shape, B = max pool length (rows past a
    pool's own length are padding: invalid, -inf utility).
    """
    mode, dtype = _resolve(mode, dtype)
    L = len(pools)
    assert L == len(layers), (L, len(layers))
    hws = [hw] * L if isinstance(hw, HardwareConfig) else list(hw)
    assert L == len(hws), (L, len(hws))
    B = max((len(p) for p in pools), default=0)
    b = _bucket(B)
    factors = np.ones((L, b, N_LEVELS, N_DIMS), np.int64)
    orders = np.tile(np.arange(N_DIMS, dtype=np.int32), (2, L, b, 1))
    for k, p in enumerate(pools):
        n = len(p)
        if n:
            factors[k, :n] = p.factors
            orders[0, k, :n] = p.order_gb
            orders[1, k, :n] = p.order_dram
    layv = np.repeat(layer_vecs(layers)[:, None, :], b, axis=1)
    hwv = np.repeat(hw_vecs(hws)[:, None, :], b, axis=1)
    _count(sum(len(p) for p in pools if not isinstance(p, PaddingPool)),
           L * b)
    to_device = trace.to_device
    with _dtype_scope(dtype):
        out = _forward(
            to_device(factors.reshape(L * b, N_LEVELS, N_DIMS), dtype),
            to_device(orders[0].reshape(L * b, N_DIMS), jnp.int32),
            to_device(orders[1].reshape(L * b, N_DIMS), jnp.int32),
            to_device(hwv.reshape(L * b, 15), dtype),
            to_device(layv.reshape(L * b, 8), dtype),
            mode=mode,
        )
    return {k: v.reshape(L, b, *v.shape[1:])[:, :B] for k, v in out.items()}


# --- EDP lower bounds (bound-and-prune pass) -------------------------------------

@jax.jit
def _lower_bounds(hwv, layb, caps):
    """(n, L) provable EDP lower bounds from (n, 15) hw vectors + (L, 2)
    [macs, traffic_lb] layer constants + (L, 4, A) sorted spatial-cap tables.
    Reuses the `hw_vec` plumbing of the fused forward: the energy/bandwidth
    block is the same `hwv[:, H_EMAC:]` consts slice `edp_reduce` consumes,
    and the mesh shape + dataflow pins select each config's best-achievable
    PE count from the cap tables.  Same formulas as `bounds.lower_bound` /
    `batch.edp_lower_bounds_batch` (derivation in `timeloop.bounds`)."""
    consts = hwv[:, H_EMAC:]
    e_mac, e_lb, e_noc, e_gb, e_dram, gb_bw, dram_bw = (
        consts[:, j:j + 1] for j in range(7))
    # dataflow variant per config: v = 2*(df_fh==2) + (df_fw==2)
    v = (2 * (hwv[:, H_DFH] == 2.0) + (hwv[:, H_DFW] == 2.0)).astype(jnp.int32)
    capsel = jnp.take(caps, v, axis=1)  # (L, n, A)
    mx, my = hwv[:, H_MX], hwv[:, H_MY]
    ax = jnp.max(jnp.where(capsel <= mx[None, :, None], capsel, 1.0), axis=-1)
    ay = jnp.max(jnp.where(capsel <= my[None, :, None], capsel, 1.0), axis=-1)
    used = (ax * ay).T  # (n, L) best-achievable PE count
    macs, traffic = layb[:, 0][None, :], layb[:, 1][None, :]
    energy = (macs * e_mac + (4.0 * macs + traffic) * e_lb
              + traffic * (e_noc + e_gb + e_dram))
    delay = jnp.maximum(macs / used,
                        jnp.maximum(traffic / gb_bw, traffic / dram_bw))
    return energy * delay


def edp_lower_bounds_device(hws, layers, dtype: str | None = None) -> np.ndarray:
    """(n_hw, L) bound matrix over a hardware pool x layer stack as ONE jitted
    dispatch -- the JAX twin of `bounds.edp_lower_bounds`, parity-pinned in
    tests/test_bounds.py.  The pool axis is padded to the shared power-of-two
    buckets (all-ones padding rows are benign: every bound input is >= 1, and
    an all-ones row selects variant 0 with unit mesh caps), so the compiled
    program is reused across pool sizes; results come back to the host, where
    the prune hook filters plain candidate lists."""
    from repro.timeloop.bounds import layer_bound_vecs, layer_caps

    _, dtype = _resolve(None, dtype)
    n = len(hws)
    b = _bucket(n)
    hwv = np.ones((b, 15), np.float64)
    if n:
        hwv[:n] = hw_vecs(hws)
    with _dtype_scope(dtype):
        out = _lower_bounds(trace.to_device(hwv, dtype),
                            trace.to_device(layer_bound_vecs(layers), dtype),
                            trace.to_device(layer_caps(layers), dtype))
    trace.dispatched()
    return trace.fetch(out)[:n]


# --- host-facing twins of the NumPy engine -------------------------------------

def valid_batch(
    mb: MappingBatch, hw: HardwareConfig, layer: ConvLayer, **kw
) -> np.ndarray:
    """(B,) bool -- exact twin of `batch.valid_batch` / `mapping_is_valid`."""
    return np.asarray(forward_device(hw, mb, layer, **kw)["valid"])


def evaluate_batch(
    hw: HardwareConfig, mb: MappingBatch, layer: ConvLayer, **kw
) -> dict[str, np.ndarray]:
    """Twin of `batch.evaluate_batch` (plus a precomputed `utility` entry)."""
    out = forward_device(hw, mb, layer, **kw)
    return {k: np.asarray(v) for k, v in out.items() if k != "features"}


def features_batch(
    mb: MappingBatch, hw: HardwareConfig, layer: ConvLayer, **kw
) -> np.ndarray:
    """(B, 14) feature matrix -- twin of `batch.features_batch`."""
    return np.asarray(forward_device(hw, mb, layer, **kw)["features"])
