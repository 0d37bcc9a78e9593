"""Pallas kernel for the per-mapping trip-count / energy reduction.

This is the innermost arithmetic of the analytical cost model (`model.evaluate`
-> `batch.evaluate_batch`): for each candidate mapping, reduce the per-level
loop factors into refetch trip counts (the Timeloop temporal-reuse rule),
read-modify-write passes, and finally the energy / delay / EDP scalars.

The numerics live in `_reduce_rows`, which sees every operand as a list of
same-shaped arrays, one per (level, tensor, loop position) slot, and uses only
elementwise ops and static unrolled loops.  It runs two ways:

  * `reduce_edp_terms` feeds it the columns of full `(B, ...)` arrays -- the
    `jnp` path that CPU CI runs, and the reference the kernel is tested
    against;
  * `edp_reduce(..., interpret=...)` packs the operands lane-major and runs it
    blockwise in the Pallas kernel `_edp_kernel` -- compiled on TPU
    (`interpret=False`), through the Pallas interpreter elsewhere.

Both paths are driven by `repro.timeloop.batch_jax`; see that module for the
packed operand layout.

Operand layout (all leading dim B):

  fo     (B, 2, 6)     loop factors *in loop order* at [gb, dram] level
  relo   (B, 2, 3, 6)  0/1 relevance per [level, tensor(W,I,O), loop position]
  tiles  (B, 2, 3)     [lb, gb] x [W, I, O] tile sizes
  sp     (B, 6)        [sp_rel_W, sp_rel_I, sp_rel_O, sp_all, used_pes, macs]
  consts (B, 7)        [e_mac, e_lb, e_noc, e_gb, e_dram, gb_bw, dram_bw]

`macs` rides with the per-row operands (not a shared constant) because rows of
one batch may belong to *different layers*: the layer-stacked nested search
packs all layers' candidate pools into a single (L*B,)-row program per
hardware probe, so every layer-dependent quantity must be per-row.  The
energy/bandwidth constants are per-row for the same reason one level up: the
probe-fanout nested search stacks the pools of H different *hardware* probes
into one (H*L*B,)-row program, so the hardware-dependent quantities ride per
row too (single-probe callers just broadcast one row).

Outputs:

  ev     (B, 3)        [energy_pj, delay_cycles, edp]
  trips  (B, 6)        refetch trips [W, I, O]@gb then [W, I, O]@dram
                       (feature inputs: `features_batch` takes log1p of these)

Kernel layout: the 67 operand slots above become the rows of one
`(67, B/128, 128)` array, so the pool axis fills the TPU's (8, 128) vector
tiles and every slot of a block is whole vregs; the 9 output slots come back
the same way.  The pool is padded with benign all-ones rows to a multiple of
128 (one block) or, above `_BLOCK_ROWS`, of `_BLOCK_ROWS` (a grid of blocks).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N_DIMS = 6
N_TENSORS = 3
_LANES = 128
_BLOCK_ROWS = 8 * _LANES  # one (8, 128) vreg per operand slot and grid step

# Packed row offsets: fo, relo, tiles, sp, consts.
_FO = 0
_RELO = _FO + 2 * N_DIMS
_TILES = _RELO + 2 * N_TENSORS * N_DIMS
_SP = _TILES + 2 * N_TENSORS
_CONSTS = _SP + 6
_N_IN = _CONSTS + 7
_N_OUT = 3 + 2 * N_TENSORS


def _reduce_rows(fo, relo, tiles, sp, consts):
    """The reduction over per-slot arrays: `fo[level][pos]`,
    `relo[level][tensor][pos]`, `tiles[level][tensor]`, `sp[j]`, `consts[j]`.
    Returns ([energy, delay, edp], six refetch trip arrays).

    Mirrors `repro.timeloop.model.evaluate` / `batch.evaluate_batch` exactly.
    The products over loop positions are unrolled multiplies (loop factors
    are integers, so every order gives the same f64 result)."""
    one = jnp.ones_like(fo[0][0])

    def level_trips(f, r):
        # A loop position counts if it is relevant, or if a relevant loop
        # with factor > 1 sits inside it (Timeloop's temporal-reuse rule);
        # no such loop at all -> one trip.  Walk inner to outer.
        t, inner = one, None
        for p in reversed(range(N_DIMS)):
            rel = r[p] > 0.5
            include = rel if inner is None else rel | inner
            t = t * jnp.where(include, f[p], one)
            active = rel & (f[p] > 1.0)
            inner = active if inner is None else inner | active
        return jnp.where(inner, t, one)

    def passes(f, r):
        # Reduction passes for outputs: irrelevant loops outside all relevant
        # loops with factor > 1.  Walk outer to inner.
        t, outer = one, None
        for p in range(N_DIMS):
            rel = r[p] > 0.5
            active = rel & (f[p] > 1.0)
            outer = active if outer is None else outer | active
            t = t * jnp.where(~rel & ~outer, f[p], one)
        return t

    e_mac, e_lb, e_noc, e_gb, e_dram, gb_bw, dram_bw = consts
    macs = sp[5]

    trips = [
        level_trips(fo[li], relo[li][ti])
        for li in range(2)
        for ti in range(N_TENSORS)
    ]
    rw_gb = 2.0 * passes(fo[0], relo[0][2]) - 1.0
    rw_dram = 2.0 * passes(fo[1], relo[1][2]) - 1.0

    sp_all = sp[3]
    used = sp[4]
    lb_acc = jnp.zeros_like(one)
    noc_acc = jnp.zeros_like(one)
    gb_acc = jnp.zeros_like(one)
    dram_acc = jnp.zeros_like(one)
    for ti in range(N_TENSORS):
        gb_trips = trips[ti]
        dram_trips = trips[N_TENSORS + ti]
        rw = rw_gb if ti == 2 else one
        rw_d = rw_dram if ti == 2 else one
        fills_lb = tiles[0][ti] * gb_trips * dram_trips
        gb_acc += fills_lb * sp[ti] * rw
        noc_acc += fills_lb * sp_all * rw
        lb_acc += fills_lb * sp_all * rw
        dram_acc += tiles[1][ti] * dram_trips * rw_d
    lb_acc += 4.0 * macs

    energy = (
        macs * e_mac
        + lb_acc * e_lb
        + noc_acc * e_noc
        + gb_acc * e_gb
        + dram_acc * e_dram
    )
    delay = jnp.maximum(
        macs / used, jnp.maximum(gb_acc / gb_bw, dram_acc / dram_bw)
    )
    return [energy, delay, energy * delay], trips


def _slots(get):
    """Operand slot lists for `_reduce_rows` from a packed-row getter."""
    fo = [[get(_FO + N_DIMS * li + p) for p in range(N_DIMS)]
          for li in range(2)]
    relo = [[[get(_RELO + N_DIMS * (N_TENSORS * li + ti) + p)
              for p in range(N_DIMS)] for ti in range(N_TENSORS)]
            for li in range(2)]
    tiles = [[get(_TILES + N_TENSORS * li + ti) for ti in range(N_TENSORS)]
             for li in range(2)]
    sp = [get(_SP + j) for j in range(6)]
    consts = [get(_CONSTS + j) for j in range(7)]
    return fo, relo, tiles, sp, consts


def _pack(fo, relo, tiles, sp, consts):
    """(B, ...) operands -> (B, 67), one column per slot (see `_slots`)."""
    n = fo.shape[0]
    return jnp.concatenate(
        [fo.reshape(n, -1), relo.reshape(n, -1), tiles.reshape(n, -1),
         sp, consts], axis=1)


def reduce_edp_terms(fo, relo, tiles, sp, consts):
    """Batched trip-count + energy reduction (see module docstring for
    shapes): the plain-`jnp` path and the kernel's reference."""
    x = _pack(fo, relo, tiles, sp, consts)
    ev, trips = _reduce_rows(*_slots(lambda i: x[:, i]))
    return jnp.stack(ev, axis=1), jnp.stack(trips, axis=1)


def _edp_kernel(x_ref, out_ref):
    ev, trips = _reduce_rows(*_slots(lambda i: x_ref[i]))
    for j, row in enumerate(ev + trips):
        out_ref[j] = row


def edp_reduce(fo, relo, tiles, sp, consts, *, interpret: bool):
    """Pallas dispatch of the reduction, blocked over the pool dim.

    `interpret=False` compiles the kernel for the accelerator;
    `interpret=True` runs its body through the Pallas interpreter (the CPU CI
    path).  The mode is the caller's choice: nothing here falls back."""
    n = fo.shape[0]
    rows = _LANES if n <= _BLOCK_ROWS else _BLOCK_ROWS
    n_pad = -(-n // rows) * rows
    x = _pack(fo, relo, tiles, sp, consts).T
    # Benign padding: all-ones slots give finite arithmetic (trips 1, used 1).
    x = jnp.pad(x, ((0, 0), (0, n_pad - n)), constant_values=1.0)
    x = x.reshape(_N_IN, n_pad // _LANES, _LANES)
    sub = min(n_pad, _BLOCK_ROWS) // _LANES
    out = pl.pallas_call(
        _edp_kernel,
        grid=(n_pad // (sub * _LANES),),
        in_specs=[pl.BlockSpec((_N_IN, sub, _LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((_N_OUT, sub, _LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((_N_OUT, n_pad // _LANES, _LANES),
                                       fo.dtype),
        interpret=interpret,
    )(x)
    out = out.reshape(_N_OUT, n_pad)[:, :n].T
    return out[:, :3], out[:, 3:]
