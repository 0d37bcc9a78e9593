"""Plain reference of the analytical accelerator cost model (arXiv 2010.02075).

Written from the model's stated rules, with nothing taken from the program
under test: it reads plain arrays and numbers, never the program's objects.

    A tensor tile resident at one storage level is refetched from its parent
    once per iteration of every relevant loop at the parent level, and once
    per iteration of every irrelevant loop ordered outside at least one
    relevant loop with factor > 1.  Outputs are read-modify-write: reduction
    loops ordered outside every output-relevant loop force 2*passes - 1
    accesses.
    Energy = macs*e_mac + lb*e_lb + noc*e_noc + gb*e_gb(width) + dram*e_dram
    Delay  = max(macs/used_pes, gb/gb_bandwidth, dram/dram_bandwidth)
    EDP    = Energy * Delay

Rows are mappings: `factors` (N, 5, 6) over levels (lb, sx, sy, gb, dram) and
dims (R, S, P, Q, C, K); `order_gb`, `order_dram` (N, 6) dim indices,
outermost loop first.  `hw` and `layer` map each field to one number or one
(N,) array.  `dtype` is the arithmetic precision: every input is cast to it
and every operation rounds to it.  float64 is the reference; a lower
precision (`ml_dtypes.bfloat16`) is the control that the check must reject.
"""

from __future__ import annotations

import numpy as np

DIMS = ("R", "S", "P", "Q", "C", "K")
LB, SX, SY, GB, DRAM = range(5)
RELEVANT = {  # which loop dims index each tensor
    "W": ("R", "S", "C", "K"),
    "I": ("R", "S", "P", "Q", "C"),
    "O": ("P", "Q", "K"),
}
HW_FIELDS = ("pe_mesh_x", "pe_mesh_y", "lb_input", "lb_weight", "lb_output",
             "gb_entries", "gb_instances", "gb_block", "gb_cluster", "df_fw",
             "df_fh", "dram_bandwidth", "e_mac", "e_lb", "e_noc", "e_gb",
             "e_dram")


def _prod(x):
    """Product over the last axis, one rounding per multiply."""
    out = x[..., 0]
    for i in range(1, x.shape[-1]):
        out = out * x[..., i]
    return out


def _tiles(f, stride, one):
    """[W, I, O] tile sizes from per-dim factors f (N, 6)."""
    r, s, p, q, c, k = (f[:, j] for j in range(6))
    return (r * s * c * k,
            ((p - one) * stride + r) * ((q - one) * stride + s) * c,
            p * q * k)


def _refetches(f, rel, one):
    """Iterations at one level that force a refetch of the child tile.
    `f` (N, 6) factors and `rel` (N, 6) relevance, both in loop order."""
    trips = one
    inner = np.zeros(len(one), bool)  # an active relevant loop lies inside
    for p in reversed(range(6)):
        trips = trips * np.where(rel[:, p] | inner, f[:, p], one)
        inner = inner | (rel[:, p] & (f[:, p] > one))
    return np.where(inner, trips, one)


def _output_passes(f, rel, one):
    """Product of the irrelevant loops ordered outside every active
    output-relevant loop (loop order, as in `_refetches`)."""
    passes = one
    outer = np.zeros(len(one), bool)
    for p in range(6):
        outer = outer | (rel[:, p] & (f[:, p] > one))
        passes = passes * np.where(~rel[:, p] & ~outer, f[:, p], one)
    return passes


def evaluate_rows(factors, order_gb, order_dram, hw: dict, layer: dict,
                  dtype=np.float64) -> dict:
    """Validity (N,) bool and EDP (N,) float64 (inf on invalid rows)."""
    n = len(factors)

    def cast(v):
        return np.broadcast_to(np.asarray(v, np.float64), (n,)).astype(dtype)

    f = np.asarray(factors, np.float64).astype(dtype)
    h = {k: cast(hw[k]) for k in HW_FIELDS}
    dims = np.stack([cast(layer[d]) for d in DIMS], axis=1)
    stride = cast(layer["stride"])
    one = np.ones(n, dtype)
    macs = _prod(dims)

    lb_w, lb_i, lb_o = _tiles(f[:, LB], stride, one)
    gb_w, gb_i, gb_o = _tiles(f[:, LB] * f[:, SX] * f[:, SY] * f[:, GB],
                              stride, one)
    sx, sy = _prod(f[:, SX]), _prod(f[:, SY])
    valid = np.all(_prod(np.moveaxis(f, 1, 2)) == dims, axis=1)
    valid &= (h["df_fw"] != 2) | (f[:, LB, 1] == dims[:, 1])
    valid &= (h["df_fh"] != 2) | (f[:, LB, 0] == dims[:, 0])
    valid &= (lb_w <= h["lb_weight"]) & (lb_i <= h["lb_input"])
    valid &= lb_o <= h["lb_output"]
    valid &= gb_w + gb_i + gb_o <= h["gb_entries"]
    valid &= (sx <= h["pe_mesh_x"]) & (sy <= h["pe_mesh_y"])

    og = np.asarray(order_gb, np.int64)
    od = np.asarray(order_dram, np.int64)
    f_gb = np.take_along_axis(f[:, GB], og, axis=1)
    f_dram = np.take_along_axis(f[:, DRAM], od, axis=1)
    sp = f[:, SX] * f[:, SY]
    sp_all = _prod(sp)

    lb_acc = noc_acc = gb_acc = dram_acc = np.zeros(n, dtype)
    for t, lb_tile, gb_tile in (("W", lb_w, gb_w), ("I", lb_i, gb_i),
                                ("O", lb_o, gb_o)):
        rel = np.array([d in RELEVANT[t] for d in DIMS])
        rel_gb, rel_dram = rel[og], rel[od]
        gb_trips = _refetches(f_gb, rel_gb, one)
        dram_trips = _refetches(f_dram, rel_dram, one)
        sp_rel = _prod(np.where(rel[None, :], sp, one[:, None]))
        rw = rw_d = one
        if t == "O":
            rw = 2 * _output_passes(f_gb, rel_gb, one) - one
            rw_d = 2 * _output_passes(f_dram, rel_dram, one) - one
        fills_lb = lb_tile * gb_trips * dram_trips
        gb_acc = gb_acc + fills_lb * sp_rel * rw
        noc_acc = noc_acc + fills_lb * sp_all * rw
        lb_acc = lb_acc + fills_lb * sp_all * rw
        dram_acc = dram_acc + gb_tile * dram_trips * rw_d
    lb_acc = lb_acc + 4 * macs

    width = h["gb_block"] * h["gb_cluster"]
    e_gb = h["e_gb"] * np.sqrt(width) / width
    energy = (macs * h["e_mac"] + lb_acc * h["e_lb"] + noc_acc * h["e_noc"]
              + gb_acc * e_gb + dram_acc * h["e_dram"])
    with np.errstate(divide="ignore", invalid="ignore"):
        delay = np.maximum(macs / (sx * sy),
                           np.maximum(gb_acc / (width * h["gb_instances"]),
                                      dram_acc / h["dram_bandwidth"]))
    edp = (energy * delay).astype(np.float64)
    return {"valid": valid, "edp": np.where(valid, edp, np.inf)}


def hardware_is_valid(hw: dict, budget: dict) -> bool:
    """The accelerator budget's structural constraints (paper Fig. 7)."""
    return (hw["pe_mesh_x"] * hw["pe_mesh_y"] == budget["num_pes"]
            and hw["lb_input"] + hw["lb_weight"] + hw["lb_output"]
            <= budget["lb_budget"]
            and min(hw["lb_input"], hw["lb_weight"], hw["lb_output"]) >= 1
            and hw["gb_entries"] == budget["gb_entries"]
            and hw["gb_mesh_x"] * hw["gb_mesh_y"] == hw["gb_instances"]
            and hw["pe_mesh_x"] % hw["gb_mesh_x"] == 0
            and hw["pe_mesh_y"] % hw["gb_mesh_y"] == 0
            and 16 % hw["gb_block"] == 0 and 16 % hw["gb_cluster"] == 0
            and hw["df_fw"] in (1, 2) and hw["df_fh"] in (1, 2))
