"""Work of the cost model's fused forward (`timeloop/batch_jax.py _forward`),
counted from what one mapping row needs, whatever implements it.

One row goes in as its loop factors (5, 6), two loop orders (6,) of int32,
its hardware vector (15,) and its layer vector (8,), and comes out as a
validity flag (bool), energy, delay, EDP and utility, and 14 features.  The
kernel's own packed operand, (67, B/128, 128) with padding, is not counted:
padding is a choice of the implementation, not work the model needs.
"""

from __future__ import annotations

IN_FLOATS = 5 * 6 + 15 + 8
IN_INT32 = 2 * 6
OUT_FLOATS = 4 + 14
OUT_BOOLS = 1

# Arithmetic per row, from the model's equations (compares, selects and
# casts not counted): tiles at LB (13) and at GB with its cumulative factors
# (18 + 13); validity products and sums (24 + 10 + 2); refetch products for
# 3 tensors at 2 levels (36), output passes (12) and read-modify-write (4);
# spatial products (6 + 15 + 5); the four access sums (42 + 2); energy (9),
# delay (5) and EDP (1); features and utility (19).
OPS_PER_ROW = 13 + 31 + 36 + 36 + 12 + 4 + 26 + 44 + 15 + 19


def bytes_per_row(float_bytes: int) -> int:
    return (IN_FLOATS + OUT_FLOATS) * float_bytes + IN_INT32 * 4 + OUT_BOOLS


def least_seconds(rows: int, float_bytes: int, peaks: dict) -> tuple[float,
                                                                      str]:
    """The least time the chip needs for `rows` rows, and which bound sets
    it ("bytes" or "ops")."""
    t_bytes = rows * bytes_per_row(float_bytes) / peaks["hbm_bytes_per_s"]
    t_ops = rows * OPS_PER_ROW / peaks["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
