"""The work of the joint-state offline DP of a multi-tenant edge, from its
shape alone.

Any implementation of ``V_t(j) = min_j' [V_{t-1}(j') + F(j', j)] + w_t(j)``
over ``rows`` independent sites, ``T`` slots, ``J`` joint states and ``N``
services is credited with the same operations and bytes, whatever it fuses
or reorders (the conventions of ``bench.dpwork``):

* operations per row and slot: the holding cost ``w_t(j) = c_t L_j +
  sum_n g_n(j) x_{n,t}`` (2 N + 1 per state: N products and N sums for the
  service term, one product for the rent), the J x J candidate sums, the
  J (J - 1) comparisons of the minimum and the J additions of ``w_t``:
  ``2 J^2 + (2 N + 1) J``;
* bytes: the slot's observations must be read once per row (N int32
  arrival counts and one float32 rent), and the row's float32 cost
  written; the fetch matrix and the frontier stay on chip.
"""
from __future__ import annotations

ARRIVAL_BYTES = 4               # int32 arrivals, one count per service
RENT_BYTES = 4                  # float32 rent, one per site
COST_BYTES_PER_ROW = 4          # float32 optimum


def dp_ops(rows: int, T: int, J: int, N: int) -> int:
    return rows * T * (2 * J * J + (2 * N + 1) * J)


def dp_bytes(rows: int, T: int, J: int, N: int) -> int:
    del J
    return rows * (T * (N * ARRIVAL_BYTES + RENT_BYTES) + COST_BYTES_PER_ROW)


def least_time(rows: int, T: int, J: int, N: int, peaks: dict):
    """``(seconds, bound)``: the larger of operations over the bf16 peak and
    bytes over HBM bandwidth, and which of the two it is."""
    t_ops = dp_ops(rows, T, J, N) / peaks["bf16_flops_per_s"]
    t_bytes = dp_bytes(rows, T, J, N) / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
