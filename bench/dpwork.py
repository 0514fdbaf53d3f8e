"""The work of the offline min-plus DP, from its shape alone.

Any implementation of ``J_t(k) = min_k' [J_{t-1}(k') + F(k', k)] + w_t(k)``
over ``rows`` independent instances, ``T`` slots and ``K`` levels is
credited with the same operations and bytes, whatever it fuses or reorders:

* operations per row and slot: the holding cost ``w_t(k) = c_t lv_k +
  g_k x_t`` (3 per level), the K x K candidate sums, the K (K - 1)
  comparisons of the minimum, and the K additions of ``w_t``:
  ``2 K^2 + 3 K``;
* bytes: the slot's observations must be read once per row (an int32
  arrival count and a float32 rent), and the row's float32 cost written;
  the K x K fetch matrix and the frontier stay on chip.
"""
from __future__ import annotations

OBS_BYTES_PER_SLOT = 4 + 4      # int32 arrivals + float32 rent
COST_BYTES_PER_ROW = 4          # float32 optimum


def dp_ops(rows: int, T: int, K: int) -> int:
    return rows * T * (2 * K * K + 3 * K)


def dp_bytes(rows: int, T: int, K: int) -> int:
    del K
    return rows * (T * OBS_BYTES_PER_SLOT + COST_BYTES_PER_ROW)


def least_time(rows: int, T: int, K: int, peaks: dict):
    """``(seconds, bound)``: the larger of operations over the bf16 peak and
    bytes over HBM bandwidth, and which of the two it is."""
    t_ops = dp_ops(rows, T, K) / peaks["bf16_flops_per_s"]
    t_bytes = dp_bytes(rows, T, K) / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
