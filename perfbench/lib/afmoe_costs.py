"""Operations and bytes the ``afmoe`` configuration's two kernels need,
from shapes and counts alone (``sizes`` is the configuration file's dict).
Nothing here imports the program.

- The paged attend with grouped heads reads, per key row IN REACH and
  layer, one K and one V row of every K/V head (``num_key_value_heads`` x
  ``head_dim`` values each) whatever the number of query heads, and spends
  per such row and QUERY head a score product and a value product of
  ``head_dim``.  The rows are counted in reach (a window layer's stream
  counts at most ``sliding_window`` of them), whatever the kernel walks:
  one that reads past the window reads a LOW share, never one over 100%.
- The grouped product over ALL experts reads the three matrices of every
  expert that got at least one row, reads and writes each routed row once,
  and spends three H x F products a routed (token, expert) pair.
"""


_ITEMSIZE = 2                     # bf16 weights and pools (assumed.dtype)


def kv_row_bytes(sizes: dict) -> int:
    """K and V of one token and layer."""
    return 2 * int(sizes["num_key_value_heads"]) * int(sizes["head_dim"]) \
        * _ITEMSIZE


def attend_bytes(sizes: dict, rows_in_reach: float) -> float:
    """Pool bytes for ``rows_in_reach`` key rows (summed over streams AND
    layers, each layer's as far as it reaches: the ``decode`` span's
    ``context_tokens_in_reach``)."""
    return float(rows_in_reach) * kv_row_bytes(sizes)


def attend_flops(sizes: dict, rows_in_reach: float,
                 rows_per_stream: int = 1) -> float:
    """Scores and values, 2 FLOPs a multiply-add, for every query head."""
    return float(rows_in_reach) * int(rows_per_stream) \
        * int(sizes["num_attention_heads"]) * 2 * int(sizes["head_dim"]) * 2


def expert_gemm_bytes(sizes: dict, experts_with_rows: float,
                      pairs: float) -> float:
    H, F = int(sizes["hidden_size"]), int(sizes["moe_intermediate_size"])
    b = _ITEMSIZE
    return float(experts_with_rows) * 3 * H * F * b + float(pairs) * 2 * H * b


def expert_gemm_flops(sizes: dict, pairs: float) -> float:
    H, F = int(sizes["hidden_size"]), int(sizes["moe_intermediate_size"])
    return float(pairs) * 6 * H * F


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) over the kernel's time, in
    percent."""
    floor = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
