"""Operations and bytes the ``sdar_moe`` configuration's two kernels need in
a block pass, from shapes and counts alone (``sizes`` is the configuration
file's dict).  Nothing here imports the program.

- The paged attend of a block pass: a stream's ``block_length`` rows x
  ``group`` query heads a K/V head (4 x 8 = 32 query rows) share ONE read
  of the stream's K and V rows in reach, a layer; every one of those query
  rows spends a score product and a value product of ``head_dim`` a key
  row.  Rows in reach are the ``decode`` spans' ``context_tokens_in_reach``
  (the streams' lengths as the host has them after the dispatch, summed
  over layers: a stream whose block is not committed yet counts without the
  block's own rows, which the kernel does read — the share reads that much
  LOW, never over 100%).
- The grouped product over ALL experts reads the three H x F matrices of
  every expert that got at least one row, reads and writes each routed row
  once, and spends three H x F products a routed (row, expert) pair.
"""
from perfbench.lib.afmoe_costs import (attend_bytes, expert_gemm_bytes,
                                       expert_gemm_flops, roofline_share)


def block_length(sizes: dict) -> int:
    return int((sizes.get("assumed") or {}).get("block_length", 4))


def attend_flops(sizes: dict, rows_in_reach: float) -> float:
    """Scores and values, 2 FLOPs a multiply-add, for every query head of
    every row of the block."""
    return float(rows_in_reach) * block_length(sizes) \
        * int(sizes["num_attention_heads"]) * 2 * int(sizes["head_dim"]) * 2


__all__ = ["attend_bytes", "attend_flops", "expert_gemm_bytes",
           "expert_gemm_flops", "roofline_share", "block_length"]
